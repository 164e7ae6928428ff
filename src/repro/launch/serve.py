"""Batched serving driver: prefill a prompt batch, then decode tokens.

CPU-scale by default (reduced config); the full configs are exercised via
the dry-run. Serves any assigned decoder arch:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \\
      --batch 4 --prompt-len 64 --gen 16

Serve-while-training (DESIGN.md §9): with ``--ckpt-dir`` the server
polls the training run's :class:`~repro.checkpoint.CheckpointManager`
for the newest full-round-state checkpoint and serves its global
params — atomic saves guarantee it never reads a torn file:

  PYTHONPATH=src python -m repro.launch.serve --arch fedtest-mlp --smoke \\
      --ckpt-dir experiments/ckpt --wait-secs 60
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.config import reduce_for_smoke
from repro.configs import get_config
from repro.core.engine import RoundState
from repro.core.scoring import init_scores
from repro.models import build_model
from repro.models.frontend_stub import stub_embeddings
from repro.utils.compile_cache import enable_compile_cache


def load_serving_params(mgr: CheckpointManager, model, arch: str = None,
                        wait_secs: float = 0.0, poll_s: float = 0.5):
    """The serve-while-training read path: poll ``mgr`` until a
    checkpoint exists (up to ``wait_secs``), then restore the newest
    loadable one and return ``(global_params, step)``.

    The trainer checkpoints the complete ``RoundState``; the manifest
    written next to it carries the client count and architecture, so
    the reader rebuilds the state template without needing the
    training run's ``FedConfig``, and refuses to serve weights from a
    different arch.
    """
    deadline = time.time() + wait_secs
    while mgr.latest_step() is None:
        if time.time() >= deadline:
            raise FileNotFoundError(
                f"no checkpoint appeared in {mgr.directory} within "
                f"{wait_secs:.0f}s")
        time.sleep(poll_s)
    manifest = mgr.read_manifest() or {}
    saved_arch = manifest.get("arch")
    if arch is not None and saved_arch is not None and saved_arch != arch:
        raise SystemExit(
            f"checkpoint dir holds arch {saved_arch!r}, server was "
            f"asked to serve {arch!r} — refusing")
    num_users = int(manifest.get("fed", {}).get("num_users", 1))

    def abstract_state(key):
        pk, rk = jax.random.split(key)
        return RoundState(global_params=model.init(pk),
                          scores=init_scores(num_users),
                          round_idx=jnp.zeros((), jnp.int32), key=rk)

    template = jax.eval_shape(abstract_state,
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    state, step = mgr.restore_with_step(template)
    return state.global_params, step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve the newest checkpoint from a (possibly "
                         "still-running) training run instead of fresh "
                         "init")
    ap.add_argument("--wait-secs", type=float, default=0.0,
                    help="poll --ckpt-dir this long for a first "
                         "checkpoint before giving up")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg).replace(dtype="float32")
    if cfg.family == "cnn":
        raise SystemExit("cnn has no serving path")
    model = build_model(cfg, max_target_positions=args.prompt_len
                        + args.gen + 1)
    key = jax.random.PRNGKey(args.seed)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        params, step = load_serving_params(mgr, model, arch=cfg.name,
                                           wait_secs=args.wait_secs)
        print(f"serving round-{step} weights from {args.ckpt_dir}")
    else:
        params = model.init(key)

    B, S = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(
        jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patches"] = stub_embeddings(cfg, B, jax.random.fold_in(key, 2),
                                           dtype=model.dtype)
    if cfg.family == "encdec":
        batch["frames"] = stub_embeddings(cfg, B, jax.random.fold_in(key, 2),
                                          dtype=model.dtype)

    cap = S + args.gen + (cfg.num_patches if cfg.family == "vlm" else 0) + 1
    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=cap))
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    def sample(logits, k):
        if args.temperature <= 0:
            return jnp.argmax(logits[:, -1], -1)
        return jax.random.categorical(k, logits[:, -1] / args.temperature)

    # key itself already seeded model.init — draw the first token from a
    # folded stream (9; 10+i cover the rest of the generation loop)
    toks = sample(logits, jax.random.fold_in(key, 9))[:, None].astype(
        jnp.int32)
    out = [toks]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, toks)
        toks = sample(logits, jax.random.fold_in(key, 10 + i)
                      )[:, None].astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={B} prompt={S} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({B*S/max(t_prefill,1e-9):.0f} tok/s)")
    print(f"decode : {t_decode*1e3:.1f} ms "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.1f} tok/s)")
    print("sample tokens:", gen[0, :12].tolist())


if __name__ == "__main__":
    main()
