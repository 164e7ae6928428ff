"""Run the FedTest round on TPU chips through the training entry points.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four chips of one host

One chip: the paper's CNN at its published widths on ``cifar_like``
(20 users, 5 testers, 3 ``random_weights`` attackers, the ``fedtest``
aggregator), once per round and once as a scanned two-round program;
``qwen2-0.5b`` at its published widths on the ``lm`` data; then the
Pallas kernels against their references at real sizes. Four chips: the
pod round of ``launch/federated.py`` (ring and allgather, one client per
chip) and the population tier's cohort sharded over the chips, each
against the same run on one chip.

The script drives the code that ``python -m repro.launch.train`` and
``python -m repro.launch.federated`` run, with random weights from a
seed. Every phase checks its own results and the first failure ends the
run with a non-zero exit. The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU (for example under ``JAX_PLATFORMS=cpu``) the script exits
non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# the paper's CNN experiment (configs/fedtest_cnn.py widths), CLI
# defaults for the local steps; 6 rounds divide into scan chunks of 2
CNN_ARGV = ["--arch", "fedtest-cnn", "--dataset", "cifar_like",
            "--users", "20", "--testers", "5", "--malicious", "3",
            "--attack", "random_weights", "--aggregator", "fedtest",
            "--batch", "32", "--rounds", "6"]
# qwen2-0.5b at published widths, bf16. Four users do not fit one v5e
# chip: the compiled round needs 18.2 GB at batch 4 (memory_analysis of
# the v5e compile), so two users, who are also the round's testers
LM_ARGV = ["--arch", "qwen2-0.5b", "--dataset", "lm", "--users", "2",
           "--testers", "2", "--batch", "8", "--rounds", "3"]
# the pod and population CI smokes of launch/federated.py
POD_ARGV = ["--clients", "4", "--rounds", "3", "--attack", "sign_flip",
            "--malicious", "1"]
POPULATION_ARGV = ["--clients", "4", "--population", "4096",
                   "--cohort", "32", "--rounds", "3",
                   "--attack", "sign_flip", "--malicious", "820",
                   "--testers", "8", "--testers-from-cohort",
                   "--local-steps", "4", "--batch", "8"]

# kernel checks: [C, M] aggregation operands, qwen2-0.5b attention heads
AGG_SHAPE = (20, 2 ** 22)
FLASH_SHAPE = dict(B=1, S=2048, Hq=14, Hkv=2, D=64)
# f32 reductions of 20 terms against an f32 reference at "highest"
# matmul precision; bf16 attention outputs against the f32 oracle
AGG_TOL = 1e-5
FLASH_TOL = 2e-2
# four chips against one: weights, scores and malicious_weight, each in
# [0, 1]; 0.02 is about one of a tester's 64 eval samples flipping
PARITY_TOL = 0.02

_KERNEL_CALL = re.compile(r"%([A-Za-z_]+?)(?:\.\d+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"')


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def pallas_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels in a compiled program's HLO."""
    return set(_KERNEL_CALL.findall(hlo_text))


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- one chip
def run_trainer(name: str, argv, kernels, chance) -> dict:
    """Build the ``launch.train`` run for ``argv``, compile its first
    driver program, train, and check the trajectory: finite losses, one
    trace, the Pallas ``kernels`` in the compiled round and, where
    ``chance`` is given, a final global accuracy above it."""
    import jax
    import numpy as np

    from repro.launch import train

    args = train.build_parser().parse_args(argv)
    run = train.build_run(args)
    trainer, data, fed = run.trainer, run.data, run.fed
    rpc = trainer.rounds_per_call
    key = jax.random.PRNGKey(fed.seed)

    state = trainer.init(key)
    t0 = time.perf_counter()
    compiled = trainer.compile_driver(state, data)
    compile_s = time.perf_counter() - t0
    found = pallas_kernels(compiled.as_text())
    log(f"[{name}] compile {compile_s:.2f} s; Pallas kernels in the "
        f"round: {sorted(found)}")
    check(set(kernels) <= found,
          f"{name}: compiled round lacks Pallas {set(kernels) - found}")
    mem = compiled.memory_analysis()
    log(f"[{name}] round memory: arguments "
        f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB")

    t0 = time.perf_counter()
    state, first = trainer.run(key, data, rounds=rpc, state=state,
                               verbose=True)
    jax.block_until_ready(state)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, rest = trainer.run(key, data, state=state, verbose=True)
    jax.block_until_ready(state)
    steady_s = (time.perf_counter() - t0) / (fed.rounds - rpc)
    log(f"[{name}] first call ({rpc} round(s), global-eval compile "
        f"included) {first_s:.3f} s; steady {steady_s:.4f} s per round "
        "(global eval included)")

    losses = first["local_loss"] + rest["local_loss"]
    acc = rest["global_accuracy"][-1]
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    check(0.0 <= acc <= 1.0, f"{name}: global accuracy {acc}")
    if chance is not None:
        check(acc > chance, f"{name}: final global accuracy {acc:.4f} is "
              f"not above chance {chance:.4f}")
    check(trainer.num_traces == 1,
          f"{name}: round traced {trainer.num_traces} times")
    return {"compile_s": compile_s, "first_call_s": first_s,
            "steady_s_per_round": steady_s, "final_accuracy": acc,
            "local_loss": losses,
            "malicious_weight": (first["malicious_weight"]
                                 + rest["malicious_weight"])}


def max_err(got, want) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def compiled_kernels(fn, *args) -> set:
    import jax
    return pallas_kernels(jax.jit(fn).lower(*args).compile().as_text())


def check_kernels() -> None:
    """Each kernel on the chip against its ``ref.py`` on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dequant_aggregate.ops import dequant_aggregate
    from repro.kernels.dequant_aggregate.ref import dequant_aggregate_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.weighted_aggregate.ops import weighted_aggregate
    from repro.kernels.weighted_aggregate.ref import weighted_aggregate_ref

    k = jax.random.split(jax.random.PRNGKey(0), 8)
    C, M = AGG_SHAPE
    chunk = 256
    w = jax.nn.softmax(jax.random.normal(k[0], (C,)))
    x = jax.random.normal(k[1], (C, M), jnp.float32)
    q = jax.random.randint(k[2], (C, M), -127, 128, jnp.int8)
    s = jax.random.uniform(k[3], (C, M // chunk), jnp.float32, 1e-4, 1e-2)
    b, S, hq, hkv, d = (FLASH_SHAPE[n] for n in ("B", "S", "Hq", "Hkv", "D"))
    fq = jax.random.normal(k[4], (b, S, hq, d), jnp.bfloat16)
    fk = jax.random.normal(k[5], (b, S, hkv, d), jnp.bfloat16)
    fv = jax.random.normal(k[6], (b, S, hkv, d), jnp.bfloat16)

    cases = [
        ("weighted_aggregate", AGG_TOL,
         lambda x, w: weighted_aggregate(x, w, impl="pallas"),
         weighted_aggregate_ref, (x, w)),
        ("dequant_aggregate", AGG_TOL,
         lambda w, s, q: dequant_aggregate(w, s, q, chunk=chunk,
                                           impl="pallas"),
         lambda w, s, q: dequant_aggregate_ref(w, s, q, chunk), (w, s, q)),
        ("flash_attention", FLASH_TOL,
         lambda q, k, v: flash_attention(q, k, v, impl="pallas"),
         attention_ref, (fq, fk, fv)),
    ]
    for name, tol, kernel, ref, args in cases:
        t0 = time.perf_counter()
        check(name in compiled_kernels(kernel, *args),
              f"kernel {name}: its Pallas path has no {name} kernel")
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        scale = max(1.0, float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
        err = max_err(got, want)
        log(f"[kernel {name}] shapes {[a.shape for a in args]}: max |err| "
            f"{err:.3e}, tolerance {tol * scale:.3e} "
            f"({time.perf_counter() - t0:.1f} s with compiles)")
        check(err <= tol * scale, f"kernel {name}: max |err| {err:.3e} "
              f"exceeds {tol * scale:.3e}")


def one_chip() -> dict:
    phases = {}
    t0 = time.perf_counter()
    for rpc in (1, 2):
        name = f"cnn rounds_per_call={rpc}"
        phases[name] = run_trainer(
            name, CNN_ARGV + ["--rounds-per-call", str(rpc)],
            kernels=("weighted_aggregate",), chance=0.1)
    phases["cnn_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    phases["qwen2-0.5b"] = run_trainer(
        "qwen2-0.5b", LM_ARGV,
        kernels=("flash_attention", "weighted_aggregate"), chance=None)
    phases["lm_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    check_kernels()
    phases["kernels_s"] = time.perf_counter() - t0
    return phases


# ------------------------------------------------------------- four chips
def collectives(hlo_text: str) -> dict:
    ops = ("all-gather", "all-reduce", "collective-permute",
           "reduce-scatter", "all-to-all")
    return {op: len(re.findall(rf"= [^\n]*\b{op}(?:-start)?\(", hlo_text))
            for op in ops}


def compare(name: str, four: dict, one: dict) -> dict:
    errs = {k: max(max_err(a, b) for a, b in zip(four[k], one[k]))
            for k in four}
    log(f"[{name}] 4 chips vs 1 chip, max |diff| over rounds: "
        + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
        + f" (tolerance {PARITY_TOL})")
    check(all(v <= PARITY_TOL for v in errs.values()),
          f"{name}: 4-chip run differs from the 1-chip run: {errs}")
    return errs


def check_spread(name: str, compiled) -> dict:
    """The round is one program partitioned over four chips that
    exchanges through collectives."""
    import jax
    text = compiled.as_text()
    parts = re.search(r"num_partitions=(\d+)", text)
    parts = int(parts.group(1)) if parts else 1
    colls = collectives(text)
    out_devs = sorted({len(s.device_set) for s in
                       jax.tree_util.tree_leaves(compiled.output_shardings)})
    log(f"[{name}] program partitions {parts}, outputs on {out_devs} "
        f"devices, collectives {colls}")
    check(parts == 4 and out_devs == [4],
          f"{name}: round not spread over 4 devices")
    check(sum(colls.values()) > 0, f"{name}: no collectives in the HLO")
    return {"partitions": parts, "collectives": colls}


def pod_vs_local(exchange: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FederatedTrainer
    from repro.core.engine import round_keys
    from repro.core.scoring import init_scores
    from repro.data import sample_client_batches
    from repro.launch import federated

    args = federated.build_parser().parse_args(
        POD_ARGV + ["--exchange", exchange])
    pod = federated.build_pod(args, federated.client_mesh(4))
    name = f"pod {exchange}"

    four = {"weights": [], "scores": [], "malicious_weight": []}
    for r, _, m in federated.pod_rounds(pod, args.rounds, args.seed):
        for k in four:
            four[k].append(np.asarray(m[k]))
        log(f"[{name}] round {r + 1}: mal_w "
            f"{float(m['malicious_weight']):.4f} weights "
            f"{np.round(np.asarray(m['weights']), 4).tolist()}")

    # the same schedule on the local backend, on one chip
    trainer = FederatedTrainer(pod.model, pod.fed, pod.train,
                               eval_batch=64)
    state = trainer.init(jax.random.PRNGKey(args.seed))._replace(
        global_params=pod.model.init(jax.random.PRNGKey(args.seed)),
        key=jax.random.PRNGKey(args.seed + 1))
    one = {k: [] for k in four}
    for _ in range(args.rounds):
        state, m = trainer.run_round(state, pod.data)
        for k in one:
            one[k].append(np.asarray(m[k]))
    check(all(leaf.devices() == {jax.devices()[0]} for leaf in
              jax.tree_util.tree_leaves(state.global_params)),
          f"{name}: the local run left chip 0")
    errs = compare(name, four, one)

    fed, data = pod.fed, pod.data
    key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), 0)
    bx, by = sample_client_batches(round_keys(key).batch, data.train,
                                   fed.local_steps, pod.train.batch_size)
    params = pod.model.init(jax.random.PRNGKey(args.seed))
    call = (params, init_scores(fed.num_users), bx, by,
            data.test.xs[:, :64], data.test.ys[:, :64], key,
            jnp.asarray(0, jnp.int32))
    compiled = pod.round_fn.lower(*call).compile()
    spread = check_spread(name, compiled)
    client_devs = len(compiled.input_shardings[0][2].device_set)
    log(f"[{name}] client batches sharded over {client_devs} devices")
    check(client_devs == 4, f"{name}: client batches on {client_devs} "
          "devices")
    want = "collective-permute" if exchange == "ring" else "all-gather"
    check(spread["collectives"][want] > 0,
          f"{name}: no {want} in the HLO")
    return {"max_diff": errs, **spread}


def population_vs_one_chip() -> dict:
    import jax
    import numpy as np

    from repro.launch import federated

    args = federated.build_parser().parse_args(POPULATION_ARGV)
    name = "population"
    runs = {}
    for label, mesh in (("four", federated.client_mesh(4)), ("one", None)):
        _, data, trainer = federated.build_population(args, mesh)
        state = trainer.init(jax.random.PRNGKey(args.seed))
        traj = {"weights": [], "scores": [], "malicious_weight": []}
        for r in range(args.rounds):
            state, m = trainer.run_round(state, data)
            for k in traj:
                traj[k].append(np.asarray(m[k]))
            log(f"[{name} {label}] round {r + 1}: mal_w "
                f"{float(m['malicious_weight']):.4f}")
        runs[label] = (traj, trainer, data, state)
    errs = compare(name, runs["four"][0], runs["one"][0])

    _, trainer, data, state = runs["four"]
    spread = check_spread(name, trainer.compile_driver(state, data))
    _, one_trainer, one_data, one_state = runs["one"]
    one = one_trainer.compile_driver(one_state, one_data)
    one_devs = {len(s.device_set) for s in
                jax.tree_util.tree_leaves(one.output_shardings)}
    check(one_devs == {1}, f"{name}: 1-chip run spans {one_devs} devices")
    return {"max_diff": errs, **spread}


def four_chips() -> dict:
    phases = {}
    for exchange in ("ring", "allgather"):
        t0 = time.perf_counter()
        phases[f"pod_{exchange}"] = pod_vs_local(exchange)
        phases[f"pod_{exchange}"]["seconds"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    phases["population"] = population_vs_one_chip()
    phases["population"]["seconds"] = time.perf_counter() - t0
    return phases


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips, each "
                         "against the same run on one chip")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind}, {len(devices)} chip(s); compile "
        f"cache {jax.config.jax_compilation_cache_dir}")

    t0 = time.perf_counter()
    try:
        phases = four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    log("summary: " + json.dumps(phases, default=float))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
