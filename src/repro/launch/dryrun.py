import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

For each combination this script:
  1. builds the production mesh (single-pod 16x16 or multi-pod 2x16x16),
  2. constructs ShapeDtypeStruct stand-ins for params / optimizer state /
     batch / KV-cache (no allocation),
  3. jits the right step function with explicit in_shardings,
  4. ``.lower().compile()`` — any sharding mismatch, unsupported collective
     or compile-time OOM is a bug in the framework,
  5. records ``memory_analysis()`` / ``cost_analysis()`` / parsed
     per-device collective bytes into a JSON artifact for §Dry-run and
     §Roofline of EXPERIMENTS.md.

FLOPs/bytes accounting: XLA's cost analysis counts a while-loop (scan)
body once, NOT multiplied by trip count. Since layer stacks are scanned,
the script also compiles reduced-depth variants (2 and 4 scan iterations)
and extrapolates linearly — exact because scan iterations are identical.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun --resume
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.config import INPUT_SHAPES, TrainConfig
from repro.configs import get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    activation_rules, batch_spec_tree, cache_specs, cache_spec_tree,
    input_specs, model_for, param_sharding_tree, params_and_opt_specs,
    supported, to_named)
from repro.launch.steps import (
    make_decode_step, make_prefill_step, make_train_step)
from repro.roofline import TPU_V5E, model_flops, parse_collectives
from repro.roofline.analysis import (
    collective_bytes_per_device, roofline_terms)
from repro.sharding import logical_rules

ASSIGNED = [a for a in list_configs() if not a.startswith("fedtest-")]


def _layer_period(cfg) -> int:
    from repro.models.decoder import _period
    return _period(cfg) if cfg.family != "encdec" else 1


def _with_depth(cfg, n_units: int):
    """Reduced-depth variant of the same config (n_units scan iterations)."""
    period = _layer_period(cfg)
    kw = {"num_layers": n_units * period}
    if cfg.family == "encdec":
        kw["encoder_layers"] = n_units
    return cfg.replace(**kw)


def _lower_compile(cfg, shape, multi_pod, train_cfg=None,
                   rules_override=None, want_hlo=False, unroll=False):
    """One lower+compile; returns raw per-device cost numbers."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = model_for(cfg, shape, unroll=unroll)
    train_cfg = train_cfg or TrainConfig()
    rules = dict(activation_rules(cfg, shape, mesh))
    if rules_override:
        rules.update(rules_override)

    params, opt_state = params_and_opt_specs(cfg, shape, train_cfg)
    p_spec = param_sharding_tree(cfg, mesh, params)
    batch = input_specs(cfg, shape)
    b_spec = batch_spec_tree(cfg, shape, mesh, batch)

    t0 = time.time()
    with jax.set_mesh(mesh), logical_rules(rules):
        named = lambda spec: to_named(mesh, spec)   # noqa: E731
        if shape.kind == "train":
            step, _ = make_train_step(model, train_cfg)
            o_spec = _opt_specs(opt_state, p_spec)
            lowered = jax.jit(step,
                              in_shardings=(named(p_spec), named(o_spec),
                                            named(b_spec)),
                              donate_argnums=(0, 1)).lower(
                params, opt_state, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model, cache_len=shape.seq_len)
            lowered = jax.jit(step,
                              in_shardings=(named(p_spec),
                                            named(b_spec))).lower(
                params, batch)
        else:
            step = make_decode_step(model)
            cache = cache_specs(cfg, shape)
            c_spec = cache_spec_tree(cfg, shape, mesh, cache)
            lowered = jax.jit(step,
                              in_shardings=(named(p_spec), named(c_spec),
                                            named(b_spec)),
                              donate_argnums=(1,)).lower(
                params, cache, batch)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    rec = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": colls,
        "coll_bytes": collective_bytes_per_device(colls),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "num_chips": mesh.devices.size,
    }
    if want_hlo:
        rec["hlo"] = hlo
    return rec


def extrapolated_costs(cfg, shape, multi_pod, train_cfg=None,
                       rules_override=None, n1: int = 2, n2: int = 4):
    """Linear depth extrapolation of flops / bytes / collective bytes."""
    period = _layer_period(cfg)
    units_full = (cfg.num_layers // period if cfg.family != "encdec"
                  else cfg.num_layers)
    f1 = _lower_compile(_with_depth(cfg, n1), shape, multi_pod, train_cfg,
                        rules_override, unroll=True)
    f2 = _lower_compile(_with_depth(cfg, n2), shape, multi_pod, train_cfg,
                        rules_override, unroll=True)
    out = {}
    for key in ("flops", "bytes", "coll_bytes"):
        delta = (f2[key] - f1[key]) / (n2 - n1)
        out[key] = f1[key] + (units_full - n1) * delta
        out[key + "_per_unit"] = delta
    colls = {}
    for op in set(f1["collectives"]) | set(f2["collectives"]):
        a, b = f1["collectives"].get(op, 0), f2["collectives"].get(op, 0)
        colls[op] = a + (units_full - n1) * (b - a) / (n2 - n1)
    out["collectives"] = colls
    out["extra_compile_s"] = f1["compile_s"] + f2["compile_s"]
    return out


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              train_cfg=None, rules_override=None, want_hlo: bool = False,
              extrapolate: bool = True):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, why = supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    full = _lower_compile(cfg, shape, multi_pod, train_cfg, rules_override,
                          want_hlo=want_hlo)
    if extrapolate:
        costs = extrapolated_costs(cfg, shape, multi_pod, train_cfg,
                                   rules_override)
    else:
        costs = {k: full[k] for k in ("flops", "bytes", "coll_bytes",
                                      "collectives")}

    n_chips = full["num_chips"]
    terms = roofline_terms(costs["flops"], costs["bytes"],
                           costs["coll_bytes"], TPU_V5E, n_chips)
    mf = model_flops(cfg, shape)
    useful = mf / n_chips / max(costs["flops"], 1.0)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "num_chips": n_chips,
        "lower_s": full["lower_s"], "compile_s": full["compile_s"],
        "memory": full["memory"],
        "cost": {"flops_per_device": costs["flops"],
                 "bytes_per_device": costs["bytes"],
                 "raw_full_compile_flops": full["flops"],
                 "extrapolated": extrapolate},
        "collectives": costs["collectives"],
        "collective_bytes_per_device": costs["coll_bytes"],
        "roofline": terms,
        "model_flops_global": mf,
        "useful_flops_ratio": useful,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if want_hlo:
        rec["hlo"] = full["hlo"]
    return rec


def _opt_specs(opt_state, p_spec):
    """m/v mirror param specs; scalar counters replicate."""
    from jax.sharding import PartitionSpec as P

    def build(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in ("m", "v", "mu"):
                    out[k] = p_spec
                elif k == "step":
                    out[k] = P()
                else:
                    out[k] = build(v)
            return out
        return node

    return build(opt_state) if isinstance(opt_state, dict) else opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--resume", action="store_true",
                    help="skip combos whose artifact already exists")
    ap.add_argument("--no-extrapolate", action="store_true",
                    help="skip the depth-extrapolation compiles "
                         "(multi-pod runs only need compile success)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        for arch in ASSIGNED:
            for shape in INPUT_SHAPES:
                for mesh in ("single", "multi"):
                    combos.append((arch, shape, mesh))
    else:
        combos = [(args.arch, args.shape, args.mesh)]

    for arch, shape, mesh in combos:
        tag = f"{arch}__{shape}__{mesh}".replace("/", "_")
        path = os.path.join(args.out, tag + ".json")
        if args.resume and os.path.exists(path):
            print(f"[skip existing] {tag}")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            # roofline extrapolation is a single-pod deliverable; the
            # multi-pod pass proves the "pod" axis shards & compiles.
            extrap = (mesh == "single") and not args.no_extrapolate
            rec = lower_one(arch, shape, mesh == "multi",
                            extrapolate=extrap)
        except Exception as e:  # a failure here is a framework bug
            rec = {"arch": arch, "shape": shape, "mesh": mesh,
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" compute={r['compute_s']:.2e}s "
                     f"mem={r['memory_s']:.2e}s "
                     f"coll={r['collective_s']:.2e}s "
                     f"bn={r['bottleneck']} "
                     f"useful={rec['useful_flops_ratio']:.2f} "
                     f"compile={rec['compile_s']}s")
        print(f"[{status}] {tag}{extra}", flush=True)


if __name__ == "__main__":
    main()
