"""Readings that the check's limits are set from, on the chip.

    python3 fedbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds N] [--fault-seeds N] [--out F]

For each seed, at the cell's own size: the program's first rounds
(through the window's entry point, as a run drives them) against the
plain f32 reference, and against the same reference put in the
program's place in more ways: the control (the reference computed in the
precision below the configuration's, ``reference.control``) on the first
``--control-seeds`` seeds; and on the first ``--fault-seeds`` the faults:
half of every batch left out, one tester's report altered (replaced by
uniform accuracies),
and on several chips the exchange between chips left out (the first
chip's share of the cohort, never reduced). The benchmark's own runs do
none of this. Prints one JSON line per seed (with each kind's sorted
round-1 score gaps of the clients) and, last, the largest program
reading and the smallest of each other kind per number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell_name: str, seed_list, require_chip: bool = True,
             root=None, log=print, control_seeds=None, fault_seeds=None):
    from fedbench import harness

    cell = harness.load_cell(cell_name, root or harness.ROOT)
    with harness.program_precision(cell):
        yield from _readings(cell, seed_list, require_chip, log,
                             control_seeds, fault_seeds)


def _readings(cell, seed_list, require_chip, log, control_seeds,
              fault_seeds):
    import gc

    import jax

    from fedbench import check, harness
    from fedbench import traffic as traffic_mod
    from fedbench.reference import control

    devices = (harness.find_chips(cell.chips) if require_chip
               else jax.devices()[:cell.chips])
    program = harness.Program(cell, devices)
    rounds = cell.work["check"]["rounds"]
    n_seeds = len(seed_list)
    kinds = {"control": (dict(prec=control(cell.cfg)),
                         control_seeds or n_seeds),
             "half_batch": (dict(fault="half_batch"), fault_seeds or n_seeds),
             "altered_report": (dict(fault="altered_report"),
                                fault_seeds or n_seeds)}
    if cell.chips > 1:
        kinds["no_exchange"] = (dict(fault="no_exchange",
                                     exchange_parts=cell.chips),
                                fault_seeds or n_seeds)
    for i, seed in enumerate(seed_list):
        t0 = time.perf_counter()
        s = harness.seeds(seed)
        traffic = traffic_mod.make(cell.work["generator"],
                                   cell.work["traffic"], s["data"])
        data = program.data(traffic)
        state = program.state(harness.weights_for(program, s["weights"]),
                              jax.random.PRNGKey(s["run"]))
        state, prog = harness.drive_first_rounds(program, state, data,
                                                 rounds)
        del state, data
        gc.collect()
        ref = harness.reference_rounds(cell, program.abstract, s, traffic,
                                       rounds)
        out = {"seed": seed, "program": check.numbers(prog, ref)}
        gaps = {"program": check.score_gaps1(prog, ref)}
        for kind, (kw, first) in kinds.items():
            if i >= first:
                continue
            other = harness.reference_rounds(cell, program.abstract, s,
                                             traffic, rounds, **kw)
            out[kind] = check.numbers(other, ref)
            gaps[kind] = check.score_gaps1(other, ref)
        out["gaps1"] = {k: sorted(float(x) for x in v)
                        for k, v in gaps.items()}
        out["seconds"] = time.perf_counter() - t0
        out["excluded_leaves"] = check.leaf_gap(ref["updateN"],
                                                ref["updateN"])["excluded"]
        log(json.dumps(out))
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on the first N seeds only")
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="read the faults on the first N seeds only")
    ap.add_argument("--out", default=None,
                    help="also append the JSON lines to this file")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".")
                   != os.path.dirname(os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    out = open(args.out, "a") if args.out else None

    def log(line):
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    rows = list(readings(args.workload,
                         [int(x) for x in args.seeds.split(",")], log=log,
                         control_seeds=args.control_seeds,
                         fault_seeds=args.fault_seeds))
    summary = {}
    for number in rows[0]["program"]:
        summary[number] = {"program_max": max(r["program"][number]
                                              for r in rows)}
        for kind in rows[0]:
            if kind in ("seed", "program", "seconds", "excluded_leaves",
                        "gaps1"):
                continue
            summary[number][f"{kind}_min"] = min(r[kind][number]
                                                 for r in rows if kind in r)
    log(json.dumps({"summary": summary, "seeds": len(rows)}))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
