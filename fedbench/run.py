"""Run one benchmark cell and print its result as the last line.

    python3 fedbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. Both check the
window's results against the plain reference. Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the checkout root, not this directory, leads the import path:
    # fedbench's module names must not shadow the standard library's
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".")
                   != os.path.dirname(os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from fedbench import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"fedbench: {e}; nothing measured", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
