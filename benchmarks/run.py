"""Benchmark harness — one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows. Fast mode is the default
(CPU-budget scales); set REPRO_BENCH_FULL=1 for paper-scale runs.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run kernels    # one suite

Suites listed in ``JSON_SUITES`` additionally dump their rows as
``BENCH_<suite>.json`` (machine-readable: name, us_per_call, and any
structured extras such as GB/s and roofline fraction) — the perf
trajectory artifact CI uploads per commit.
"""
import json
import sys
import time

from benchmarks import common
from repro.utils.compile_cache import enable_compile_cache

SUITES = [
    ("kernels", "benchmarks.bench_kernels"),          # kernel micro
    ("crosstest", "benchmarks.bench_crosstest"),      # K×N eval fast path
    ("aggregation", "benchmarks.bench_aggregation"),  # FedTest server op
    ("comm", "benchmarks.bench_comm"),                # Sec. V-A accounting
    ("population", "benchmarks.bench_population"),    # cohort N-sweep (§11)
    ("roofline", "benchmarks.bench_roofline"),        # dry-run artifacts
    ("score_power", "benchmarks.bench_score_power"),  # Sec. V-B ablation
    ("testers", "benchmarks.bench_testers"),          # Sec. V-C ablation
    ("faults", "benchmarks.bench_faults"),            # dropout sweep (§9)
    ("convergence", "benchmarks.bench_convergence"),  # Figs. 4-5
]

JSON_SUITES = {"aggregation", "kernels", "crosstest", "population",
               "comm"}


def main() -> int:
    enable_compile_cache()
    want = set(sys.argv[1:])
    failed = []
    print("name,us_per_call,derived")
    for name, module in SUITES:
        if want and name not in want:
            continue
        common.ROWS.clear()
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
        except Exception as e:  # keep the harness alive per-suite...
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            failed.append(name)
        if name in JSON_SUITES and common.ROWS:
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump(common.ROWS, f, indent=1)
            print(f"# wrote {path} ({len(common.ROWS)} rows)", flush=True)
        print(f"# suite {name} done in {time.time() - t0:.0f}s", flush=True)
    if failed:
        # ...but never exit 0: a crashed JSON suite would leave the
        # committed BENCH_*.json in the worktree and the perf gate
        # would silently compare the baseline against itself
        print(f"# FAILED suites: {failed}", flush=True)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
