"""Chip benchmark of the FedTest round (see PERF.md and BENCHMARK.json).

One run drives one cell (a model configuration under a federated
workload) through the trainer's public round entry point:

    python3 fedbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, workload or per-layer
metric lives in a file of its own under ``configs/``, ``workloads/`` and
``metrics/``; the harness finds each by the name in ``BENCHMARK.json``.
"""
