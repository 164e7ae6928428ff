"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module defines ``read(ctx) -> float | None`` over a
:class:`fedbench.harness.MetricContext`. A reader that finds nothing to
read returns ``None`` and the harness leaves the metric out.
"""
