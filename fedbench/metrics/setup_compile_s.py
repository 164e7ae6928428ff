"""Seconds the run's process spent tracing, lowering and compiling, or
loading compiled programs from JAX's persistent cache, each instant
counted once (``repro.utils.tracing.compile_stats``).

The program's counters run from its import on, and the harness reads
its metrics after the window: so this is set-up's compiling plus the
global evaluation's first call after the window (a cache load of
0.02 s on a TPU v5e). A program without the counters reads nothing.
"""


def read(ctx):
    try:
        from repro.utils.tracing import compile_stats
    except ImportError:
        return None
    return compile_stats()["seconds"]
