"""Names and hooks that make the FedTest round visible to a profiler.

* **Device scopes.** Every step of the round runs under
  ``jax.named_scope(<phase>)``, one of :data:`PHASES`. The scope becomes
  part of each HLO op's ``op_name`` metadata at trace time, so a
  profiler trace can group device time by phase; it costs nothing when
  the round runs.
* **Host spans.** :func:`span` writes a ``jax.profiler`` annotation
  into the profiler's own trace, on the device trace's clock. The
  drivers mark each round dispatch (:data:`ROUND`, a step span), the
  global evaluation (:data:`GLOBAL_EVAL`) and checkpoint saves
  (:data:`CHECKPOINT`). Outside a profiler session a span is a no-op.
* **Compile counters.** ``jax.monitoring`` listeners, registered once
  at import, count JAX's own compile events per function (tracing,
  lowering, backend compiles including persistent-cache loads) and the
  persistent cache's hits and misses; :func:`compile_stats` returns a
  snapshot. They run only when something compiles.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
from jax import monitoring

PHASES = (SELECT, TRAIN, ATTACK, EXCHANGE, CROSS_TEST, SCORE, AGGREGATE) = (
    "fedtest.select", "fedtest.train", "fedtest.attack", "fedtest.exchange",
    "fedtest.cross_test", "fedtest.score", "fedtest.aggregate")

ROUND = "fedtest.round"
GLOBAL_EVAL = "fedtest.global_eval"
CHECKPOINT = "fedtest.checkpoint"


def span(name: str, step: Optional[int] = None):
    """A host span named ``name`` in the profiler's trace; with ``step``
    a step span (``StepTraceAnnotation``), which the profiler's step
    view reads."""
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


# ------------------------------------------------------- compile counters
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class _CompileCounters:
    """Per function: count and seconds of each compile event. Tracing a
    function traces the jitted functions it calls, so their trace
    events nest inside its own; ``seconds`` is the wall time covered by
    any compile event, which counts each nested instant once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._per_fun: Dict[str, Dict[str, List[float]]] = {
            kind: {} for kind in _DURATIONS.values()}
        self._cache = {name: 0 for name in _CACHE.values()}
        self._covered: List[Tuple[float, float]] = []   # disjoint, sorted
        self._seconds = 0.0

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        kind = _DURATIONS.get(event)
        if kind is None:
            return
        with self._lock:
            c = self._per_fun[kind].setdefault(str(kw.get("fun_name")),
                                               [0, 0.0])
            c[0] += 1
            c[1] += end - start
            self._cover(start, end)

    def on_event(self, event: str, **kw) -> None:
        name = _CACHE.get(event)
        if name is not None:
            with self._lock:
                self._cache[name] += 1

    def _cover(self, start: float, end: float) -> None:
        # merge [start, end) into the disjoint covered intervals; events
        # end in order, so a nested span finds its parent's children
        # already there and the merge stays at the tail of the list
        later = []
        while self._covered and self._covered[-1][1] >= start:
            s, e = self._covered.pop()
            if s > end:             # ended after this span began on
                later.append((s, e))    # another thread: no overlap
                continue
            self._seconds -= e - s
            start, end = min(start, s), max(end, e)
        self._covered.append((start, end))
        self._covered.extend(reversed(later))
        self._seconds += end - start

    def snapshot(self) -> dict:
        with self._lock:
            out = {kind: {f: {"count": n, "seconds": s}
                          for f, (n, s) in funs.items()}
                   for kind, funs in self._per_fun.items()}
            out.update(self._cache)
            out["seconds"] = self._seconds
        return out


_COUNTERS = _CompileCounters()
monitoring.register_event_time_span_listener(_COUNTERS.on_span)
monitoring.register_event_listener(_COUNTERS.on_event)


def compile_stats() -> dict:
    """What this process has compiled so far.

    ``trace``, ``lower`` and ``compile`` map each function's name (as
    JAX reports it: ``f`` for tracing, ``jit(f)`` for lowering and
    compiling) to ``{"count", "seconds"}``; ``compile`` includes loads
    from the persistent compilation cache. ``cache_hits`` and
    ``cache_misses`` count that cache's lookups that found an entry and
    its writes. ``seconds`` is the wall time spent in any of the three,
    each instant counted once.
    """
    return _COUNTERS.snapshot()
