"""Reduce a profiler trace to device busy and idle time, per-kernel and
collective device time, and the breakdown of top ops and idle gaps.

A :class:`Trace` holds the device operations of each chip (the
``XLA Ops`` line of every ``/device:TPU:<i>`` plane) and the harness's
host annotations (events named ``fedbench.*`` on host planes), all in
nanoseconds on the profiler's one clock. :func:`load` reads it from an
``.xplane.pb`` file, :func:`from_dict` from plain data (the tests'
synthetic traces); :func:`reduce` does the arithmetic.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start ns, duration ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ANNOTATION = "fedbench."
WINDOW = "fedbench.window"
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]
    host: List[Event]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the chips
    devices: int
    op_s: Dict[str, float]              # HLO instruction -> summed over chips
    collective_s: float                 # summed over chips
    idle_gaps: List[Tuple[str, float]]  # longest first, named by host span
    windows: int                        # number of window annotations

    def kernel_s(self, kernel: str) -> Optional[float]:
        """Device time of every call of ``kernel`` (its instructions are
        named ``kernel``, ``kernel.1``, ...), summed over the chips."""
        t = sum(v for k, v in self.op_s.items() if base_name(k) == kernel)
        return t if t else None

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / self.devices) for k, v in ops]


def load(path: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``path`` (a file or the
    directory ``jax.profiler.trace`` wrote)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(ANNOTATION))
    return Trace(devices, host)


def from_dict(d: dict) -> Trace:
    return Trace({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                 [tuple(e) for e in d["host"]])


def instruction(op: str) -> str:
    """The HLO instruction's name: the TPU trace names an op by its HLO
    text, ``%fusion.12 = f32[...] fusion(...)``."""
    m = re.match(r"\s*%?([^\s=%]+)\s*=", op)
    return m.group(1) if m else op


def base_name(op: str) -> str:
    """``weighted_aggregate.3`` -> ``weighted_aggregate``."""
    return re.sub(r"(\.\d+)+$", "", instruction(op))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _host_at(host: List[Event], s: float, e: float) -> str:
    """The innermost harness annotation that covers most of [s, e)."""
    best, best_cover, best_len = "host", 0.0, float("inf")
    for name, hs, hd in host:
        if name == WINDOW:
            continue
        cover = min(e, hs + hd) - max(s, hs)
        if cover > best_cover or (cover == best_cover > 0
                                  and hd < best_len):
            best, best_cover, best_len = name, cover, hd
    return best


def reduce(trace: Trace, gaps: int = 10) -> Summary:
    """Summarise the device work inside the harness's window annotation
    (the whole trace where there is none)."""
    windows = [(s, s + d) for n, s, d in trace.host if n == WINDOW]
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        evs = [e for ops in trace.devices.values() for e in ops]
        lo = min((s for _, s, _ in evs), default=0.0)
        hi = max((s + d for _, s, d in evs), default=0.0)
    op_s: Dict[str, float] = {}
    busy, coll, idle = [], 0.0, []
    for dev, ops in sorted(trace.devices.items()):
        spans = []
        for name, s, d in ops:
            s0, e0 = max(s, lo), min(s + d, hi)
            if e0 <= s0:
                continue
            spans.append((s0, e0))
            key = instruction(name)
            op_s[key] = op_s.get(key, 0.0) + (e0 - s0) * 1e-9
            if base_name(key).startswith(COLLECTIVES):
                coll += (e0 - s0) * 1e-9
        union = _union(spans)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if dev == min(trace.devices):
            edges = [lo] + [x for se in union for x in se] + [hi]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    idle.sort(key=lambda se: se[0] - se[1])
    named = [(_host_at(trace.host, s, e), (e - s) * 1e-9)
             for s, e in idle[:gaps]]
    n = max(len(trace.devices), 1)
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / n if busy else 0.0,
                   devices=len(trace.devices), op_s=op_s,
                   collective_s=coll, idle_gaps=named,
                   windows=len(windows))
