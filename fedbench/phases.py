"""Device time of each phase of the FedTest round, from a profiler trace.

The program runs each step of the round under a ``jax.named_scope``
whose name starts with ``fedtest.`` (``repro.utils.tracing.PHASES``),
and marks its host work with spans of such names (``fedtest.round``
around each round's dispatch, ``fedtest.global_eval``,
``fedtest.checkpoint``). This module reads them next to the harness's
``fedbench.*`` annotations:

* each busy instant of a chip goes to the innermost op covering it (a
  loop's body ops win over the ``while`` that holds them), and that op
  to the ``fedtest.*`` component of its scope path, or to ``unscoped``;
  so the phases add up to the busy time of ``fedbench.trace.reduce``.
  The TPU trace names an op by its HLO instruction only, so the scope
  paths come from the compiled module's HLO text (``op_name``);
* each idle gap is named by the path of the host spans covering it,
  outermost first (``fedbench.dispatch/fedtest.round``).

From a trace directory that ``jax.profiler`` wrote on the chip and the
HLO text of the round it ran (``trainer.compile_driver(state,
data).as_text()``):

    python3 -m fedbench.phases <trace dir> <HLO text file>
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

from fedbench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW, Event,
                            _union, instruction)

PREFIXES = ("fedbench.", "fedtest.")
ROUND = "fedtest.round"
UNSCOPED = "unscoped"

Op = Tuple[str, float, float, str]      # name, start ns, duration ns, scope


@dataclasses.dataclass
class PhaseTrace:
    devices: Dict[str, List[Op]]
    host: List[Event]                   # fedbench.* and fedtest.* spans


@dataclasses.dataclass
class Split:
    window_s: float
    busy_s: float                       # mean over the chips
    rounds: int                         # fedtest.round spans in the window
    phase_s: Dict[str, float]           # mean over the chips
    idle_gaps: List[Tuple[str, float]]  # longest first, named by span path

    def ms_per_round(self) -> Dict[str, float]:
        if not self.rounds:
            return {}
        return {k: 1e3 * v / self.rounds for k, v in self.phase_s.items()}


HLO_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(hlo: str) -> Dict[str, str]:
    """Each instruction's scope path in a compiled module's HLO text.
    One that XLA made with no phase of its own (a layout copy of an
    argument, a fusion a pass split off) takes the scope of a user that
    has one."""
    scopes, users = {}, {}
    for line in hlo.splitlines():
        m = HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        scope = OP_NAME.search(rest)
        scopes[name] = scope.group(1) if scope else ""
        for operand in set(re.findall(r"%([\w.\-]+)",
                                      rest.split("metadata=")[0])):
            users.setdefault(operand, []).append(name)
    changed = True
    while changed:
        changed = False
        for name, scope in scopes.items():
            if phase_of(scope) != UNSCOPED:
                continue
            for u in users.get(name, ()):
                if phase_of(scopes.get(u, "")) != UNSCOPED:
                    scopes[name], changed = scopes[u], True
                    break
    return scopes


def load(path: str, hlo: str = "") -> PhaseTrace:
    """Read the newest ``.xplane.pb`` under ``path`` (a file or the
    directory ``jax.profiler`` wrote); ``hlo`` is the HLO text of the
    module that ran, which gives each op its scope path."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    scopes = hlo_scopes(hlo)
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns,
                         scopes.get(instruction(e.name), ""))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(PREFIXES))
    return PhaseTrace(devices, host)


def from_dict(d: dict) -> PhaseTrace:
    return PhaseTrace(
        {k: [tuple(e) for e in v] for k, v in d["devices"].items()},
        [tuple(e) for e in d["host"]])


def phase_of(scope: str) -> str:
    """The first ``fedtest.*`` component of a scope path."""
    return next((c for c in scope.split("/") if c.startswith("fedtest.")),
                UNSCOPED)


def innermost(ops: List[Op], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of each phase on one chip inside [lo, hi): every busy
    instant counted once, for the innermost op covering it (the latest
    started; of two started together, the first to end)."""
    evs = sorted(((max(s, lo), min(s + d, hi), phase_of(scope))
                  for _, s, d, scope in ops if min(s + d, hi) > max(s, lo)),
                 key=lambda ev: (ev[0], -ev[1]))
    bounds = sorted({x for s, e, _ in evs for x in (s, e)})
    out: Dict[str, float] = {}
    active, i = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][0] <= a:
            active.append(evs[i])
            i += 1
        active = [ev for ev in active if ev[1] > a]
        if active:
            phase = max(active, key=lambda ev: (ev[0], -ev[1]))[2]
            out[phase] = out.get(phase, 0.0) + (b - a) * 1e-9
    return out


def span_path(host: List[Event], s: float, e: float) -> str:
    """The host spans covering the middle of [s, e), outermost first;
    where none does, the one covering most of it; else ``host``."""
    spans = [(n, hs, hd) for n, hs, hd in host if n != WINDOW]
    mid = (s + e) / 2
    cover = sorted((hs, -hd, n) for n, hs, hd in spans if hs <= mid < hs + hd)
    if cover:
        return "/".join(n for _, _, n in cover)
    best, best_cover = "host", 0.0
    for n, hs, hd in spans:
        c = min(e, hs + hd) - max(s, hs)
        if c > best_cover:
            best, best_cover = n, c
    return best


def split(trace: PhaseTrace, gaps: int = 10) -> Split:
    """Phase seconds, rounds and named idle gaps inside the harness's
    window annotation (the whole trace where there is none)."""
    windows = [(s, s + d) for n, s, d in trace.host if n == WINDOW]
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        evs = [op for ops in trace.devices.values() for op in ops]
        lo = min((s for _, s, _, _ in evs), default=0.0)
        hi = max((s + d for _, s, d, _ in evs), default=0.0)
    phase_s: Dict[str, float] = {}
    idle: List[Tuple[float, float]] = []
    for dev, ops in sorted(trace.devices.items()):
        for k, v in innermost(ops, lo, hi).items():
            phase_s[k] = phase_s.get(k, 0.0) + v
        if dev == min(trace.devices):
            union = _union([(max(s, lo), min(s + d, hi))
                            for _, s, d, _ in ops
                            if min(s + d, hi) > max(s, lo)])
            edges = [lo] + [x for se in union for x in se] + [hi]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    n = max(len(trace.devices), 1)
    phase_s = {k: v / n for k, v in sorted(phase_s.items())}
    idle.sort(key=lambda se: se[0] - se[1])
    return Split(
        window_s=(hi - lo) * 1e-9, busy_s=sum(phase_s.values()),
        rounds=sum(1 for nm, s, _ in trace.host
                   if nm == ROUND and lo <= s < hi),
        phase_s=phase_s,
        idle_gaps=[(span_path(trace.host, s, e), (e - s) * 1e-9)
                   for s, e in idle[:gaps]])


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    with open(args[1]) as f:
        sp = split(load(args[0], f.read()))
    print(json.dumps({"window_s": sp.window_s, "busy_s": sp.busy_s,
                      "rounds": sp.rounds, "phase_s": sp.phase_s,
                      "ms_per_round": sp.ms_per_round(),
                      "idle_gaps": sp.idle_gaps}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
