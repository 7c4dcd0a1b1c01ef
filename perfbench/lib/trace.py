"""Reduction of a profiler trace to device busy time, idle gaps and op time.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain lists: the operations each device
ran (the ``XLA Ops`` line of every ``/device:`` plane) and the harness's
own host spans (``perfbench.*`` annotations).  On a TPU an op's name is
its HLO instruction (``%vmap_jit_select_from_base__.8 = f32[...] ...``,
a Pallas kernel named after the jitted function that launches it), and
ops nest: the scan's ``%while`` op spans every op of its body.  Everything
after loading is plain interval arithmetic on nanoseconds, kept here so
that every run computes the numbers the same way.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "perfbench."
CALL_SPAN = SPAN_PREFIX + "call"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str       # the event's name: on a TPU, the HLO instruction
    start: float    # ns
    dur: float      # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]                  # device plane -> its ops
    spans: List[Tuple[str, float, float]]         # (name, start ns, end ns)

    def calls(self) -> List[Tuple[float, float]]:
        return sorted((s, e) for n, s, e in self.spans if n == CALL_SPAN)

    def window(self) -> Optional[Tuple[float, float]]:
        c = self.calls()
        return (c[0][0], c[-1][1]) if c else None


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    names: Dict[str, str] = {}  # one string object per distinct op name
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    ops.append(Op(names.setdefault(name, name), float(ev.start_ns),
                                  float(ev.duration_ns)))
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: (o.start, -o.dur))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    return Trace(devices, spans)


def find_xplane(directory: Path) -> Optional[Path]:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def merged(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the ops' intervals clipped to ``[lo, hi]``, as disjoint
    sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted((max(o.start, lo), min(o.end, hi)) for o in ops):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(ops: Sequence[Op], lo: float, hi: float) -> float:
    """Nanoseconds in ``[lo, hi]`` during which some op ran."""
    return sum(e - s for s, e in merged(ops, lo, hi))


def gaps(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merged(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def op_time(ops: Sequence[Op], pattern: str, lo: float, hi: float) -> Optional[float]:
    """Nanoseconds of the ops whose name matches ``pattern`` and that start
    in ``[lo, hi]``; ``None`` when none match."""
    rx = re.compile(pattern)
    match = {}
    hit = [o.dur for o in ops
           if lo <= o.start < hi and match.setdefault(o.name, bool(rx.search(o.name)))]
    return sum(hit) if hit else None


def short(name: str) -> str:
    """An HLO instruction's name without its shapes and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Op], lo: float, hi: float) -> Dict[str, float]:
    """Nanoseconds per op name, each op less the ops nested inside it,
    over the ops that start in ``[lo, hi]``."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name]
    for o in ops:  # sorted by start, longest first
        if not lo <= o.start < hi:
            continue
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1][0]:
            out[stack[-1][1]] -= o.dur
        name = short(o.name)
        out[name] = out.get(name, 0.0) + o.dur
        stack.append([o.end, name])
    return out


def gap_label(tr: Trace, ops: Sequence[Op], s: float, e: float) -> str:
    """What the host was doing during the idle gap ``[s, e)`` of a device:
    the innermost harness span at its midpoint; else, inside a call,
    whether the device had not started that call's ops yet, had finished
    them, or was between them; else ``between calls``."""
    t = (s + e) / 2
    inner = None
    for name, a, b in tr.spans:
        if a <= t < b and name != CALL_SPAN and (inner is None or a >= inner[1]):
            inner = (name, a)
    if inner:
        return inner[0][len(SPAN_PREFIX):]
    for a, b in tr.calls():
        if a <= t < b:
            inside = [o for o in ops if a <= o.start < b]
            if not inside or t < inside[0].start:
                return "call, before its device ops"
            if t >= max(o.end for o in inside):
                return "call, after its device ops"
            return "call, between device ops"
    return "between calls"
