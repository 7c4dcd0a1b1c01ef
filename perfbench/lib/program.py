"""The program's own spans, counters and stage map, read after a traced run.

The program under test keeps an in-memory record of its calls
(``repro.obs``): per call of ``run_batched`` a tree of host spans
(``presample``, ``transfer``, ``dispatch``, ``scan``, ``fetch``,
``aggregate``) and counters (lanes, bytes), and it maps each instruction of
its compiled scan to the ``EngineCore`` stage that emitted it.  A program
without that module has nothing to read: every function here then returns
``None``.

The traced calls are the last ``run_batched`` calls of the record, as many
as the trace holds harness ``call`` spans (readers that run after the
window open calls of other roots).  Program spans are timed on
``time.perf_counter_ns``; they go onto the trace's clock by the offset
between the program's ``aggregate`` span and the harness's
``perfbench.aggregate`` span, which wrap the same call.  The
``presample`` pair gives a second offset (the program's span also holds
the validation before ``presample_arrivals``); the two are printed to
standard error, and agree to well within a millisecond.
"""

from __future__ import annotations

import importlib
import json
import sys
from typing import Dict, List, Optional, Tuple

from perfbench.lib import trace as tracelib

ROOT_SPAN = "run_batched"


def obs():
    """The program's ``repro.obs``, or ``None``."""
    try:
        return importlib.import_module("repro.obs")
    except ImportError:
        return None


def _memo(key: str, ctx, fn):
    """``fn()`` once per reading context (several readers share it)."""
    memo = ctx.__dict__.setdefault("_program_memo", {})
    if key not in memo:
        memo[key] = fn()
    return memo[key]


def traced_calls(ctx) -> Optional[list]:
    """The program's records of the traced calls, oldest first."""
    o, tr = obs(), ctx.trace
    if o is None or tr is None or not tr.calls():
        return None
    mine = [c for c in o.calls() if c.root.name == ROOT_SPAN and c.root.end_ns]
    n = len(tr.calls())
    return mine[-n:] if len(mine) >= n else None


def _harness(tr, name: str, lo: float, hi: float) -> Optional[float]:
    starts = [s for n, s, _ in tr.spans if n == tracelib.SPAN_PREFIX + name and lo <= s < hi]
    return starts[0] if starts else None


def offsets(ctx) -> Optional[Dict[str, float]]:
    """Trace-clock ns minus program ns, from the ``presample`` and the
    ``aggregate`` pairs of the traced calls (their means)."""
    def compute():
        calls = traced_calls(ctx)
        if not calls:
            return None
        pairs = {"presample": [], "aggregate": []}
        for call, (lo, hi) in zip(calls, ctx.trace.calls()):
            for name, got in pairs.items():
                mine, theirs = call.find(name), _harness(ctx.trace, name, lo, hi)
                if mine and theirs is not None:
                    got.append(theirs - mine[0].start_ns)
        if not all(pairs.values()):
            return None
        out = {k: sum(v) / len(v) for k, v in pairs.items()}
        print("program span offsets " + json.dumps(
            {**out, "disagree_ms": abs(out["presample"] - out["aggregate"]) * 1e-6}),
            file=sys.stderr)
        return out
    return _memo("offsets", ctx, compute)


def phase_spans(ctx) -> Optional[List[Tuple[str, float, float]]]:
    """Every span of the traced calls below the root, as ``(name, start,
    end)`` on the trace's clock."""
    calls, off = traced_calls(ctx), offsets(ctx)
    if not calls or off is None:
        return None
    d = off["aggregate"]
    return [(s.name, s.start_ns + d, s.end_ns + d)
            for c in calls for s in c.spans if s.parent is not None]


def span_seconds(ctx, name: str) -> Optional[float]:
    """Seconds of the traced calls' ``name`` spans, per call."""
    calls = traced_calls(ctx)
    if not calls:
        return None
    spans = [s for c in calls for s in c.find(name)]
    return sum(s.seconds for s in spans) / len(calls) if spans else None


def counters(ctx) -> Optional[Dict[str, int]]:
    """The traced calls' counters, summed."""
    calls = traced_calls(ctx)
    if not calls:
        return None
    out: Dict[str, int] = {}
    for c in calls:
        for k, v in c.counters.items():
            out[k] = out.get(k, 0) + v
    return out


def stage_times(tr, smap: Dict[str, str], calls: int) -> Dict[str, float]:
    """Device milliseconds per call of each stage of ``smap``, the largest
    over the devices: the self time of every op of the traced window whose
    name the map holds, summed by its stage.  Ops the map does not name
    (no op of the scan's executable) go under ``unmapped``."""
    lo, hi = tr.window()
    worst: Dict[str, float] = {}
    for ops in tr.devices.values():
        per: Dict[str, float] = {}
        for name, ns in tracelib.self_times(ops, lo, hi).items():
            stage = smap.get(name, "unmapped")
            per[stage] = per.get(stage, 0.0) + ns
        for stage, ns in per.items():
            worst[stage] = max(worst.get(stage, 0.0), ns * 1e-6 / calls)
    return worst


def stages(ctx) -> Optional[Dict[str, float]]:
    """:func:`stage_times` of the traced window, with the stage map of the
    last call's program (the executable it launched)."""
    def compute():
        o, tr = obs(), ctx.trace
        if (o is None or not hasattr(o, "stage_map") or tr is None or not tr.devices
                or not tr.calls() or not ctx.captured):
            return None
        smap = o.stage_map(ctx.captured[-1][0])
        out = stage_times(tr, smap, len(tr.calls()))
        print("stage split ms " + json.dumps(out), file=sys.stderr)
        return out
    return _memo("stages", ctx, compute)


def idle_unattributed(tr, spans: List[Tuple[str, float, float]]) -> Optional[float]:
    """Percent of the traced window in which a device is idle and no
    program span is open; the largest over the devices."""
    if not tr.devices or not tr.window():
        return None
    lo, hi = tr.window()
    covered = tracelib.merged([tracelib.Op("", s, e - s) for _, s, e in spans], lo, hi)
    worst = 0.0
    for ops in tr.devices.values():
        idle = 0.0
        for a, b in tracelib.gaps(ops, lo, hi):
            idle += (b - a) - sum(max(0.0, min(b, e) - max(a, s)) for s, e in covered)
        worst = max(worst, 100.0 * idle / (hi - lo))
    return worst
