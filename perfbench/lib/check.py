"""The comparison that decides ``correct``.

Every call of the window is held against the reference on the same seed.
Both sides are compared by what they mean, not by how the program lays
them out: each replica's real events (those before the final slot, then
the one sentinel that samples it) are taken in order and any padding past
them is dropped, so a program that pads its stream differently, or keeps
other ring coordinates, is compared the same way.

* ``stream_mismatch``: entries of the stream the call ran that differ
  from the harness's own draw (``lib/stream.py``): each event's slot, each
  arrival's class, end slot, tenant and priority, and the GPUs that fail
  or recover at each slot.  A replica with another number of real events
  counts whole;
* ``decision_mismatch``: per real event, decisions that differ from the
  reference's: each arrival's admission and (where admitted) its GPU and
  anchor index, the free slices and active GPUs left by the previous
  event, and whatever the policy and protocol add (migrations, parks,
  wait admissions and evictions, which name arrivals by their index among
  the replica's events);
* ``frag_gap``: the widest relative gap of the cluster-mean fragmentation
  score at each real event.  The program divides a sum of integers in
  float32, the reference in float64, so it is not exact;
* ``aggregate_gap``: the widest relative gap between the numbers the call
  returned and the reference's reduction of its own decisions.  Not exact
  either: ``frag_severity`` averages the float32 scores, and float64 sums
  over differently laid-out arrays round differently in the last bit.

The first two are exact comparisons (limit 0).  The gaps' limits were
set from chip readings of the program and of the bfloat16 control; see
PERF.md.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from perfbench.lib.stream import Stream

LIMITS = {"stream_mismatch": 0, "decision_mismatch": 0, "frag_gap": 1e-5,
          "aggregate_gap": 1e-5}

#: decisions defined only where the arrival was admitted
ON_ADMIT = ("gpu", "aidx")


def real(x, mask: np.ndarray) -> Optional[np.ndarray]:
    """The entries of an ``(E, R, ...)`` array at the real events of
    ``mask`` ``(E, R)``, replica by replica, each in event order."""
    if x is None:
        return None
    x = np.asarray(x)
    if x.shape[:2] != mask.shape:
        return None
    return np.swapaxes(x, 0, 1)[mask.T]


def _count(got, want) -> int:
    if want is None:
        return 0
    if got is None or got.shape != want.shape:
        return int(want.size) or 1
    return int((got != want).sum())


def events_mask(slot: np.ndarray, total_slots: int) -> np.ndarray:
    """The real events of an ``(E, R)`` slot array: every event before the
    final slot, and the first one at it (the sentinel)."""
    past = slot >= total_slots
    return ~past | (past & (np.cumsum(past, axis=0) == 1))


def real_events(slot, total_slots: int, want: np.ndarray) -> Optional[np.ndarray]:
    """The mask of the program's real events, or None where some replica has
    another number of them than ``want`` (the harness's mask)."""
    slot = np.asarray(slot)
    if slot.ndim != 2 or slot.shape[1] != want.shape[1]:
        return None
    mask = events_mask(slot, total_slots)
    return mask if (mask.sum(axis=0) == want.sum(axis=0)).all() else None


def stream_mismatch(events, meta, s: Stream) -> int:
    """Entries of the program's stream (``EventStream``, ``EventMeta``) that
    differ from the harness's draw."""
    want_mask = events_mask(s.slot, s.total_slots)
    mask = real_events(meta.slot, s.total_slots, want_mask)
    if mask is None:
        return int(want_mask.sum()) or 1
    arr_want = real(s.pid, want_mask) >= 0
    arr_got = real(events.pid, mask) >= 0
    n = _count(real(meta.slot, mask), real(s.slot, want_mask))
    n += _count(real(events.pid, mask), real(s.pid, want_mask))
    if (arr_got != arr_want).any():
        return n + int(arr_want.sum())
    for got, want in ((meta.end, s.end), (getattr(events, "tenant", None), s.tenant),
                      (getattr(events, "prio", None), s.prio)):
        g = real(got, mask)
        n += _count(None if g is None else g[arr_got],
                    None if want is None else real(want, want_mask)[arr_want])
    for name in ("fail", "recover"):
        n += _count(real(getattr(events, name, None), mask), real(getattr(s, name), want_mask))
    return n


def decision_mismatch(trace, ref: Dict[str, np.ndarray], mask, want_mask) -> int:
    if mask is None:
        return int(want_mask.sum()) * len(ref)
    ok = real(ref["ok"], want_mask)
    n = 0
    for k, want in ref.items():
        if k == "frag":
            continue
        want = real(want, want_mask)
        got = real(getattr(trace, k, None), mask)
        if k in ON_ADMIT and got is not None and got.shape == want.shape:
            got, want = got[ok], want[ok]
        n += _count(got, want)
    return n


def frag_gap(trace, ref, num_gpus: int, mask, want_mask) -> float:
    got = real(getattr(trace, "frag", None), mask) if mask is not None else None
    want = real(ref["frag"], want_mask)
    if got is None or got.shape != want.shape:
        return float("inf")
    gap = np.abs(np.asarray(got, np.float64) - want) / np.maximum(want, 1.0 / num_gpus)
    return float(gap.max(initial=0.0))


def aggregate_gaps(got: dict, want: dict) -> Dict[str, float]:
    """The relative gap of each returned number (the widest element of an
    array); a missing or reshaped one is an infinite gap."""
    out = {}
    for k, v in want.items():
        w = np.asarray(v, np.float64)
        g = np.asarray(got.get(k, np.full(w.shape, np.nan)), np.float64)
        if g.shape != w.shape:
            out[k] = float("inf")
            continue
        gap = np.abs(g - w) / np.maximum(np.abs(w), 1e-12)
        out[k] = float(np.nan_to_num(gap, nan=np.inf).max(initial=0.0))
    return out


def compare(calls: List[tuple], s: Stream, ref, ref_agg: dict, num_gpus: int) -> Dict[str, float]:
    """The worst reading of each number over the calls.  ``calls`` holds
    ``(events, meta, trace, returned)`` per call."""
    out = {"stream_mismatch": 0, "decision_mismatch": 0, "frag_gap": 0.0, "aggregate_gap": 0.0}
    want_mask = events_mask(s.slot, s.total_slots)
    for events, meta, trace, got in calls:
        mask = real_events(meta.slot, s.total_slots, want_mask)
        out["stream_mismatch"] = max(out["stream_mismatch"], stream_mismatch(events, meta, s))
        out["decision_mismatch"] = max(out["decision_mismatch"],
                                       decision_mismatch(trace, ref, mask, want_mask))
        out["frag_gap"] = max(out["frag_gap"], frag_gap(trace, ref, num_gpus, mask, want_mask))
        out["aggregate_gap"] = max([out["aggregate_gap"], *aggregate_gaps(got, ref_agg).values()])
    return out


def passed(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
