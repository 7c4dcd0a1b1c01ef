"""The harness's own copy of the engine's traffic arithmetic.

Copied from ``repro.sim.simulator.steady_params`` / ``request_probs`` and
``repro.sim.batched.presample_arrivals`` (with ``presample_fault_slots``),
so that the count of work in a window and the stream the reference replays
do not move when the program changes.  The draws happen in the same order
as the program's, so the same seed gives the same stream.  What the fleet
is (classes, device tables, demand mixes) comes from the configuration
(:class:`~perfbench.lib.fleet.Fleet`); what the traffic is (protocol, load,
horizons, queue draws, faults) from the mix.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from perfbench.lib.fleet import Fleet

SAMPLE_EVERY = 10  # slots between metric samples in the measurement window


def steady_params(fleet: Fleet, sim: dict) -> Tuple[int, int, int, float]:
    """``(T, warm, meas, rate)``: the longest lease in slots, the warm-up and
    measurement slots, and the Poisson arrival rate per slot.  Demand is
    counted in the canonical class sizes, capacity in the fleet's slices."""
    probs = fleet.probs(sim["distribution"], sim.get("model_distributions"))
    cap = fleet.capacity
    mean_mem = float(np.asarray(probs) @ fleet.class_mem)
    T = int(np.ceil(cap / mean_mem))
    mean_dur = (1 + T) / 2
    rate = sim["offered_load"] * cap / (mean_dur * mean_mem)
    return T, sim["warmup_horizons"] * T, sim["measure_horizons"] * T, rate


def arrivals_per_call(fleet: Fleet, sim: dict, replicas: int) -> float:
    """Offered arrivals of one whole call: replicas x rate x simulated slots."""
    _, warm, meas, rate = steady_params(fleet, sim)
    return replicas * rate * (warm + meas)


@dataclasses.dataclass
class Fault:
    """The fault process: exponential up (``mtbf``) and down (``mttr``)
    phases per GPU in slots, per-model overrides ``[[model, [mtbf, mttr]]]``,
    and the retry budget of an evicted lease."""

    mtbf: float
    mttr: float
    per_model: tuple = ()
    max_retries: int = 2
    backoff_base: int = 2

    def rates_for(self, model: str) -> Tuple[float, float]:
        for name, pair in self.per_model:
            if name == model:
                return float(pair[0]), float(pair[1])
        return self.mtbf, self.mttr

    def backoff(self, attempt: int) -> int:
        """Slots to wait before re-queue attempt ``attempt`` (1-based)."""
        return self.backoff_base * 2 ** max(0, attempt - 1)


@dataclasses.dataclass
class Stream:
    """One presampled stream, every array ``(E_max, R)`` (``fail``/``recover``
    ``(E_max, R, M)``): one event per arrival and a heartbeat for each empty
    slot, then a sentinel at slot ``total_slots``, right-padded to the
    longest replica.  ``pid`` is -1 where an event is no arrival; ``end`` is
    the absolute end slot of an arrival's lease."""

    pid: np.ndarray
    new_slot: np.ndarray
    sample: np.ndarray
    measuring: np.ndarray
    slot: np.ndarray
    end: np.ndarray
    total_slots: int
    ring_rows: int                    # T + 1: a live lease's end lies in (t, t + T]
    prio: Optional[np.ndarray] = None
    tenant: Optional[np.ndarray] = None
    wlive: Optional[np.ndarray] = None
    fail: Optional[np.ndarray] = None
    recover: Optional[np.ndarray] = None

def _fault_slots(fleet: Fleet, fault: Fault, runs, total_slots, rng):
    m = fleet.num_gpus
    rates = [fault.rates_for(fleet.models[k]) for k in fleet.model_of]
    fail = np.zeros((runs, total_slots, m), dtype=bool)
    recover = np.zeros((runs, total_slots, m), dtype=bool)
    for r in range(runs):
        for g in range(m):
            mtbf, mttr = rates[g]
            t = 0.0
            while True:
                t += max(1.0, np.ceil(rng.exponential(mtbf)))
                if t >= total_slots:
                    break
                fail[r, int(t), g] = True
                t += max(1.0, np.ceil(rng.exponential(mttr)))
                if t >= total_slots:
                    break
                recover[r, int(t), g] = True
    return fail, recover


def shape(s: Stream) -> Dict[str, int]:
    """What the stream's size depends on: the longest replica's event count
    plus its sentinel, and the most arrivals of one replica that end in the
    same slot (the width of the program's expiry ring)."""
    e, r = np.nonzero(s.pid >= 0)
    same_end = np.unique(r * (int(s.end.max()) + 1) + s.end[e, r], return_counts=True)[1]
    return {"events": int(s.pid.shape[0]), "ring_cols": int(same_end.max(initial=0))}


def program_seed(fleet: Fleet, sim: dict, replicas: int, seed: int,
                 want: Dict[str, int]) -> int:
    """The seed handed to the program for the run's ``seed``: the first of
    ``seed * 2**20 + k`` (k = 0, 1, ...) whose stream has the mix's shape.

    Every run of a mix then does the same amount of work in the same
    compiled program, whatever its seed: a stream's length and ring width
    change the scan's work by up to 40% (the fault stage walks the whole
    ring) and each new pair compiles anew.  The traffic is the Poisson
    process conditioned on that shape.  The event count comes from the
    first draw alone, so most candidates cost one Poisson draw.
    """
    _, warm, meas, rate = steady_params(fleet, sim)
    for k in range(2**20):
        cand = seed * 2**20 + k
        counts = np.random.default_rng(cand).poisson(rate, size=(replicas, warm + meas))
        if int(np.maximum(counts, 1).sum(axis=1).max()) + 1 != want["events"]:
            continue
        if shape(presample(fleet, sim, replicas, cand, extras=False)) == want:
            return cand
    raise RuntimeError(f"no stream of shape {want} among the candidates of seed {seed}")


def presample(fleet: Fleet, sim: dict, replicas: int, seed: int,
              fault: Optional[Fault] = None, extras: bool = True) -> Stream:
    """The steady-protocol stream of ``replicas`` replicas from ``seed``.

    One event per Poisson arrival plus a heartbeat for each empty slot and
    a trailing sentinel, right-padded to the longest replica.  After the
    arrivals, a mix that names ``num_tenants`` (the queued and faulted
    protocols) draws each arrival's tenant and priority, and a mix with a
    fault model then draws the per-GPU fail/recover slots (``extras=False``
    skips both).
    """
    rng = np.random.default_rng(seed)
    probs = fleet.probs(sim["distribution"], sim.get("model_distributions"))
    T, warm, meas, rate = steady_params(fleet, sim)
    total_slots = warm + meas

    counts = rng.poisson(rate, size=(replicas, total_slots))
    ev_per_slot = np.maximum(counts, 1)
    n_events = ev_per_slot.sum(axis=1)
    e_max = int(n_events.max()) + 1

    pid = np.full((replicas, e_max), -1, dtype=np.int32)
    slot = np.full((replicas, e_max), total_slots, dtype=np.int32)
    new_slot = np.zeros((replicas, e_max), dtype=bool)
    end = np.zeros((replicas, e_max), dtype=np.int64)
    for r in range(replicas):
        n = n_events[r]
        slots_r = np.repeat(np.arange(total_slots), ev_per_slot[r])
        within = np.arange(n) - np.repeat(
            np.cumsum(ev_per_slot[r]) - ev_per_slot[r], ev_per_slot[r]
        )
        is_arr = within < counts[r, slots_r]
        na = int(is_arr.sum())
        pid[r, :n][is_arr] = rng.choice(fleet.num_classes, size=na, p=probs)
        slot[r, :n] = slots_r
        new_slot[r, :n] = within == 0
        end[r, :n][is_arr] = slots_r[is_arr] + rng.integers(1, T + 1, size=na)
        new_slot[r, n] = True

    is_arrival = pid >= 0
    prev = slot - 1
    sample = new_slot & (prev >= warm) & ((prev - warm) % SAMPLE_EVERY == 0)
    measuring = is_arrival & (slot >= warm)
    out = Stream(
        pid=pid.T, new_slot=new_slot.T, sample=sample.T, measuring=measuring.T,
        slot=slot.T, end=end.T, total_slots=total_slots, ring_rows=T + 1,
        wlive=(slot < total_slots).T,
    )
    if not extras:
        return out
    if "num_tenants" in sim:
        tenant = np.zeros((replicas, e_max), dtype=np.int32)
        prio = np.zeros((replicas, e_max), dtype=np.int32)
        for r in range(replicas):
            sel = is_arrival[r]
            na = int(sel.sum())
            tenant[r, sel] = rng.integers(0, max(1, sim["num_tenants"]), size=na)
            prio[r, sel] = rng.integers(0, max(1, sim["num_priorities"]), size=na)
        out.tenant, out.prio = tenant.T, prio.T
    if fault is not None:
        fail_s, rec_s = _fault_slots(fleet, fault, replicas, total_slots, rng)
        m = fleet.num_gpus
        fail = np.zeros((replicas, e_max, m), dtype=bool)
        recover = np.zeros((replicas, e_max, m), dtype=bool)
        first = new_slot & (slot < total_slots)
        rr_idx, ee_idx = np.nonzero(first)
        fail[rr_idx, ee_idx] = fail_s[rr_idx, slot[rr_idx, ee_idx]]
        recover[rr_idx, ee_idx] = rec_s[rr_idx, slot[rr_idx, ee_idx]]
        out.fail = np.ascontiguousarray(fail.transpose(1, 0, 2))
        out.recover = np.ascontiguousarray(recover.transpose(1, 0, 2))
    return out
