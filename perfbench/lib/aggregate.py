"""The harness's own copy of the engine's aggregation arithmetic.

Copied from ``repro.sim.batched.aggregate``, ``_aggregate_queued`` and
``_aggregate_faulted`` (and ``repro.sim.simulator.jain_fairness``), so the
reference reduces its own decisions to the numbers ``api.simulate``
returns, with the same floating-point operations in the same order.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from perfbench.lib.fleet import Fleet
from perfbench.lib.stream import Stream


def jain_fairness(values) -> float:
    x = np.asarray(list(values), dtype=np.float64)
    if x.size == 0:
        return 1.0
    sq = float(np.square(x).sum())
    if sq == 0.0:
        return 1.0
    s = float(x.sum())
    return s * s / (x.size * sq)


def steady(s: Stream, tr: Dict[str, np.ndarray], fleet: Fleet) -> dict:
    """The aggregates of one steady call, keyed as ``api.simulate`` returns them."""
    runs = s.pid.shape[1]
    cap = float(fleet.capacity)
    ok = tr["ok"]
    meas, samp = s.measuring, s.sample
    arrived = np.maximum(meas.sum(axis=0), 1)
    accepted = (ok & meas).sum(axis=0)
    nsamp = np.maximum(samp.sum(axis=0), 1)
    util = ((cap - tr["free_sum"]) / cap * samp).sum(axis=0) / nsamp
    active = (tr["active"] * samp).sum(axis=0) / nsamp
    frag = (tr["frag"] * samp).sum(axis=0) / nsamp
    arrivals_p = np.stack([((s.pid == p) & meas).sum() for p in range(fleet.num_classes)])
    rejects_p = np.stack([((s.pid == p) & meas & ~ok).sum() for p in range(fleet.num_classes)])
    return {
        "acceptance_rate": float((accepted / arrived).mean()),
        "allocated_workloads": float(accepted.mean()),
        "active_gpus": float(active.mean()),
        "utilization": float(util.mean()),
        "frag_severity": float(frag.mean()),
        "rejects_by_profile": rejects_p / runs,
        "arrivals_by_profile": arrivals_p / runs,
    }


def queued(s: Stream, tr, fleet: Fleet) -> dict:
    """The aggregates of a call with a wait queue."""
    runs = s.pid.shape[1]
    cap = float(fleet.capacity)
    ok = tr["ok"]
    wadm = tr["wadm_eidx"]
    slot, tenant = s.slot, s.tenant
    meas, samp = s.measuring, s.sample
    late_ok = np.zeros_like(ok)
    wait = np.zeros(ok.shape, np.float64)
    for r in range(runs):
        adm = np.flatnonzero(wadm[:, r] >= 0)
        orig = wadm[adm, r]
        late_ok[orig, r] = True
        wait[orig, r] = slot[adm, r] - slot[orig, r]
    acc_all = ok | late_ok
    arrived = np.maximum(meas.sum(axis=0), 1)
    accepted = (acc_all & meas).sum(axis=0)
    nsamp = np.maximum(samp.sum(axis=0), 1)
    util = ((cap - tr["free_sum"]) / cap * samp).sum(axis=0) / nsamp
    active = (tr["active"] * samp).sum(axis=0) / nsamp
    frag = (tr["frag"] * samp).sum(axis=0) / nsamp
    p50, p99, fair = np.zeros(runs), np.zeros(runs), np.zeros(runs)
    for r in range(runs):
        w = wait[:, r][acc_all[:, r] & meas[:, r]]
        p50[r] = np.percentile(w, 50) if len(w) else 0.0
        p99[r] = np.percentile(w, 99) if len(w) else 0.0
        tm = meas[:, r]
        rates = [
            (acc_all[:, r] & tm & (tenant[:, r] == tn)).sum() / (tm & (tenant[:, r] == tn)).sum()
            for tn in np.unique(tenant[:, r][tm])
        ]
        fair[r] = jain_fairness(rates)
    arrivals_p = np.stack([((s.pid == p) & meas).sum() for p in range(fleet.num_classes)])
    rejects_p = np.stack([((s.pid == p) & meas & ~acc_all).sum() for p in range(fleet.num_classes)])
    return {
        "acceptance_rate": float((accepted / arrived).mean()),
        "allocated_workloads": float(accepted.mean()),
        "active_gpus": float(active.mean()),
        "utilization": float(util.mean()),
        "frag_severity": float(frag.mean()),
        "rejects_by_profile": rejects_p / runs,
        "arrivals_by_profile": arrivals_p / runs,
        "wait_p50": float(p50.mean()),
        "wait_p99": float(p99.mean()),
        "fairness": float(fair.mean()),
        "queue_admits": float((late_ok & meas).sum(axis=0).mean()),
    }


def faulted(s: Stream, tr, fleet: Fleet) -> dict:
    """The aggregates of a faulted call: the queued ones, then goodput,
    evictions and recovery."""
    out = queued(s, tr, fleet)
    runs = s.pid.shape[1]
    slot, end, fail = s.slot, s.end, s.fail
    wlive, new_slot, meas = s.wlive, s.new_slot, s.measuring
    ok, gpu_tr, wadm, wgpu = tr["ok"], tr["gpu"], tr["wadm_eidx"], tr["wadm_gpu"]
    e_max = ok.shape[0]
    goodput, recovered = np.zeros(runs), np.zeros(runs)
    ttr_p50, ttr_p99 = np.zeros(runs), np.zeros(runs)
    for r in range(runs):
        alive, done, pending = {}, set(), {}
        n_evict = n_recovered = 0
        ttrs = []
        for e in range(e_max):
            if not wlive[e, r]:
                continue
            t = slot[e, r]
            if new_slot[e, r]:
                for k in [k for k, (_, kend) in alive.items() if kend <= t]:
                    del alive[k]
                    done.add(k)
                downs = set(np.flatnonzero(fail[e, r]).tolist())
                if downs:
                    for k in [k for k, (g, _) in alive.items() if g in downs]:
                        del alive[k]
                        pending[k] = t
                        n_evict += 1
            a = int(wadm[e, r])
            if a >= 0:
                alive[a] = (int(wgpu[e, r]), int(end[a, r]))
                if a in pending:
                    n_recovered += 1
                    ttrs.append(t - pending.pop(a))
            if ok[e, r]:
                alive[e] = (int(gpu_tr[e, r]), int(end[e, r]))
        done.update(alive)
        m = meas[:, r]
        goodput[r] = sum(1 for k in done if m[k]) / max(1, int(m.sum()))
        recovered[r] = (n_recovered / n_evict) if n_evict else 1.0
        ttr_p50[r] = np.percentile(ttrs, 50) if ttrs else 0.0
        ttr_p99[r] = np.percentile(ttrs, 99) if ttrs else 0.0
    out.update(
        goodput=float(goodput.mean()),
        evictions=float(np.asarray(tr["evicted"]).sum(axis=0).mean()),
        evictions_lost=float(np.asarray(tr["evict_lost"]).sum(axis=0).mean()),
        recovered_fraction=float(recovered.mean()),
        ttr_p50=float(ttr_p50.mean()),
        ttr_p99=float(ttr_p99.mean()),
    )
    return out
