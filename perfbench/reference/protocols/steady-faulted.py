"""The steady-faulted protocol: the steady protocol's arrivals on GPUs that
fail and recover.  At the first event of a slot a GPU recovers, then
failing GPUs evict their leases (in expiry order: end slot modulo T + 1,
then arrival) into a bounded wait queue, one retry spent, ready after a
backoff; an eviction that finds no room or no retry is lost.  Before the
arrivals of each event the queue drops leases past their end, re-arms
(or drops, once out of retries) waits past their patience, and admits at
most one ready entry by MFI: priority first, then the longest wait, then
the earliest arrival.  A rejected arrival parks in the queue if it has
room."""

import numpy as np

from perfbench.lib import aggregate

FIELDS = dict(parked=False, wadm_eidx=-1, wadm_gpu=-1, wadm_aidx=-1, evicted=0,
              evict_lost=0, evict_esum=0)


def decide(ref):
    ref.waiting = [[] for _ in range(ref.s.pid.shape[1])]  # [eidx, arr, prio, tries, rdy]
    return ref.steady(FIELDS, before=_before, after=_park)


def reduce(ref, decisions):
    return aggregate.faulted(ref.s, decisions, ref.fleet)


def _before(ref, e, tr):
    s = ref.s
    for r in np.flatnonzero(s.recover[e].any(axis=1) | s.fail[e].any(axis=1)):
        _faults(ref, e, r, tr)
    for r in np.flatnonzero(s.wlive[e]):
        if ref.waiting[r]:
            _wait(ref, e, r, tr)


def _park(ref, e, ok, tr):
    s = ref.s
    for r in np.flatnonzero((s.pid[e] >= 0) & ~ok & s.wlive[e]):
        if len(ref.waiting[r]) < ref.sim["wait_capacity"]:
            t = int(s.slot[e, r])
            ref.waiting[r].append([e, t, int(s.prio[e, r]), 0, t])
            tr["parked"][e, r] = True


def _faults(ref, e, r, tr):
    s, fault = ref.s, ref.fault
    t = int(s.slot[e, r])
    ref.up[r, s.recover[e, r]] = True
    downs = s.fail[e, r]
    if not downs.any():
        return
    w = np.flatnonzero(ref.live[:, r])
    w = w[downs[ref.gpu_of[w, r]]]
    w = w[np.lexsort((w, s.end[w, r] % s.ring_rows))]
    ref.release(np.full(len(w), r), w)
    ref.up[r, downs] = False
    lost = 0
    for x in w:
        if fault.max_retries >= 1 and len(ref.waiting[r]) < ref.sim["wait_capacity"]:
            ref.waiting[r].append([int(x), t, int(s.prio[x, r]), 1, t + fault.backoff(1)])
        else:
            lost += 1
    tr["evicted"][e, r] = len(w)
    tr["evict_lost"][e, r] = lost
    tr["evict_esum"][e, r] = int(w.sum())


def _wait(ref, e, r, tr):
    s, fault = ref.s, ref.fault
    t = int(s.slot[e, r])
    kept = []
    for x in ref.waiting[r]:
        eidx, arr, prio, tries, rdy = x
        if t - arr > ref.sim["wait_patience"]:
            if tries < fault.max_retries and s.end[eidx, r] > t:
                k = tries + 1
                kept.append([eidx, t, prio, k, t + fault.backoff(k)])
        elif s.end[eidx, r] > t:
            kept.append(x)
    ref.waiting[r] = kept
    ready = [x for x in kept if x[4] <= t]
    if not ready:
        return
    head = min(ready, key=lambda x: (x[2], -(t - x[1]), x[0]))
    w = head[0]
    g, a, ok = ref.policy.select(ref, ref.pat[r : r + 1], ref.up[r : r + 1], s.pid[w, r : r + 1])
    if not ok[0]:
        return
    kept.remove(head)
    ref.place(np.array([r]), g, a, np.array([w]))
    tr["wadm_eidx"][e, r] = w
    tr["wadm_gpu"][e, r] = int(g[0])
    tr["wadm_aidx"][e, r] = int(a[0])
