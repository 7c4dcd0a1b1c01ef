"""MFI with a single-migration defrag search on reject, as the batched
engine compiles ``mfi-defrag``: every running workload of the replica, in
(GPU, anchor) order, is a victim; take it out, place the arrival by MFI,
re-place the victim by MFI, and keep the move with the least total blocked
score (the first wins ties)."""

import numpy as np

from perfbench.lib.cell import module

select = module("reference/policies", "mfi").select

#: decisions this policy adds: the victim's old GPU and anchor and its new ones
FIELDS = dict(mig=False, mig_from_gpu=-1, mig_from_anchor=-1, mig_to_gpu=-1, mig_to_anchor=-1)


def on_reject(ref, e, r, pid, tr):
    """Search a migration that admits class ``pid`` in replica ``r``; make
    it and return the arrival's ``(gpu, anchor index)``, or None."""
    w, vg, va = ref.running(r)
    n = len(w)
    if not n:
        return None
    vc = ref.s.pid[w, r]
    rows = np.arange(n)
    p1 = np.broadcast_to(ref.pat[r], (n, ref.fleet.num_gpus)).copy()
    p1[rows, vg] &= ~ref.window(vg, vc, ref.a_of[w, r])
    up = np.broadcast_to(ref.up[r], p1.shape)
    rg, ra, rok = select(ref, p1, up, np.full(n, pid))
    p2 = p1.copy()
    p2[rows, rg] |= np.where(rok, ref.window(rg, pid, ra), 0)
    ng, na, nok = select(ref, p2, up, vc)
    p3 = p2.copy()
    p3[rows, ng] |= np.where(nok, ref.window(ng, vc, na), 0)
    total = np.where(rok & nok, ref.total(p3), np.inf)
    b = int(total.argmin())
    if not np.isfinite(total[b]):
        return None
    vw = int(w[b])
    ref.release(np.array([r]), np.array([vw]))
    ref.place(np.array([r]), np.array([ng[b]]), np.array([na[b]]), np.array([vw]))
    tr["mig"][e, r] = True
    tr["mig_from_gpu"][e, r], tr["mig_from_anchor"][e, r] = vg[b], va[b]
    tr["mig_to_gpu"][e, r] = ng[b]
    tr["mig_to_anchor"][e, r] = ref.t.anchor[ref.kg[ng[b]], ref.s.pid[vw, r], na[b]]
    return int(rg[b]), int(ra[b])
