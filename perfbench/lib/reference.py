"""Plain host reference of the scheduling semantics the engine runs.

Independent of the program: it imports nothing of it and replays a
:class:`~perfbench.lib.stream.Stream` that the harness drew itself, on the
fleet its configuration states (:class:`~perfbench.lib.fleet.Fleet`).  This
module holds what every policy and protocol shares: the ``blocked``
fragmentation score of Algorithm 1 per device model, the state of each
replica (occupancy pattern and up flag of each GPU, and where each arrival
runs) and the steady event loop.  The placement rule is a file of
``perfbench/reference/policies/``, the protocol's stages and reduction a
file of ``perfbench/reference/protocols/``, each found by the name the
configuration or the mix gives; :func:`replay` puts them together.

One event is one step over all replicas at once; per-replica Python runs
only where a policy or protocol needs it (a defrag search, a GPU that
fails, a non-empty wait queue).

``dtype`` is the precision of the fragmentation sums (the cluster-mean
score of each sample and a defrag search's total score).  The program
computes them in float32, whose sums of integers are exact here; the
reference uses float64; the control passes bfloat16.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from perfbench.lib.cell import module
from perfbench.lib.fleet import Fleet
from perfbench.lib.stream import Fault, Stream

#: the widest device model the pattern tables are built for
NUM_SLICES_MAX = 12


class Tables:
    """Per device model ``k`` of the fleet, over occupancy patterns (bit s =
    memory slice s occupied): the blocked score ``F[k, pattern]``, each
    class's windows and anchors, and for every pattern and class the least
    score increment over the anchors and the first anchor index attaining
    it (``best``/``best_a``; +inf where the class does not fit)."""

    def __init__(self, fleet: Fleet, dtype):
        width = int(fleet.slices.max())
        if width > NUM_SLICES_MAX:
            raise ValueError(f"device models of {width} slices are not supported")
        pats = 1 << width
        n_k, n_p = len(fleet.models), fleet.num_classes
        n_a = max(1, max(len(a) for per in fleet.anchors for a in per))
        self.popcount = np.array([bin(p).count("1") for p in range(pats)], dtype=np.int64)
        self.window = np.zeros((n_k, n_p, n_a), dtype=np.int64)
        self.anchor = np.full((n_k, n_p, n_a), -1, dtype=np.int64)
        for k in range(n_k):
            for p in range(n_p):
                for a, anchor in enumerate(fleet.anchors[k][p]):
                    self.window[k, p, a] = ((1 << int(fleet.mem[k, p])) - 1) << anchor
                    self.anchor[k, p, a] = anchor
        self.F = np.zeros((n_k, pats), dtype=np.int64)
        for k in range(n_k):
            for pat in range(1 << int(fleet.slices[k])):
                free = int(fleet.slices[k]) - int(self.popcount[pat])
                self.F[k, pat] = sum(
                    int(fleet.mem[k, p])
                    for p in range(n_p) for a in range(len(fleet.anchors[k][p]))
                    if pat & int(self.window[k, p, a]) and fleet.mem[k, p] <= free)
        delta = np.full((n_k, pats, n_p, n_a), np.inf)
        f = self.F.astype(dtype)
        for k in range(n_k):
            for pat in range(1 << int(fleet.slices[k])):
                for p in range(n_p):
                    for a in range(len(fleet.anchors[k][p])):
                        w = int(self.window[k, p, a])
                        if not pat & w:
                            delta[k, pat, p, a] = float(f[k, pat | w] - f[k, pat])
        self.best, self.best_a = delta.min(axis=-1), delta.argmin(axis=-1)


class Replay:
    """The state of every replica of one stream, and the steady event loop.

    ``pat``/``up`` are ``(R, M)``; ``live``/``gpu_of``/``a_of`` are
    ``(E_max, R)``, an arrival's event index being its workload id.
    """

    def __init__(self, stream: Stream, fleet: Fleet, policy, sim: dict,
                 fault: Optional[Fault], dtype=np.float64):
        self.s, self.fleet, self.policy = stream, fleet, policy
        self.sim, self.fault, self.dtype = sim, fault, dtype
        self.t = Tables(fleet, dtype)
        self.kg = fleet.model_of  # model index of each GPU
        self.free_slices = fleet.slices[fleet.model_of]
        e_max, runs = stream.pid.shape
        self.pat = np.zeros((runs, fleet.num_gpus), dtype=np.int64)
        self.up = np.ones((runs, fleet.num_gpus), dtype=bool)
        self.live = np.zeros((e_max, runs), dtype=bool)
        self.gpu_of = np.zeros((e_max, runs), dtype=np.int64)
        self.a_of = np.zeros((e_max, runs), dtype=np.int64)

    # -- scores --------------------------------------------------------------
    def scores(self, pat: np.ndarray) -> np.ndarray:
        """F of each GPU of rows of patterns ``(..., M)``."""
        return self.t.F[self.kg, pat]

    def total(self, pat: np.ndarray) -> np.ndarray:
        """The summed score of each row, accumulated in ``dtype``."""
        return self.scores(pat).astype(self.dtype).sum(axis=-1, dtype=self.dtype).astype(np.float64)

    def mean_score(self, pat: np.ndarray) -> np.ndarray:
        """The cluster-mean score of each row, summed and divided in ``dtype``."""
        tot = self.scores(pat).astype(self.dtype).sum(axis=-1, dtype=self.dtype)
        return (tot / self.dtype(self.fleet.num_gpus)).astype(np.float64)

    def window(self, g, pid, a):
        """The slice bits of class ``pid`` at anchor index ``a`` on GPU ``g``."""
        return self.t.window[self.kg[g], pid, a]

    # -- placements ----------------------------------------------------------
    def place(self, r, g, a, w):
        """Run workloads ``w`` of replicas ``r`` on GPUs ``g`` at anchor
        indices ``a`` (arrays of one length; one placement per replica)."""
        self.pat[r, g] |= self.window(g, self.s.pid[w, r], a)
        self.live[w, r] = True
        self.gpu_of[w, r], self.a_of[w, r] = g, a

    def release(self, r, w):
        """Stop workloads ``w`` of replicas ``r`` (arrays of one length)."""
        g = self.gpu_of[w, r]
        np.bitwise_and.at(self.pat, (r, g), ~self.window(g, self.s.pid[w, r], self.a_of[w, r]))
        self.live[w, r] = False

    def running(self, r):
        """Workloads running in replica ``r``, in (GPU, anchor) order, with
        their GPUs and anchors."""
        w = np.flatnonzero(self.live[:, r])
        g = self.gpu_of[w, r]
        anchor = self.t.anchor[self.kg[g], self.s.pid[w, r], self.a_of[w, r]]
        k = np.lexsort((anchor, g))
        return w[k], g[k], anchor[k]

    # -- the steady event loop ----------------------------------------------
    def steady(self, fields: Dict[str, object] = None,
               before: Optional[Callable] = None, after: Optional[Callable] = None):
        """Replay the stream event by event.  At each event: record the
        state left by the previous one (free slices, active GPUs, the
        cluster-mean score), end the leases that end in a new slot, run
        ``before(self, e, tr)``, place the arrivals by the policy (its
        ``on_reject`` may rescue a rejected one), then run
        ``after(self, e, ok, tr)``.  Returns the per-event decisions."""
        s = self.s
        e_max, runs = s.pid.shape
        # arrivals by (replica, end slot), to find the leases a slot ends
        span = int(s.end.max()) + 2
        arr_e, arr_r = np.nonzero(s.pid >= 0)
        key = arr_r * span + s.end[arr_e, arr_r]
        order = np.argsort(key, kind="stable")
        key, arr_e, arr_r = key[order], arr_e[order], arr_r[order]

        tr = dict(ok=np.zeros((e_max, runs), bool), gpu=np.zeros((e_max, runs), np.int64),
                  aidx=np.zeros((e_max, runs), np.int64),
                  free_sum=np.zeros((e_max, runs), np.int64),
                  active=np.zeros((e_max, runs), np.int64), frag=np.zeros((e_max, runs)))
        for name, fill in {**getattr(self.policy, "FIELDS", {}), **(fields or {})}.items():
            tr[name] = np.full((e_max, runs), fill)
        on_reject = getattr(self.policy, "on_reject", None)
        for e in range(e_max):
            pc = self.t.popcount[self.pat]
            tr["free_sum"][e] = (self.free_slices - pc).sum(axis=1)
            tr["active"][e] = (pc > 0).sum(axis=1)
            tr["frag"][e] = self.mean_score(self.pat)

            # the first event of a slot ends the leases that end in it
            rows = np.flatnonzero(s.new_slot[e])
            q = rows * span + s.slot[e, rows]
            lo = np.searchsorted(key, q, "left")
            n = np.searchsorted(key, q, "right") - lo
            idx = np.repeat(lo - np.cumsum(n) + n, n) + np.arange(n.sum())
            w, r = arr_e[idx], arr_r[idx]
            alive = self.live[w, r]
            self.release(r[alive], w[alive])

            if before is not None:
                before(self, e, tr)
            pid = s.pid[e]
            valid = pid >= 0
            g, a, ok = self.policy.select(self, self.pat, self.up, np.maximum(pid, 0))
            ok &= valid
            if on_reject is not None:
                for r in np.flatnonzero(valid & ~ok):
                    found = on_reject(self, e, r, int(pid[r]), tr)
                    if found is not None:
                        g[r], a[r] = found
                        ok[r] = True
            rows = np.flatnonzero(ok)
            self.place(rows, g[rows], a[rows], np.full(len(rows), e))
            tr["ok"][e], tr["gpu"][e], tr["aidx"][e] = ok, np.where(ok, g, 0), a
            if after is not None:
                after(self, e, ok, tr)
        return tr


def replay(stream: Stream, fleet: Fleet, policy: str, sim: dict,
           fault: Optional[Fault], dtype=np.float64):
    """The reference's per-event decisions on ``stream`` and its reduction
    of them to the numbers ``api.simulate`` returns: the policy's file
    places, the protocol's file runs the stages and reduces."""
    protocol = module("reference/protocols", sim["protocol"])
    ref = Replay(stream, fleet, module("reference/policies", policy), sim, fault, dtype)
    decisions = protocol.decide(ref)
    return decisions, protocol.reduce(ref, decisions)
