"""MFI (the paper's Alg. 2): place an arrival where the blocked
fragmentation score grows least; ties by GPU id, then by anchor index.
GPUs that are down take nothing."""

import numpy as np


def select(ref, pat, up, pid):
    """Rows of GPUs ``pat``/``up`` ``(N, M)`` and a class per row ``pid``
    ``(N,)``; returns ``(gpu, anchor index, ok)`` per row."""
    d = np.where(up, ref.t.best[ref.kg[None, :], pat, pid[:, None]], np.inf)
    g = d.argmin(axis=1)
    rows = np.arange(len(g))
    ok = np.isfinite(d[rows, g])
    return g, ref.t.best_a[ref.kg[g], pat[rows, g], pid], ok
