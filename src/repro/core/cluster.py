"""Vectorized, jittable cluster scheduling in JAX.

The paper's Algorithms 1/2 are per-GPU python loops.  On TPU we recast them
as batched bitmask algebra (DESIGN.md §5): cluster occupancy ``X (M, 8)``
against the constant placement-window matrix ``Wᵀ (8, 18)``, partial-window
predicate and weighted reduction — one fused launch per scheduling decision.

Everything here is pure ``jnp`` and jit-compatible with a *traced* profile
id, which lets the serving engine batch scheduling decisions.  The Pallas
kernels in :mod:`repro.kernels.fragscore` / :mod:`repro.kernels.mfi_select`
implement the same math with explicit VMEM tiling; this module doubles as
their oracle at cluster scale.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mig

MAX_ANCHORS = max(p.num_placements for p in mig.PROFILES)  # 7


class DeviceTables(NamedTuple):
    """One device model's placement tables as jnp constants.

    Shapes (N = flattened placements, A = padded anchor count, S = slices):
      ``placement_masks (N, S)`` / ``placement_mem (N,)`` — flattened table;
      ``profile_masks (P, A, S)`` / ``profile_anchors (P, A)`` /
      ``profile_valid (P, A)`` — per-class padded anchor views.
    """

    placement_masks: jax.Array
    placement_mem: jax.Array
    profile_masks: jax.Array
    profile_anchors: jax.Array
    profile_valid: jax.Array

    @property
    def num_mem_slices(self) -> int:
        return self.placement_masks.shape[1]


def _np_profile_tables(
    model: mig.DeviceModel, max_anchors: int = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-profile padded anchor tables of one device model.

    Returns:
      masks:   (P, A_max, S) int32 — placement window bitmask (0 where padded)
      anchors: (P, A_max)    int32 — anchor index (-1 where padded)
      valid:   (P, A_max)    bool  — anchor validity
    """
    P = mig.NUM_PROFILES
    A = max_anchors if max_anchors is not None else model.max_anchors
    masks = np.zeros((P, A, model.num_mem_slices), dtype=np.int32)
    anchors = np.full((P, A), -1, dtype=np.int32)
    valid = np.zeros((P, A), dtype=bool)
    for pid, prof in enumerate(model.profiles):
        for j, a in enumerate(prof.anchors):
            masks[pid, j, a : a + prof.mem] = 1
            anchors[pid, j] = a
            valid[pid, j] = True
    return masks, anchors, valid


@functools.lru_cache(maxsize=None)
def tables_for(model: mig.DeviceModel, max_anchors: int = None) -> DeviceTables:
    """Build (and cache) the jnp placement tables of a device model."""
    masks, anchors, valid = _np_profile_tables(model, max_anchors)
    return DeviceTables(
        placement_masks=jnp.asarray(model.placement_masks, dtype=jnp.float32),
        placement_mem=jnp.asarray(model.placement_mem, dtype=jnp.float32),
        profile_masks=jnp.asarray(masks),
        profile_anchors=jnp.asarray(anchors),
        profile_valid=jnp.asarray(valid),
    )


_PROFILE_MASKS_NP, _PROFILE_ANCHORS_NP, _PROFILE_VALID_NP = _np_profile_tables(
    mig.A100_80GB
)

# Constant A100-80GB tables (host numpy; closed over by jitted fns as
# literals) — the defaults whenever no ``tables`` argument is passed.
PLACEMENT_MASKS = jnp.asarray(mig.PLACEMENT_MASKS, dtype=jnp.float32)  # (18, 8)
PLACEMENT_MEM = jnp.asarray(mig.PLACEMENT_MEM, dtype=jnp.float32)  # (18,)
PROFILE_MASKS = jnp.asarray(_PROFILE_MASKS_NP)  # (P, 7, 8)
PROFILE_ANCHORS = jnp.asarray(_PROFILE_ANCHORS_NP)  # (P, 7)
PROFILE_VALID = jnp.asarray(_PROFILE_VALID_NP)  # (P, 7)
PROFILE_MEM = jnp.asarray(mig.PROFILE_MEM)  # (P,)

_DEFAULT_TABLES = DeviceTables(
    placement_masks=PLACEMENT_MASKS,
    placement_mem=PLACEMENT_MEM,
    profile_masks=PROFILE_MASKS,
    profile_anchors=PROFILE_ANCHORS,
    profile_valid=PROFILE_VALID,
)


def frag_scores(
    occ: jax.Array, metric: str = "blocked", tables: DeviceTables = None
) -> jax.Array:
    """F(m) for every same-model GPU.  occ: (M, S) int — returns (M,) float32."""
    t = _DEFAULT_TABLES if tables is None else tables
    occf = occ.astype(jnp.float32)
    occ_in_window = occf @ t.placement_masks.T  # (M, N)
    size = t.placement_mem[None, :]
    if metric == "blocked":
        counted = occ_in_window > 0
    elif metric == "partial":
        counted = (occ_in_window > 0) & (occ_in_window < size)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    free = t.num_mem_slices - occf.sum(axis=1, keepdims=True)  # (M, 1)
    eligible = size <= free
    return jnp.sum(jnp.where(counted & eligible, size, 0.0), axis=1)


class MFIDecision(NamedTuple):
    gpu: jax.Array      # int32, -1 when rejected
    anchor: jax.Array   # int32, -1 when rejected
    accepted: jax.Array  # bool
    delta_f: jax.Array  # float32 ΔF of the chosen placement (0 when rejected)


def placement_feasibility(
    occ: jax.Array, profile_id: jax.Array, tables: DeviceTables = None,
    gpu_ok: jax.Array = None,
) -> jax.Array:
    """(M, A) bool — anchors of ``profile_id`` whose window is fully free.

    Columns follow ``tables.profile_anchors[profile_id]`` (ascending anchor
    order); padded anchor columns are always infeasible.  ``gpu_ok`` is an
    optional (M,) bool availability mask (False rows — e.g. failed GPUs —
    are infeasible regardless of occupancy).
    """
    t = _DEFAULT_TABLES if tables is None else tables
    masks = t.profile_masks[profile_id]  # (A, S) int32
    valid = t.profile_valid[profile_id]  # (A,)
    occf = occ.astype(jnp.float32)
    overlap = occf @ masks.T.astype(jnp.float32)  # (M, A)
    feasible = (overlap == 0) & valid[None, :]
    if gpu_ok is not None:
        feasible = feasible & gpu_ok[:, None]
    return feasible


def placement_delta_f(
    occ: jax.Array,
    profile_id: jax.Array,
    metric: str = "blocked",
    frag_fn=None,
    tables: DeviceTables = None,
) -> jax.Array:
    """(M, A) float32 — ΔF of every dry-run placement of ``profile_id``.

    ``frag_fn`` maps an (N, S) occupancy to (N,) scores; defaults to the
    pure-jnp :func:`frag_scores` (the Pallas ``fragscore`` kernel is a
    drop-in — see :mod:`repro.kernels.fragscore.ops`).
    """
    t = _DEFAULT_TABLES if tables is None else tables
    if frag_fn is None:
        frag_fn = functools.partial(frag_scores, metric=metric, tables=tables)
    masks = t.profile_masks[profile_id]  # (A, S) int32
    f_before = frag_fn(occ)  # (M,)
    hypo = jnp.minimum(occ[:, None, :] + masks[None, :, :], 1)  # (M, A, S)
    f_after = frag_fn(hypo.reshape(-1, t.num_mem_slices)).reshape(
        occ.shape[0], -1
    )  # (M, A)
    return f_after - f_before[:, None]


@functools.partial(jax.jit, static_argnames=("metric", "use_kernel", "interpret"))
def mfi_select(
    occ: jax.Array,
    profile_id: jax.Array,
    metric: str = "blocked",
    tables: DeviceTables = None,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> MFIDecision:
    """Algorithm 2's argmin over all feasible (GPU, anchor) dry-runs.

    The single entry point for both lowerings: the pure-jnp dense dry-run
    (default) and the fused Pallas ``mfi_delta`` kernel (``use_kernel=True``
    — feasibility + ΔF in one launch; ``interpret`` as in
    :func:`repro.kernels.interpret_mode`).  Both produce the identical
    decision: scores are integer-valued, the argmin's first-occurrence
    tie-break is shared.

    Args:
      occ: (M, S) int32 occupancy of same-model GPUs (``tables`` selects the
        model; default A100-80GB).
      profile_id: scalar int32 (traced — one jit serves all profiles).
    """
    t = _DEFAULT_TABLES if tables is None else tables
    anchors = t.profile_anchors[profile_id]  # (A,)
    if use_kernel:
        from repro.kernels.fragscore import fragscore as _k

        big = jnp.float32(1e30)  # the kernel's own infeasibility sentinel
        scored = _k.mfi_delta(
            occ,
            t.placement_masks,
            t.placement_mem,
            t.profile_masks[profile_id],
            t.profile_valid[profile_id].astype(jnp.float32),
            metric=metric,
            interpret=interpret,
        )
    else:
        feasible = placement_feasibility(occ, profile_id, tables)
        delta = placement_delta_f(occ, profile_id, metric, tables=tables)
        big = jnp.float32(1e9)
        scored = jnp.where(feasible, delta, big)
    flat = scored.reshape(-1)
    k = jnp.argmin(flat)  # first occurrence == (gpu, anchor) lexicographic tie-break
    accepted = flat[k] < big
    gpu = jnp.where(accepted, k // scored.shape[1], -1).astype(jnp.int32)
    aidx = k % scored.shape[1]
    anchor = jnp.where(accepted, anchors[aidx], -1).astype(jnp.int32)
    return MFIDecision(gpu, anchor, accepted, jnp.where(accepted, flat[k], 0.0))


@functools.partial(jax.jit, static_argnames=("metric",))
def mfi_allocate(
    occ: jax.Array,
    profile_id: jax.Array,
    metric: str = "blocked",
    tables: DeviceTables = None,
) -> Tuple[jax.Array, MFIDecision]:
    """Select AND commit: returns (new_occ, decision).  Pure/jittable."""
    t = _DEFAULT_TABLES if tables is None else tables
    d = mfi_select(occ, profile_id, metric, tables)
    masks = t.profile_masks[profile_id]  # (A, S)
    aidx = jnp.argmax(t.profile_anchors[profile_id] == d.anchor)
    mask = masks[aidx] * d.accepted.astype(jnp.int32)  # zero mask when rejected
    row = jnp.where(d.accepted, d.gpu, 0)
    new_occ = occ.at[row].set(jnp.minimum(occ[row] + mask, 1))
    return new_occ, d


@jax.jit
def release(
    occ: jax.Array,
    gpu: jax.Array,
    profile_id: jax.Array,
    anchor: jax.Array,
    tables: DeviceTables = None,
) -> jax.Array:
    """Free a previously committed placement (jittable)."""
    t = _DEFAULT_TABLES if tables is None else tables
    aidx = jnp.argmax(t.profile_anchors[profile_id] == anchor)
    mask = t.profile_masks[profile_id][aidx]
    return occ.at[gpu].set(jnp.maximum(occ[gpu] - mask, 0))
