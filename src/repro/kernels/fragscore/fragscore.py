"""Pallas TPU kernels for batched MIG fragmentation scoring (paper Alg. 1/2).

TPU adaptation (DESIGN.md §5): the per-GPU python loop becomes bitmask
algebra — an (BLK_M, S) occupancy slab in VMEM against the constant
placement-window matrix Wᵀ (S, N), one small matmul per block plus VPU
predicates.  Cloud-scale schedulers score 10⁴–10⁶ GPUs per decision batch;
the M axis is tiled in BLK_M-row slabs.

Weights/constants are passed as operands (broadcast BlockSpec) so the same
compiled kernel serves any placement table: each :class:`DeviceModel`
(including the non-8-slice H200-141GB, ``S = 12``) supplies its own
``(N, S)`` window matrix — shapes are static per model, so a mixed fleet
dispatches one compiled kernel per model group.

Five kernels:

* :func:`fragscore` — F(m) from raw ``(M, S)`` occupancy bitmaps (Alg. 1);
* :func:`mfi_delta` — feasibility-masked ΔF over all (GPU, anchor)
  dry-runs from raw occupancy (Alg. 2's inner loop);
* :func:`delta_from_base` — the engine-hot-path form of the ΔF table: it
  consumes the *window-count state* ``base = occ @ Wᵀ`` (+ free counts and
  pre-scores) that :class:`repro.sim.batched.EngineCore` maintains
  incrementally, fusing eligibility, the occupied/cross split and the
  final subtraction into one launch — no occupancy materialization, no
  per-anchor hypothetical matmuls.  Mirrors
  :func:`repro.sim.batched._delta_from_base` bit-for-bit (all scores are
  integer-valued, hence exact in float32);
* :func:`select_from_base` — the *fused select*: ΔF **and** the masked
  lexicographic argmin of the policy's scoring keys in one launch; only
  per-tile winner rows ``(keys…, gpu, anchor-column, ok)`` leave VMEM, the
  ``(M, A)`` score table never round-trips through HBM;
* :func:`migrate_refine` — the *fused migrate-search* refinements: the
  per-class ``(P, M, A)`` untouched-row refinement reduced to best +
  runner-up per class (``_lex_top2``) as grid pass 0, and the per-victim
  ``O(C·A)`` patched-row refinement as grid pass 1 — one launch for both
  (the second grid dimension selects the pass).

The fused kernels take the policy's ordered keys as a static
``((base, sign), …)`` tuple.  Every key value is integer-valued (ΔF
included), hence exact in float32: equality-based masked refinement and
cross-tile lexicographic merges reproduce the pure-jnp total order
bit-for-bit.  See ``docs/KERNELS.md`` for the packing scheme.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NUM_SLICES = 8  # canonical A100-style geometry (kernels accept any S)
BLK_M = 512  # GPUs per VMEM slab (512×8 f32 = 16 KiB)

# Block-shape rule (Mosaic): the last two dims of every block are (8, 128)
# multiples or the array's own.  Under ``vmap`` a replica dim is prepended
# and squeezed, so a 1-D per-request operand such as ``(A,)`` becomes an
# ``(R, A)`` array with a squeezed second-minor dim, which Mosaic refuses.
# Per-request vectors therefore travel as ``(1, A)`` rows, per-request
# scalars as ``(1, 1)`` SMEM blocks (:func:`_smem`), and per-tile
# output rows as ``(1, W)`` trailing blocks of a ``(T, 1, W)`` array.


def _smem(shape):
    """A whole-array SMEM block for small per-call scalar tables."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape), memory_space=pltpu.SMEM)


def _score_block(occ, w, v, metric: str):
    """Score a (blk, S) occupancy slab.  occ f32, w (N, S) f32, v (N,) f32."""
    num_slices = occ.shape[-1]
    inwin = jnp.dot(occ, w.T, preferred_element_type=jnp.float32)  # (blk, N)
    if metric == "blocked":
        counted = inwin > 0
    else:  # partial
        counted = (inwin > 0) & (inwin < v[None, :])
    free = num_slices - jnp.sum(occ, axis=-1, keepdims=True)  # (blk, 1)
    eligible = v[None, :] <= free
    return jnp.sum(jnp.where(counted & eligible, v[None, :], 0.0), axis=-1)


def _fragscore_kernel(occ_ref, w_ref, v_ref, out_ref, *, metric: str):
    occ = occ_ref[...].astype(jnp.float32)  # (BLK_M, S)
    out_ref[...] = _score_block(occ, w_ref[...], v_ref[...], metric)[:, None]


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def fragscore(
    occ: jax.Array,
    w: jax.Array,
    v: jax.Array,
    *,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """F(m) for every GPU.

    Args:
      occ: (M, S) occupancy bitmap (any int/float dtype, any slice count S).
      w: (N, S) placement-window masks of the device model.
      v: (N,) memory-slice weights.
      metric: "blocked" | "partial".
      interpret: see :func:`repro.kernels.interpret_mode` (default: Mosaic
        on TPU, the interpreter elsewhere).

    Returns:
      (M,) float32.
    """
    m, s = occ.shape
    m_pad = -(-m // BLK_M) * BLK_M
    occ_p = jnp.zeros((m_pad, s), occ.dtype).at[:m].set(occ)

    out = pl.pallas_call(
        functools.partial(_fragscore_kernel, metric=metric),
        grid=(m_pad // BLK_M,),
        in_specs=[
            pl.BlockSpec((BLK_M, s), lambda i: (i, 0)),
            pl.BlockSpec((w.shape[0], s), lambda i: (0, 0)),
            pl.BlockSpec((v.shape[0],), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLK_M, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, 1), jnp.float32),
        interpret=interpret_mode(interpret),
        name="fragscore",
    )(occ_p.astype(jnp.float32), w.astype(jnp.float32), v.astype(jnp.float32))
    return out[:m, 0]


def _mfi_delta_kernel(occ_ref, w_ref, v_ref, pm_ref, pv_ref, out_ref, *, metric: str, max_anchors: int):
    """ΔF of placing the requested profile at each anchor, +inf if infeasible."""
    occ = occ_ref[...].astype(jnp.float32)  # (BLK_M, S)
    w = w_ref[...]
    v = v_ref[...]
    f_before = _score_block(occ, w, v, metric)  # (BLK_M,)
    big = jnp.float32(1e30)
    for a in range(max_anchors):  # unrolled: A <= 12
        mask = pm_ref[a, :]  # (S,)
        valid = pv_ref[a]  # scalar 0/1
        overlap = jnp.sum(occ * mask[None, :], axis=-1)  # (BLK_M,)
        feasible = (overlap == 0) & (valid > 0)
        hypo = jnp.minimum(occ + mask[None, :], 1.0)
        delta = _score_block(hypo, w, v, metric) - f_before
        out_ref[:, a] = jnp.where(feasible, delta, big)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def mfi_delta(
    occ: jax.Array,
    w: jax.Array,
    v: jax.Array,
    profile_masks: jax.Array,
    profile_valid: jax.Array,
    *,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused Algorithm-2 inner loop: ΔF over all (GPU, anchor) dry-runs.

    Args:
      occ: (M, S) occupancy.
      w, v: placement table as in :func:`fragscore`.
      profile_masks: (A, S) window masks of the *requested* profile's anchors
        (padded rows are zero).
      profile_valid: (A,) 1.0 for real anchors, 0.0 for padding.

    Returns:
      (M, A) float32 ΔF, +1e30 where the placement is infeasible.
    """
    m, s = occ.shape
    a = profile_masks.shape[0]
    m_pad = -(-m // BLK_M) * BLK_M
    occ_p = jnp.zeros((m_pad, s), occ.dtype).at[:m].set(occ)

    out = pl.pallas_call(
        functools.partial(_mfi_delta_kernel, metric=metric, max_anchors=a),
        grid=(m_pad // BLK_M,),
        in_specs=[
            pl.BlockSpec((BLK_M, s), lambda i: (i, 0)),
            pl.BlockSpec((w.shape[0], s), lambda i: (0, 0)),
            pl.BlockSpec((v.shape[0],), lambda i: (0,)),
            pl.BlockSpec((a, s), lambda i: (0, 0)),
            pl.BlockSpec((a,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLK_M, a), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, a), jnp.float32),
        interpret=interpret_mode(interpret),
        name="mfi_delta",
    )(
        occ_p.astype(jnp.float32),
        w.astype(jnp.float32),
        v.astype(jnp.float32),
        profile_masks.astype(jnp.float32),
        profile_valid.astype(jnp.float32),
    )
    return out[:m]


def _delta_block(base, free, f_before, v, mw, mp, mem, metric: str):
    """ΔF tile from window counts — the shared fused-ΔF math.

    Window counts after a feasible placement are ``base + mw`` (the anchor
    window is disjoint from current occupancy), so for the "blocked" metric
    the counted-predicate decomposes as ``(base > 0) | (mw > 0)`` and the
    whole (blk, A) tile is one (blk, N) × (N, A) matmul on the MXU plus
    VPU predicates; "partial" takes the dense (blk, A, N) elementwise
    path (A ≤ 12, N ≤ 31 — a few hundred KiB of VMEM).
    """
    free_after = free - mem                  # (blk,) — same for every anchor
    elig = v[None, :] <= free_after[:, None]  # (blk, N)
    if metric == "partial":
        ba = base[:, None, :] + mw[None, :, :]  # (blk, A, N)
        counted = (ba > 0) & (ba < v[None, None, :])
        f_after = jnp.sum(
            jnp.where(counted & elig[:, None, :], v[None, None, :], 0.0), axis=-1
        )
    else:  # blocked: counted_after = (base > 0) | (mw > 0)
        cb = base > 0                        # (blk, N)
        s_occ = jnp.sum(jnp.where(cb & elig, v[None, :], 0.0), axis=-1)  # (blk,)
        cross = jnp.dot(                     # (blk, A)
            jnp.where(~cb & elig, v[None, :], 0.0),
            mp.T,
            preferred_element_type=jnp.float32,
        )
        f_after = s_occ[:, None] + cross
    return f_after - f_before[:, None]


def _delta_rows(base, free, f_before, v, mw, mp, mem, metric: str):
    """Row-wise ΔF: every row is an independent GPU with its *own* window
    sizes ``v (blk, N)``, per-row anchor tables ``mw/mp (blk, A, N)`` and
    per-row slice demand ``mem (blk,)`` — the per-victim patched-row form.
    """
    free_after = free - mem                  # (blk,)
    elig = v <= free_after[:, None]          # (blk, N)
    if metric == "partial":
        ba = base[:, None, :] + mw           # (blk, A, N)
        counted = (ba > 0) & (ba < v[:, None, :])
        f_after = jnp.sum(
            jnp.where(counted & elig[:, None, :], v[:, None, :], 0.0), axis=-1
        )
    else:
        cb = base > 0                        # (blk, N)
        s_occ = jnp.sum(jnp.where(cb & elig, v, 0.0), axis=-1)  # (blk,)
        cross = jnp.sum(                     # (blk, A)
            jnp.where(~cb & elig, v, 0.0)[:, None, :] * mp, axis=-1
        )
        f_after = s_occ[:, None] + cross
    return f_after - f_before[:, None]


def _delta_from_base_kernel(
    base_ref, free_ref, f_ref, v_ref, mw_ref, mp_ref, mem_ref, out_ref,
    *, metric: str,
):
    """Fused ΔF dry-run table from the incremental window-count state."""
    out_ref[...] = _delta_block(
        base_ref[...], free_ref[...][:, 0], f_ref[...][:, 0], v_ref[...],
        mw_ref[...], mp_ref[...], mem_ref[0, 0], metric,
    )


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def delta_from_base(
    base: jax.Array,
    free: jax.Array,
    v: jax.Array,
    mw: jax.Array,
    mp: jax.Array,
    mem: jax.Array,
    f_before: jax.Array,
    *,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """ΔF of every anchor dry-run of one request, from window counts.

    The Pallas form of :func:`repro.sim.batched._delta_from_base` for one
    model group (all GPUs share the placement table ``v``); the batched
    engine dispatches one call per :class:`~repro.core.mig.ClusterSpec`
    model group.  Output is the *raw* ΔF (no feasibility masking) —
    exactly what the engine's masked-refinement select consumes.

    Args:
      base: (M, N) float32 — occupied-slice count per placement window.
      free: (M,) — free memory slices per GPU.
      v: (N,) float32 — placement-window sizes (0 where padded).
      mw: (A, N) float32 — slices the request's anchors add per window.
      mp: (A, N) float32 — ``mw > 0`` indicator.
      mem: scalar — the request's slice demand on this model.
      f_before: (M,) float32 — current F(m) scores.
      metric: "blocked" | "partial".
      interpret: see :func:`repro.kernels.interpret_mode` (default: Mosaic
        on TPU, the interpreter elsewhere).

    Returns:
      (M, A) float32 ΔF table.
    """
    m, n = base.shape
    a = mw.shape[0]
    m_pad = -(-m // BLK_M) * BLK_M
    base_p = jnp.zeros((m_pad, n), jnp.float32).at[:m].set(base)
    free_p = jnp.zeros((m_pad, 1), jnp.float32).at[:m, 0].set(
        free.astype(jnp.float32)
    )
    f_p = jnp.zeros((m_pad, 1), jnp.float32).at[:m, 0].set(f_before)

    out = pl.pallas_call(
        functools.partial(_delta_from_base_kernel, metric=metric),
        grid=(m_pad // BLK_M,),
        in_specs=[
            pl.BlockSpec((BLK_M, n), lambda i: (i, 0)),
            pl.BlockSpec((BLK_M, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_M, 1), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((a, n), lambda i: (0, 0)),
            pl.BlockSpec((a, n), lambda i: (0, 0)),
            _smem((1, 1)),
        ],
        out_specs=pl.BlockSpec((BLK_M, a), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, a), jnp.float32),
        interpret=interpret_mode(interpret),
        name="delta_from_base",
    )(
        base_p,
        free_p,
        f_p,
        v.astype(jnp.float32),
        mw.astype(jnp.float32),
        mp.astype(jnp.float32),
        jnp.reshape(mem, (1, 1)).astype(jnp.float32),
    )
    return out[:m]


# ---------------------------------------------------------------------------
# Fused select / migrate-search kernels: ΔF + lexicographic argmin in-kernel
# ---------------------------------------------------------------------------

#: the refinement sentinel — MUST equal ``repro.sim.batched._BIG`` so the
#: in-kernel masked refinements and the host-side cross-tile merges compare
#: against the same value the pure-jnp lowering uses.  Kept a Python float:
#: a module-level jax array would be captured as a constant by pallas kernels.
BIG = 1e9


#: victim rows per tile of the migrate search's pass 1.  Each victim row
#: carries its own ``(A, N)`` anchor table, which pads to a whole (8, 128)
#: tile in VMEM (4 KiB per row, double-buffered, plus ``(A, N)``-shaped
#: temporaries), so pass 1 tiles narrower than the per-GPU slabs of pass 0.
BLK_V = 128


def _blk_rows(m: int, cap: int = BLK_M) -> int:
    """Adaptive row-tile: whole problem when it fits, ``cap``-row slabs beyond.

    Fleets are usually far smaller than BLK_M; padding 16 rows to 512 would
    make every fused launch 32× wider than the work.  TPU f32 tiles are
    (8, 128), so round up to a multiple of 8.
    """
    return min(cap, -(-m // 8) * 8)


def padded_rows(m: int) -> int:
    """Rows a fused launch over ``m`` GPUs covers: ``m`` rounded up to a
    whole number of its row tiles (:func:`_blk_rows`)."""
    blk = _blk_rows(m)
    return -(-m // blk) * blk


def _key_tile(base_key, sign, delta, free, mem, gid, anchors, shape):
    """One effective scoring key as a (blk, A) tile (direction applied).

    ``anchors`` broadcasts along rows when it is a shared (A,) vector (the
    per-class form) and is taken as-is when per-row (blk, A) (the
    per-victim form); ``gid``/``free``/``mem`` are (blk,) / scalar-or-(blk,).
    Request-scoped keys never reach the kernels — they are constant over
    one request's candidates and are dropped from the effective key tuple
    by the dispatch builders.
    """
    if base_key == "frag-delta":
        val = delta
    elif base_key == "free-slices":
        val = jnp.broadcast_to((free - mem)[:, None], shape)
    elif base_key == "gpu":
        val = jnp.broadcast_to(gid[:, None], shape)
    elif base_key == "anchor":
        a2 = anchors if anchors.ndim == 2 else anchors[None, :]
        val = jnp.broadcast_to(a2, shape)
    else:  # pragma: no cover — guarded by PolicySpec.argmin_fusable
        raise ValueError(f"key {base_key!r} is not argmin-fusable")
    return -val if sign < 0 else val


def _refine_cols(feas, vals):
    """Masked per-row refinement along the anchor axis (``_refine_rows``'s
    total order): returns ``(okr (blk, 1), wincol (blk, 1) int32, keyr)``
    where ``keyr`` lists each key's winner-column value (blk, 1).

    Winner extraction is *unmasked* at the first surviving column
    (``argmax``-of-mask semantics, column 0 for all-infeasible rows) so the
    values match the jnp lowering's ``take_along_axis`` bit-for-bit even on
    rows no feasible anchor survives.
    """
    blk, a = feas.shape
    mask = feas
    for val in vals:
        mval = jnp.where(mask, val, BIG)
        mask = mask & (mval == jnp.min(mval, axis=-1, keepdims=True))
    okr = jnp.any(mask, axis=-1, keepdims=True)            # (blk, 1)
    cid = jax.lax.broadcasted_iota(jnp.int32, (blk, a), 1)
    wincol = jnp.min(jnp.where(mask, cid, a), axis=-1, keepdims=True)
    wincol = jnp.where(okr, wincol, 0)                     # (blk, 1)
    w = cid == wincol
    keyr = [
        jnp.sum(jnp.where(w, val, 0.0), axis=-1, keepdims=True) for val in vals
    ]
    return okr, wincol, keyr


def _tile_top2(okr, wincol, keyr, gid2):
    """Cross-row lexicographic top-2 of per-row winners inside one tile.

    Rows ascend in global GPU id, so the in-tile row order *is* the
    ``_lex_top2`` ascending-row tie-break.  Returns two candidate rows
    ``[keys…, gpu, col, ok]`` (keys masked to BIG, gpu/col zeroed when not
    ok) ready for the host-side cross-tile merge by ``(keys…, gpu)``.
    """
    blk = okr.shape[0]
    rid = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)

    def best(rmask):
        for kv in keyr:
            mval = jnp.where(rmask, kv, BIG)
            rmask = rmask & (mval == jnp.min(mval))
        winrow = jnp.min(jnp.where(rmask, rid, blk))
        w = rmask & (rid == winrow)
        ok = jnp.any(rmask)
        okf = ok.astype(jnp.float32)
        pick = lambda t: jnp.sum(jnp.where(w, t, 0.0))  # noqa: E731
        row = [jnp.where(ok, pick(kv), BIG) for kv in keyr]
        row += [
            pick(gid2) * okf,
            pick(wincol.astype(jnp.float32)) * okf,
            okf,
        ]
        return winrow, row

    r1, row1 = best(okr)
    _, row2 = best(okr & (rid != r1))
    return row1 + row2


def _select_from_base_kernel(
    base_ref, free_ref, f_ref, gidx_ref, live_ref,
    v_ref, mw_ref, mp_ref, mem_ref, rowsel_ref, valid_ref, anchors_ref,
    out_ref, *, metric: str, keys,
):
    """Fused select: ΔF + masked lexicographic argmin, one winner row out."""
    base = base_ref[...]                      # (blk, N)
    free = free_ref[...][:, 0]                # (blk,)
    f = f_ref[...][:, 0]
    gid = gidx_ref[...][:, 0]
    live = live_ref[...][:, 0] > 0
    mem = mem_ref[0, 0]
    blk = base.shape[0]
    a = valid_ref.shape[-1]

    # feasibility: the request's anchor windows hold zero occupied slices —
    # a one-hot gather ``base @ rowsel`` on the MXU (exact: single terms)
    overlap = jnp.dot(base, rowsel_ref[...], preferred_element_type=jnp.float32)
    feas = (overlap == 0) & (valid_ref[...] > 0) & live[:, None]

    delta = None
    if any(b == "frag-delta" for b, _ in keys):
        delta = _delta_block(base, free, f, v_ref[...], mw_ref[...],
                             mp_ref[...], mem, metric)
    vals = [
        _key_tile(b, s, delta, free, mem, gid, anchors_ref[...], (blk, a))
        for b, s in keys
    ]

    # tile-global masked refinement — ``_lower_select``'s total order
    mask = feas
    for val in vals:
        mval = jnp.where(mask, val, BIG)
        mask = mask & (mval == jnp.min(mval))
    rid = jax.lax.broadcasted_iota(jnp.int32, (blk, a), 0)
    cid = jax.lax.broadcasted_iota(jnp.int32, (blk, a), 1)
    flat = rid * a + cid                      # rows ascend in global gpu id
    win = mask & (flat == jnp.min(jnp.where(mask, flat, blk * a)))
    ok = jnp.any(mask)
    okf = ok.astype(jnp.float32)
    pick = lambda t: jnp.sum(jnp.where(win, t, 0.0))  # noqa: E731
    row = [jnp.where(ok, pick(val), BIG) for val in vals]
    row += [
        pick(jnp.broadcast_to(gid[:, None], (blk, a))) * okf,
        pick(cid.astype(jnp.float32)) * okf,
        okf,
    ]
    out_ref[...] = jnp.stack(row)[None, :]


@functools.partial(jax.jit, static_argnames=("keys", "metric", "interpret"))
def select_from_base(
    base: jax.Array,
    free: jax.Array,
    f_before: jax.Array,
    gidx: jax.Array,
    v: jax.Array,
    mw: jax.Array,
    mp: jax.Array,
    mem: jax.Array,
    rowsel: jax.Array,
    valid: jax.Array,
    anchors: jax.Array,
    *,
    keys,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused select over one model group: per-tile winner rows.

    Evaluates the ΔF table *and* reduces it through the policy's masked
    lexicographic refinement in one launch — the ``(M, A)`` score table
    never leaves VMEM.  Only ``T = ceil(M / blk)`` winner rows
    ``[signed key values…, gpu, anchor-column, ok]`` (keys BIG / gpu,col 0
    when the tile has no feasible candidate) reach HBM; the caller merges
    tiles (and model groups) by ``(keys…, gpu, col)`` — exactly
    ``_lower_select``'s total order, since rows ascend in global GPU id and
    every key value is integer-valued (exact in float32).

    Args:
      base: (M, N) window counts of this group's GPUs.
      free: (M,) free slices; f_before: (M,) current F(m).
      gidx: (M,) *global* GPU ids of the group's rows (ascending).
      v/mw/mp/mem: the group's placement table and the request class's
        anchor tables, as in :func:`delta_from_base`.
      rowsel: (N, A) one-hot of ``profile_rows`` — feasibility gather.
      valid: (A,) anchor validity (1.0 real / 0.0 padded).
      anchors: (A,) anchor *values* (``profile_anchors``).
      Both travel to the kernel as ``(1, A)`` rows.
      keys: static ``((base_key, sign), …)`` effective scoring keys.

    Returns:
      (T, L + 3) float32 winner rows, ``L = len(keys)``.
    """
    m, n = base.shape
    a = mw.shape[0]
    blk = _blk_rows(m)
    m_pad = padded_rows(m)
    t = m_pad // blk
    base_p = jnp.zeros((m_pad, n), jnp.float32).at[:m].set(base)
    col = lambda x: jnp.zeros((m_pad, 1), jnp.float32).at[:m, 0].set(  # noqa: E731
        x.astype(jnp.float32)
    )
    live_p = jnp.zeros((m_pad, 1), jnp.float32).at[:m, 0].set(1.0)
    l = len(keys)

    out = pl.pallas_call(
        functools.partial(_select_from_base_kernel, metric=metric, keys=keys),
        grid=(t,),
        in_specs=[
            pl.BlockSpec((blk, n), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((a, n), lambda i: (0, 0)),
            pl.BlockSpec((a, n), lambda i: (0, 0)),
            _smem((1, 1)),
            pl.BlockSpec((n, a), lambda i: (0, 0)),
            pl.BlockSpec((1, a), lambda i: (0, 0)),
            pl.BlockSpec((1, a), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, l + 3), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1, l + 3), jnp.float32),
        interpret=interpret_mode(interpret),
        name="select_from_base",
    )(
        base_p,
        col(free),
        col(f_before),
        col(gidx),
        live_p,
        v.astype(jnp.float32),
        mw.astype(jnp.float32),
        mp.astype(jnp.float32),
        jnp.reshape(mem, (1, 1)).astype(jnp.float32),
        rowsel.astype(jnp.float32),
        valid.astype(jnp.float32).reshape(1, a),
        anchors.astype(jnp.float32).reshape(1, a),
    )
    return out.reshape(t, l + 3)


def _class_pass_impl(
    base_ref, free_ref, f_ref, gidx_ref, live_ref, v_ref,
    mw_all_ref, mp_all_ref, mem_all_ref, rowsel_all_ref, valid_all_ref,
    anchors_all_ref, out0_ref, *, metric: str, keys,
):
    """Pass 0: per-class untouched-row refinement + in-tile top-2.

    The demand-class loop is unrolled (P = 6); each class emits two
    candidate rows ``[keys…, gpu, col, ok]`` — the tile's best and
    runner-up per ``_lex_top2``'s order — into a (1, P, 2·(L+3)) block.
    """
    base = base_ref[...]                      # (blk, N)
    free = free_ref[...][:, 0]
    f = f_ref[...][:, 0]
    gid = gidx_ref[...][:, 0]
    live = live_ref[...][:, 0] > 0
    v = v_ref[...]
    blk = base.shape[0]
    p_, a = valid_all_ref.shape
    gid2 = gid[:, None]
    need_delta = any(b == "frag-delta" for b, _ in keys)
    rows = []
    for p in range(p_):
        mem = mem_all_ref[0, p]
        overlap = jnp.dot(
            base, rowsel_all_ref[p], preferred_element_type=jnp.float32
        )
        feas = (overlap == 0) & (valid_all_ref[p][None, :] > 0) & live[:, None]
        delta = None
        if need_delta:
            delta = _delta_block(
                base, free, f, v, mw_all_ref[p], mp_all_ref[p], mem, metric
            )
        vals = [
            _key_tile(b, s, delta, free, mem, gid, anchors_all_ref[p], (blk, a))
            for b, s in keys
        ]
        okr, wincol, keyr = _refine_cols(feas, vals)
        rows.append(jnp.stack(_tile_top2(okr, wincol, keyr, gid2)))
    out0_ref[...] = jnp.stack(rows)[None]


def _victim_pass_impl(
    base2_ref, free2_ref, f2_ref, vgid_ref, vv_ref, vmw_ref,
    vmem_ref, vrows_ref, vvalid_ref, vanchors_ref, out1_ref,
    *, metric: str, keys,
):
    """Pass 1: per-victim patched-row refinement.

    Every row is an independent victim with its *own* model tables (mixed
    fleets gather per victim) — the row-wise ΔF form.  Emits
    ``[keys…, col, ok]`` per victim; column 0 (unmasked values) when no
    anchor survives, matching the jnp path's argmax-of-mask semantics.
    The anchor windows' occupied counts are gathered in-kernel from the
    ``(blk, A)`` placement-row ids (one select per window), and the
    ``mw > 0`` indicator is derived from ``mw``: per-victim ``(N, A)``
    one-hots and indicator tables would pad to whole VMEM tiles per row.
    """
    base2 = base2_ref[...]                    # (blk, N)
    free2 = free2_ref[...][:, 0]
    f2 = f2_ref[...][:, 0]
    vgid = vgid_ref[...][:, 0]
    vmem = vmem_ref[...][:, 0]
    rows = vrows_ref[...]                     # (blk, A) float row ids
    blk, n = base2.shape
    a = vvalid_ref.shape[-1]
    overlap = jnp.zeros((blk, a), jnp.float32)
    for j in range(n):  # exactly one window per anchor: exact sum
        overlap = overlap + jnp.where(rows == j, base2[:, j:j + 1], 0.0)
    feas = (overlap == 0) & (vvalid_ref[...] > 0)
    delta = None
    if any(b == "frag-delta" for b, _ in keys):
        vmw = vmw_ref[...]
        delta = _delta_rows(
            base2, free2, f2, vv_ref[...], vmw,
            (vmw > 0).astype(jnp.float32), vmem, metric,
        )
    vals = [
        _key_tile(b, s, delta, free2, vmem, vgid, vanchors_ref[...], (blk, a))
        for b, s in keys
    ]
    okr, wincol, keyr = _refine_cols(feas, vals)
    out1_ref[...] = jnp.concatenate(
        keyr + [wincol.astype(jnp.float32), okr.astype(jnp.float32)], axis=1
    )


def _migrate_class_kernel(*refs, metric: str, keys):
    _class_pass_impl(*refs, metric=metric, keys=keys)


def _migrate_refine_kernel(*refs, metric: str, keys):
    """Both migrate refinements in one launch; the second grid dimension
    selects the pass.  ``pl.program_id`` counts the kernel's own grid axes
    only, so the replica axis ``vmap`` prepends does not shift it."""
    pid = pl.program_id(1)
    class_in, victim_in = refs[:12], refs[12:22]
    out0_ref, out1_ref = refs[22], refs[23]

    @pl.when(pid == 0)
    def _():
        _class_pass_impl(*class_in, out0_ref, metric=metric, keys=keys)

    @pl.when(pid == 1)
    def _():
        _victim_pass_impl(*victim_in, out1_ref, metric=metric, keys=keys)


def _pad_rows(x, m, m_pad):
    shp = (m_pad,) + x.shape[1:]
    return jnp.zeros(shp, jnp.float32).at[:m].set(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("keys", "metric", "interpret"))
def migrate_refine(
    base: jax.Array,
    free: jax.Array,
    f_before: jax.Array,
    gidx: jax.Array,
    v: jax.Array,
    mw_all: jax.Array,
    mp_all: jax.Array,
    mem_all: jax.Array,
    rowsel_all: jax.Array,
    valid_all: jax.Array,
    anchors_all: jax.Array,
    victims=None,
    *,
    keys,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
):
    """Fused migrate-search refinements over one model group.

    Pass 0 (tiled over the group's ``M`` GPUs) runs the per-class
    ``(P, M, A)`` untouched-row refinement — ΔF, feasibility, per-row
    anchor refinement, and the cross-row best/runner-up reduction — and
    emits two candidate rows ``[keys…, gpu, col, ok]`` per class per tile
    (keys BIG when not ok).  With ``victims`` (the per-victim gathered
    tables — mixed fleets gather per row, so one call covers every victim
    regardless of model), the per-victim ``O(C·A)`` patched-row refinement
    is fused as grid pass 1 of the *same* launch: grid ``(T, 2)``, the
    second dimension selecting the pass, input index maps clamped to each
    pass's own tile range (revisits rewrite identical content).

    Args:
      base/free/f_before/gidx: the group's window-count state + global ids.
      v: (N,) group placement-window sizes.
      mw_all/mp_all: (P, A, N) per-class anchor tables; mem_all: (P,).
      rowsel_all: (P, N, A) one-hot feasibility gathers; valid_all /
        anchors_all: (P, A).
      victims: optional tuple ``(base2, free2, f2, vgid, vv, vmw, vmem,
        vrows, vvalid, vanchors)`` of per-victim (C, …) tables; ``vrows``
        (C, A) holds each anchor's placement-row id (``profile_rows``).
      keys: static ``((base_key, sign), …)`` effective scoring keys.

    Returns:
      ``(out0, out1)`` — out0 (T0, P, 2·(L+3)) candidate pairs, out1
      (C, L+2) per-victim ``[keys…, col, ok]`` rows (``None`` without
      ``victims``).
    """
    m, n = base.shape
    p_, a, _ = mw_all.shape
    l = len(keys)
    w0 = 2 * (l + 3)
    blk0 = _blk_rows(m)
    m_pad = -(-m // blk0) * blk0
    t0 = m_pad // blk0

    col = lambda x: _pad_rows(x.reshape(-1, 1), m, m_pad)  # noqa: E731
    class_ops = (
        _pad_rows(base, m, m_pad),
        col(free),
        col(f_before),
        col(gidx),
        _pad_rows(jnp.ones((m, 1)), m, m_pad),
        v.astype(jnp.float32),
        mw_all.astype(jnp.float32),
        mp_all.astype(jnp.float32),
        mem_all.astype(jnp.float32).reshape(1, p_),
        rowsel_all.astype(jnp.float32),
        valid_all.astype(jnp.float32),
        anchors_all.astype(jnp.float32),
    )

    if victims is None:
        class_specs = [
            pl.BlockSpec((blk0, n), lambda i: (i, 0)),
            pl.BlockSpec((blk0, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk0, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk0, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk0, 1), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((p_, a, n), lambda i: (0, 0, 0)),
            pl.BlockSpec((p_, a, n), lambda i: (0, 0, 0)),
            _smem((1, p_)),
            pl.BlockSpec((p_, n, a), lambda i: (0, 0, 0)),
            pl.BlockSpec((p_, a), lambda i: (0, 0)),
            pl.BlockSpec((p_, a), lambda i: (0, 0)),
        ]
        out0 = pl.pallas_call(
            functools.partial(_migrate_class_kernel, metric=metric, keys=keys),
            grid=(t0,),
            in_specs=class_specs,
            out_specs=pl.BlockSpec((1, p_, w0), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((t0, p_, w0), jnp.float32),
            interpret=interpret_mode(interpret),
        name="migrate_refine",
        )(*class_ops)
        return out0, None

    (base2, free2, f2, vgid, vv, vmw, vmem, vrows, vvalid, vanchors) = victims
    c = base2.shape[0]
    blk1 = _blk_rows(c, BLK_V)
    c_pad = -(-c // blk1) * blk1
    t1 = c_pad // blk1
    t = max(t0, t1)

    colv = lambda x: _pad_rows(x.reshape(-1, 1), c, c_pad)  # noqa: E731
    victim_ops = (
        _pad_rows(base2, c, c_pad),
        colv(free2),
        colv(f2),
        colv(vgid),
        _pad_rows(vv, c, c_pad),
        _pad_rows(vmw, c, c_pad),
        colv(vmem),
        _pad_rows(vrows, c, c_pad),
        _pad_rows(vvalid, c, c_pad),  # zero-padded validity masks pad victims
        _pad_rows(vanchors, c, c_pad),
    )

    i0 = lambda i, j: (jnp.minimum(i, t0 - 1), 0)  # noqa: E731
    i1 = lambda i, j: (jnp.minimum(i, t1 - 1), 0)  # noqa: E731
    in_specs = [
        # -- pass 0 operands (clamped to the class tiles) -------------------
        pl.BlockSpec((blk0, n), i0),
        pl.BlockSpec((blk0, 1), i0),
        pl.BlockSpec((blk0, 1), i0),
        pl.BlockSpec((blk0, 1), i0),
        pl.BlockSpec((blk0, 1), i0),
        pl.BlockSpec((n,), lambda i, j: (0,)),
        pl.BlockSpec((p_, a, n), lambda i, j: (0, 0, 0)),
        pl.BlockSpec((p_, a, n), lambda i, j: (0, 0, 0)),
        _smem((1, p_)),
        pl.BlockSpec((p_, n, a), lambda i, j: (0, 0, 0)),
        pl.BlockSpec((p_, a), lambda i, j: (0, 0)),
        pl.BlockSpec((p_, a), lambda i, j: (0, 0)),
        # -- pass 1 operands (clamped to the victim tiles) ------------------
        pl.BlockSpec((blk1, n), i1),
        pl.BlockSpec((blk1, 1), i1),
        pl.BlockSpec((blk1, 1), i1),
        pl.BlockSpec((blk1, 1), i1),
        pl.BlockSpec((blk1, n), i1),
        pl.BlockSpec((blk1, a, n), lambda i, j: (jnp.minimum(i, t1 - 1), 0, 0)),
        pl.BlockSpec((blk1, 1), i1),
        pl.BlockSpec((blk1, a), i1),
        pl.BlockSpec((blk1, a), i1),
        pl.BlockSpec((blk1, a), i1),
    ]
    out0, out1 = pl.pallas_call(
        functools.partial(_migrate_refine_kernel, metric=metric, keys=keys),
        grid=(t, 2),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, p_, w0), lambda i, j: (jnp.minimum(i, t0 - 1), 0, 0)),
            pl.BlockSpec((blk1, l + 2), i1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t0, p_, w0), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, l + 2), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name="migrate_refine",
    )(*class_ops, *victim_ops)
    return out0, out1[:c]
