"""Pallas TPU flash-decode kernel: one new token attending to a GQA KV cache.

Blocking (TPU-native, DESIGN.md §5/6):
  * grid = (B, K, S/BLK_S): batch × kv-head × sequence blocks; the sequence
    axis is the innermost (sequential) grid dimension, so the online-softmax
    accumulators live in VMEM scratch across S-blocks.
  * per step the kernel holds a (G, D) query tile (the kv-head's query
    group), a (BLK_S, D) key tile and a (BLK_S, D) value tile in VMEM —
    BLK_S×D is lane-aligned (D ∈ {64..256} multiples of 64, BLK_S multiple
    of 128).
  * accumulators: running max m (G, 1), normaliser l (G, 1), weighted sum
    acc (G, D), all f32; output written on the last S-block.

Numerics follow the standard flash recurrence; masking of padded KV entries
uses a per-batch ``length`` operand.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_BLK_S = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, blk_s):
    sb = pl.program_id(2)
    nsb = pl.num_programs(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)  # (BLK_S, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)  # (BLK_S, D)

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, BLK_S)

    # mask out entries beyond the valid KV length of this batch row
    length = len_ref[0]
    pos = sb * blk_s + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < length, logits, -jnp.inf)

    m_prev = m_ref[...]  # (G, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard against all -inf blocks (fully masked): exp(-inf - -inf) -> nan
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - safe_m)  # (G, BLK_S)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)  # (G, 1)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(sb == nsb - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "blk_s", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, S, K, D)
    v: jax.Array,  # (B, S, K, D)
    length: jax.Array,  # (B,) int32 valid KV length
    *,
    scale: float | None = None,
    blk_s: int = DEFAULT_BLK_S,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, h, d = q.shape
    s, kheads = k.shape[1], k.shape[2]
    assert h % kheads == 0, (h, kheads)
    g = h // kheads
    if scale is None:
        scale = float(d) ** -0.5

    blk_s = min(blk_s, s)
    s_pad = -(-s // blk_s) * blk_s
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    qg = q.reshape(b, kheads, g, d)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blk_s=blk_s),
        grid=(b, kheads, s_pad // blk_s),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, ki, si: (bi,)),
            pl.BlockSpec((1, 1, g, d), lambda bi, ki, si: (bi, ki, 0, 0)),
            pl.BlockSpec((1, blk_s, 1, d), lambda bi, ki, si: (bi, si, ki, 0)),
            pl.BlockSpec((1, blk_s, 1, d), lambda bi, ki, si: (bi, si, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, ki, si: (bi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kheads, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(length.astype(jnp.int32), qg, k, v)
    return out.reshape(b, h, d)
