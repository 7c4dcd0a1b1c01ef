"""Public wrapper for the flash-decode kernel with CPU fallback selection."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention as _kernel
from repro.kernels.decode_attention.ref import decode_attention_ref


def gqa_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    length: jax.Array | None = None,
    *,
    scale: float | None = None,
    blk_s: int = 512,
    use_kernel: bool = True,
) -> jax.Array:
    """GQA decode attention: (B,H,D) × (B,S,K,D) KV cache -> (B,H,D).

    ``use_kernel=False`` falls back to the pure-jnp reference (used inside
    jitted model code where interpret-mode pallas would be slow on CPU).
    """
    if length is None:
        length = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
    if not use_kernel:
        return decode_attention_ref(q, k, v, scale=scale, length=length)
    return _kernel(q, k, v, length, scale=scale, blk_s=blk_s)
