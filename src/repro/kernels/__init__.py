"""Pallas TPU kernels: compiled through Mosaic on TPU, interpreted elsewhere.

* ``fragscore``       -- batched fragmentation scoring (paper Algorithm 1)
* ``fragscore.mfi_delta`` -- fused MFI dry-run delta-F table (paper Algorithm 2)
* ``decode_attention`` -- GQA flash-decode over a KV cache (serving hot path)

Each kernel ships ``ops.py`` (jit'd public wrapper) and ``ref.py``
(pure-jnp oracle); tests sweep shapes/dtypes against the oracle.
"""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode — the one place the
    choice is made.

    ``None`` picks Mosaic on a TPU backend and the interpreter on any other
    (CPU tests).  ``False`` off-TPU is how a compile-only test lowers a
    kernel for a described TPU.  ``True`` on a TPU backend raises: on the
    chip a kernel never falls back to the interpreter.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret-mode Pallas on a TPU backend would hide the device; "
            "pass interpret=None (or False) to compile through Mosaic"
        )
    return bool(interpret)
