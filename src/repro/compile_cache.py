"""JAX's persistent compilation cache, placed the same way by every runner.

Compiling the batched engine's scan is a large share of a cold run, so
every script that drives it turns the cache on through
:func:`enable_compile_cache`.  The cache key includes the directory, so the
directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads the variable itself and nothing here
overrides it), otherwise the fixed :data:`DEFAULT_DIR` inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the cache directory when the environment names none (``.gitignore``d)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use.

    Thresholds are zeroed so that the small kernel programs persist too.
    """
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
