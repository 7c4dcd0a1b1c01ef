"""Public jit'd wrappers for the fragscore / mfi_delta / delta_from_base
Pallas kernels (A100-80GB table defaults; pass other models' tables to the
kernels in :mod:`repro.kernels.fragscore.fragscore` directly)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cluster as jcluster
from repro.core import mig
from repro.kernels.fragscore import fragscore as _k

_W = np.asarray(mig.PLACEMENT_MASKS, dtype=np.float32)
_V = np.asarray(mig.PLACEMENT_MEM, dtype=np.float32)


def fragmentation_scores(occ: jax.Array, metric: str = "blocked") -> jax.Array:
    """Kernel-backed F(m) over the cluster: (M, 8) -> (M,) float32."""
    return _k.fragscore(occ, jnp.asarray(_W), jnp.asarray(_V), metric=metric)


def mfi_delta_f(occ: jax.Array, profile_id, metric: str = "blocked") -> jax.Array:
    """Kernel-backed ΔF table for Algorithm 2: (M, 8) × profile -> (M, A)."""
    masks = jcluster.PROFILE_MASKS[profile_id]  # (A, 8)
    valid = jcluster.PROFILE_VALID[profile_id].astype(jnp.float32)  # (A,)
    return _k.mfi_delta(
        occ,
        jnp.asarray(_W),
        jnp.asarray(_V),
        masks,
        valid,
        metric=metric,
    )


def delta_from_base_f(
    base: jax.Array,
    free: jax.Array,
    profile_id,
    f_before: jax.Array,
    metric: str = "blocked",
) -> jax.Array:
    """Kernel-backed engine-hot-path ΔF table from window counts.

    A100-80GB convenience wrapper over
    :func:`repro.kernels.fragscore.fragscore.delta_from_base`; the batched
    engine's per-model dispatch (:func:`repro.sim.batched.make_delta_fn`)
    calls the kernel once per ClusterSpec model group with each group's
    own tables.
    """
    tables = jcluster.tables_for(mig.A100_80GB)
    maskwin = (
        tables.profile_masks[profile_id].astype(jnp.float32) @ jnp.asarray(_W).T
    )  # (A, N)
    return _k.delta_from_base(
        base,
        free,
        jnp.asarray(_V),
        maskwin,
        (maskwin > 0).astype(jnp.float32),
        jnp.asarray(mig.PROFILE_MEM)[profile_id],
        f_before,
        metric=metric,
    )


def mfi_select(occ: jax.Array, profile_id, metric: str = "blocked") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel-backed Algorithm 2 — thin alias for the unified entry point
    :func:`repro.core.cluster.mfi_select` with ``use_kernel=True``.

    Returns the legacy ``(gpu, anchor, accepted)`` tuple.
    """
    d = jcluster.mfi_select(occ, profile_id, metric, use_kernel=True)
    return d.gpu, d.anchor, d.accepted
