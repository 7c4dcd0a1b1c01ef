"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp oracle.

CPU-interpret timings are NOT TPU performance — they validate shapes and give
the oracle-relative sanity curve.  TPU-targeted blocking is what matters
(see kernels/*/ for BlockSpecs); roofline projections live in §Roofline.

``--fused`` adds the fused select/migrate kernels (ΔF + in-kernel
lexicographic argmin): ``select_from_base`` per-model dispatch vs the
jnp ``_lower_select`` lowering, and ``migrate_refine``'s combined
class + victim launch vs the jnp per-class/per-victim refinements.
"""

from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.fragscore import ops as frag_ops
from repro.kernels.fragscore.ref import fragscore_ref


def _engine_state(spec, tables, rng, fill=0.45):
    """Randomized occupancy -> engine-layout (base, free, f)."""
    from repro.sim import batched

    midx = np.asarray(spec.model_index)
    occ = np.zeros((spec.num_gpus, spec.num_mem_slices), np.int32)
    for g in range(spec.num_gpus):
        s = spec.models[midx[g]].num_mem_slices
        occ[g, :s] = (rng.random(s) < fill).astype(np.int32)
    base = jnp.einsum(
        "ms,mns->mn", jnp.asarray(occ, jnp.float32), tables.W[midx]
    )
    free = jnp.asarray(tables.slices[midx] - occ.sum(axis=1), jnp.int32)
    f = batched._frag_from_base(base, free, "blocked", tables.V[midx])
    return base, free, f


def bench_fused(rng, rows=None):
    """Fused select / migrate-search kernels vs the pure-jnp lowering."""
    from repro.core import mig
    from repro.core.policy import resolve
    from repro.sim import batched

    print("table,kernel,shape,us_fused_pallas,us_jnp")
    pid = 2
    for m in (1024, 4096):
        spec = mig.ClusterSpec.homogeneous(mig.A100_80GB, m)
        tables = batched.spec_tables(spec)
        midx = jnp.asarray(spec.model_index)
        vg = tables.V[midx]
        base, free, f = _engine_state(spec, tables, rng)
        pspec = resolve("mfi", engine="batched")
        select_fn = batched.make_select_fn(spec, pspec)
        fused = jax.jit(lambda b, fr, ff: select_fn(b, fr, ff, pid))
        ref = jax.jit(
            lambda b, fr, ff: batched._select(
                pspec, b, fr, ff, "blocked", tables, midx, vg, pid,
                jnp.int32(0),
            )
        )
        us_k = time_fn(lambda: jax.block_until_ready(fused(base, free, f)), iters=5)
        us_r = time_fn(lambda: jax.block_until_ready(ref(base, free, f)), iters=5)
        print(f"kernels,select_from_base,M={m},{us_k:.0f},{us_r:.0f}")
        if rows is not None:
            rows.append({"kernel": "select_from_base", "shape": f"M={m}",
                         "us_fused_pallas": us_k, "us_jnp": us_r})

    # migrate_refine: per-class top-2 + per-victim patched rows, one launch
    m, c = 1024, 64
    spec = mig.ClusterSpec.homogeneous(mig.A100_80GB, m)
    tables = batched.spec_tables(spec)
    base, free, f = _engine_state(spec, tables, rng)
    vspec = mig.ClusterSpec.homogeneous(mig.A100_80GB, c)
    base2, free2, f2 = _engine_state(vspec, tables, rng)
    rg = jnp.asarray(rng.integers(0, m, size=c), jnp.int32)
    rp = jnp.asarray(rng.integers(0, mig.NUM_PROFILES, size=c), jnp.int32)
    kc = jnp.zeros((c,), jnp.int32)
    migrate_fn = batched.make_migrate_fn(
        spec, resolve("mfi-defrag", engine="batched")
    )
    mig_j = jax.jit(lambda *a: migrate_fn(*a))
    us_k = time_fn(
        lambda: jax.block_until_ready(
            mig_j(base, free, f, base2, free2, f2, rg, rp, kc)
        ),
        iters=3,
    )
    print(f"kernels,migrate_refine,M={m}/C={c},{us_k:.0f},")
    if rows is not None:
        rows.append({"kernel": "migrate_refine", "shape": f"M={m}/C={c}",
                     "us_fused_pallas": us_k, "us_jnp": None})


def main(fused: bool = False, json_path: str | None = None):
    print("table,kernel,shape,us_pallas_interpret,us_ref")
    rng = np.random.default_rng(0)
    rows = []

    for m in (1024, 16384):
        occ = jnp.asarray((rng.random((m, 8)) < 0.4).astype(np.float32))
        us_k = time_fn(lambda: jax.block_until_ready(frag_ops.fragmentation_scores(occ)), iters=5)
        refj = jax.jit(fragscore_ref)
        us_r = time_fn(lambda: jax.block_until_ready(refj(occ)), iters=5)
        print(f"kernels,fragscore,M={m},{us_k:.0f},{us_r:.0f}")
        rows.append({"kernel": "fragscore", "shape": f"M={m}",
                     "us_pallas_interpret": us_k, "us_ref": us_r})

    for (b, h, kv, d, s) in [(4, 8, 2, 64, 1024), (1, 16, 8, 128, 4096)]:
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
        ln = jnp.full((b,), s, jnp.int32)
        us_k = time_fn(lambda: jax.block_until_ready(decode_attention(q, k, v, ln)), iters=3)
        refj = jax.jit(lambda q, k, v, ln: decode_attention_ref(q, k, v, length=ln))
        us_r = time_fn(lambda: jax.block_until_ready(refj(q, k, v, ln)), iters=3)
        print(f"kernels,decode_attention,b{b}h{h}kv{kv}d{d}s{s},{us_k:.0f},{us_r:.0f}")
        rows.append({"kernel": "decode_attention",
                     "shape": f"b{b}h{h}kv{kv}d{d}s{s}",
                     "us_pallas_interpret": us_k, "us_ref": us_r})

    if fused:
        bench_fused(rng, rows=rows)

    if json_path:
        import json

        payload = {"backend": jax.default_backend(), "fused": fused,
                   "rows": rows}
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"# wrote {json_path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fused", action="store_true",
                    help="also bench the fused select/migrate kernels "
                         "(in-kernel lexicographic argmin) vs the jnp path")
    ap.add_argument("--json", default=None, help="write rows to this JSON file")
    args = ap.parse_args()
    main(fused=args.fused, json_path=args.json)
