"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracles.

Shape/dtype sweeps as required: every kernel is compared against its
``ref.py`` oracle over a grid of shapes and dtypes.  Hypothesis property
tests on the scheduler kernels live in ``test_hypothesis_properties.py``
(skip-guarded) so this module collects without the optional dev dependency.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import cluster as jcluster
from repro.core import fragmentation as frag_np
from repro.core import mig, schedulers
from repro.core.policy import resolve
from repro.kernels.fragscore import fragscore as frag_k
from repro.kernels.fragscore import ops as frag_ops
from repro.kernels.fragscore.ref import (
    delta_from_base_ref,
    fragscore_ref,
    select_from_base_ref,
)
from repro.sim import batched
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref

#: every registered device model once (the registry aliases short names)
DEVICE_MODELS = sorted(set(mig.DEVICE_MODELS.values()), key=lambda m: m.name)


class TestFragscoreKernel:
    @pytest.mark.parametrize("m", [1, 7, 100, 513, 2048])
    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    def test_matches_ref_random(self, m, metric):
        rng = np.random.default_rng(m)
        occ = (rng.random((m, 8)) < 0.4).astype(np.int32)
        got = np.asarray(frag_ops.fragmentation_scores(jnp.asarray(occ), metric))
        ref = np.asarray(fragscore_ref(jnp.asarray(occ), metric))
        np.testing.assert_allclose(got, ref)

    @pytest.mark.parametrize("dtype", [np.int32, np.float32, np.int8])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(0)
        occ = (rng.random((64, 8)) < 0.5).astype(dtype)
        got = np.asarray(frag_ops.fragmentation_scores(jnp.asarray(occ)))
        ref = frag_np.fragmentation_scores(occ.astype(np.int32))
        np.testing.assert_allclose(got, ref)

    def test_matches_numpy_reference_exhaustive(self):
        """All 256 possible occupancy bitmaps."""
        occ = np.array([[int(b) for b in f"{i:08b}"] for i in range(256)], np.int32)
        for metric in ("blocked", "partial"):
            got = np.asarray(frag_ops.fragmentation_scores(jnp.asarray(occ), metric))
            ref = frag_np.fragmentation_scores(occ, metric)
            np.testing.assert_allclose(got, ref)


class TestMFIDeltaKernel:
    @pytest.mark.parametrize("pid", range(mig.NUM_PROFILES))
    def test_matches_numpy_candidates(self, pid):
        rng = np.random.default_rng(pid)
        occ = (rng.random((257, 8)) < 0.35).astype(np.int32)
        delta = np.asarray(frag_ops.mfi_delta_f(jnp.asarray(occ), jnp.int32(pid)))
        gpus, anchors, deltas = schedulers.mfi_candidates(occ, pid)
        anchor_list = list(np.asarray(jcluster.PROFILE_ANCHORS)[pid])
        n_feasible = 0
        for g, a, d in zip(gpus, anchors, deltas):
            col = anchor_list.index(a)
            np.testing.assert_allclose(delta[g, col], d, rtol=1e-6)
            n_feasible += 1
        assert (delta < 1e29).sum() == n_feasible

    def test_select_agrees_with_reference_scheduler(self):
        rng = np.random.default_rng(42)
        occ = (rng.random((128, 8)) < 0.45).astype(np.int32)
        for pid in range(6):
            g, a, acc = frag_ops.mfi_select(jnp.asarray(occ), jnp.int32(pid))
            d = jcluster.mfi_select(jnp.asarray(occ), jnp.int32(pid))
            assert bool(acc) == bool(d.accepted)
            if bool(acc):
                assert (int(g), int(a)) == (int(d.gpu), int(d.anchor))

    def test_unified_entry_point_kernel_flag(self):
        """cluster.mfi_select is the single seam: use_kernel=True routes the
        same decision through the fused Pallas kernel (the ops.py alias
        delegates here)."""
        rng = np.random.default_rng(7)
        occ = jnp.asarray((rng.random((64, 8)) < 0.5).astype(np.int32))
        for pid in range(mig.NUM_PROFILES):
            d_jnp = jcluster.mfi_select(occ, jnp.int32(pid))
            d_k = jcluster.mfi_select(occ, jnp.int32(pid), use_kernel=True)
            assert bool(d_jnp.accepted) == bool(d_k.accepted)
            if bool(d_jnp.accepted):
                assert (int(d_jnp.gpu), int(d_jnp.anchor)) == (
                    int(d_k.gpu), int(d_k.anchor)
                )
                np.testing.assert_array_equal(d_jnp.delta_f, d_k.delta_f)


def _model_tables(model):
    """(w, v) placement table + per-profile (A, S) anchor masks of a model."""
    w = model.placement_masks.astype(np.float32)
    v = model.placement_mem.astype(np.float32)
    masks = np.zeros((mig.NUM_PROFILES, model.max_anchors, model.num_mem_slices),
                     np.float32)
    for pid, prof in enumerate(model.profiles):
        for j, a in enumerate(prof.anchors):
            masks[pid, j, a:a + prof.mem] = 1
    return w, v, masks


class TestPerModelKernelParity:
    """Kernel-vs-ref parity on every registered DeviceModel — the padded
    non-8-slice H200-141GB (S = 12) included."""

    @pytest.mark.parametrize("model", DEVICE_MODELS, ids=lambda m: m.name)
    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    def test_fragscore_matches_ref(self, model, metric):
        rng = np.random.default_rng(len(model.name))
        s = model.num_mem_slices
        occ = (rng.random((73, s)) < 0.4).astype(np.int32)
        w, v, _ = _model_tables(model)
        got = np.asarray(
            frag_k.fragscore(
                jnp.asarray(occ), jnp.asarray(w), jnp.asarray(v),
                metric=metric, interpret=True,
            )
        )
        want = np.asarray(fragscore_ref(jnp.asarray(occ), metric, w, v))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("model", DEVICE_MODELS, ids=lambda m: m.name)
    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    def test_delta_from_base_matches_ref(self, model, metric):
        """The fused ΔF kernel on the model's own window-count state: every
        demand class, raw (unmasked) ΔF values bit-for-bit."""
        rng = np.random.default_rng(1 + len(model.name))
        s = model.num_mem_slices
        occ = (rng.random((41, s)) < 0.35).astype(np.int32)
        w, v, pmasks = _model_tables(model)
        base = occ.astype(np.float32) @ w.T
        free = s - occ.sum(axis=1)
        f = np.asarray(fragscore_ref(jnp.asarray(occ), metric, w, v))
        for pid in range(mig.NUM_PROFILES):
            mw = pmasks[pid] @ w.T  # (A, N)
            mem = float(model.profiles[pid].mem)
            got = np.asarray(
                frag_k.delta_from_base(
                    jnp.asarray(base), jnp.asarray(free), jnp.asarray(v),
                    jnp.asarray(mw), jnp.asarray((mw > 0).astype(np.float32)),
                    mem, jnp.asarray(f), metric=metric, interpret=True,
                )
            )
            want = np.asarray(
                delta_from_base_ref(
                    jnp.asarray(base), jnp.asarray(free), v, mw, mem,
                    jnp.asarray(f), metric,
                )
            )
            np.testing.assert_array_equal(got, want)

    def test_ops_wrapper_matches_engine_lowering(self):
        """The A100 convenience wrapper (`ops.delta_from_base_f`) agrees
        with the batched engine's pure-jnp `_delta_from_base` on the same
        window-count state."""
        from repro.sim import batched

        model = mig.A100_80GB
        spec = mig.ClusterSpec.homogeneous(model, 6)
        tables = batched.spec_tables(spec)
        midx = jnp.asarray(spec.model_index)
        rng = np.random.default_rng(13)
        occ = (rng.random((6, 8)) < 0.4).astype(np.int32)
        base = jnp.einsum(
            "ms,mns->mn", jnp.asarray(occ, jnp.float32), tables.W[midx]
        )
        free = tables.slices[midx] - occ.sum(axis=1).astype(np.int32)
        vg = tables.V[midx]
        f = batched._frag_from_base(base, free, "blocked", vg)
        for pid in range(mig.NUM_PROFILES):
            got = np.asarray(frag_ops.delta_from_base_f(base, free, pid, f))
            want = np.asarray(
                batched._delta_from_base(
                    base, free, "blocked", vg,
                    tables.maskwin[midx, pid], tables.maskpos[midx, pid],
                    tables.profile_mem[midx, pid], f,
                )
            )
            np.testing.assert_array_equal(got, want)

    def test_delta_from_base_padded_tables(self):
        """The batched engine hands the kernel *padded* per-spec tables
        (common N/A across models, zero-padded windows); padded rows and
        anchors must not perturb the scores of the real ones."""
        from repro.sim import batched

        spec = mig.ClusterSpec(((mig.A100_80GB, 2), (mig.H200_141GB, 2)))
        tables = batched.spec_tables(spec)
        rng = np.random.default_rng(3)
        for k, model in enumerate(spec.models):
            s = model.num_mem_slices
            occ = np.zeros((5, spec.num_mem_slices), np.int32)
            occ[:, :s] = (rng.random((5, s)) < 0.4).astype(np.int32)
            w_pad = np.asarray(tables.W[k])  # (N_pad, S_pad) zero-padded
            v_pad = np.asarray(tables.V[k])
            base = occ.astype(np.float32) @ w_pad.T
            free = s - occ.sum(axis=1)
            f = np.asarray(fragscore_ref(jnp.asarray(occ[:, :s]), "blocked",
                                         *_model_tables(model)[:2]))
            for pid in range(mig.NUM_PROFILES):
                got = np.asarray(
                    frag_k.delta_from_base(
                        jnp.asarray(base), jnp.asarray(free),
                        jnp.asarray(v_pad),
                        tables.maskwin[k, pid], tables.maskpos[k, pid],
                        float(model.profiles[pid].mem), jnp.asarray(f),
                        metric="blocked", interpret=True,
                    )
                )
                want = np.asarray(
                    delta_from_base_ref(
                        jnp.asarray(base), jnp.asarray(free), v_pad,
                        np.asarray(tables.maskwin[k, pid]),
                        float(model.profiles[pid].mem), jnp.asarray(f),
                        "blocked",
                    )
                )
                np.testing.assert_array_equal(got, want)


class TestRescoreFromBase:
    """The batched engine rescores touched GPUs from the window counts
    (``_frag_from_base``) on every fleet; on the paper's A100-80GB fleet that
    equals the occupancy-based ``fragscore`` kernel and the jnp
    ``make_frag_fn`` form bit for bit (every score is a sum of small
    integers in float32)."""

    @pytest.mark.parametrize("m", [1, 11, 100])
    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    def test_base_form_equals_occupancy_forms(self, m, metric):
        model = mig.A100_80GB
        rng = np.random.default_rng(m)
        fill = rng.random((m, 1))  # every density from empty to full
        occ = (rng.random((m, model.num_mem_slices)) < fill).astype(np.int32)
        w = model.placement_masks.astype(np.float32)
        v = model.placement_mem.astype(np.float32)
        base = occ.astype(np.float32) @ w.T
        free = model.num_mem_slices - occ.sum(axis=1)
        got = np.asarray(
            batched._frag_from_base(
                jnp.asarray(base), jnp.asarray(free, jnp.int32), metric,
                jnp.broadcast_to(jnp.asarray(v), base.shape),
            )
        )
        kernel = batched.make_frag_fn(metric, True, model, interpret=True)
        for form in (kernel, batched.make_frag_fn(metric, False, model)):
            np.testing.assert_array_equal(got, np.asarray(form(jnp.asarray(occ))))
        assert got.dtype == np.float32


# ---------------------------------------------------------------------------
# Fused select / migrate kernels: ΔF + in-kernel lexicographic argmin
# ---------------------------------------------------------------------------


def _random_state(spec, tables, seed, fill=0.4):
    """Randomized occupancy -> engine-layout ``(base, free, f)``."""
    rng = np.random.default_rng(seed)
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


class TestFusedSelectParity:
    """Fused select (ΔF + in-kernel lex argmin) vs the masked-refinement
    oracle — every registered DeviceModel (padded H200-141GB included),
    randomized occupancy, interpret mode."""

    @pytest.mark.parametrize("model", DEVICE_MODELS, ids=lambda m: m.name)
    @pytest.mark.parametrize("policy", ["mfi", "bf-bi", "wf-bi"])
    def test_homogeneous_matches_oracle(self, model, policy):
        spec = mig.ClusterSpec.homogeneous(model, 9)
        tables = batched.spec_tables(spec)
        pspec = resolve(policy, engine="batched")
        keys = batched._effective_keys(pspec)
        select_fn = batched.make_select_fn(spec, pspec, interpret=True)
        arange_n = jnp.arange(int(tables.V.shape[-1]))
        gidx = jnp.arange(spec.num_gpus)
        for seed, fill in ((0, 0.0), (1, 0.45), (2, 0.9)):
            base, free, f = _random_state(
                spec, tables, seed + len(model.name), fill
            )
            for pid in range(mig.NUM_PROFILES):
                got = select_fn(base, free, f, pid)
                rowsel = (
                    tables.profile_rows[0, pid][None, :] == arange_n[:, None]
                )
                want = select_from_base_ref(
                    base, free, f, gidx, tables.V[0],
                    tables.maskwin[0, pid], tables.profile_mem[0, pid],
                    rowsel, tables.profile_valid[0, pid],
                    tables.profile_anchors[0, pid], keys,
                )
                assert tuple(int(x) for x in got) == tuple(
                    int(x) for x in want
                ), (model.name, policy, seed, pid)

    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    def test_mixed_fleet_matches_jnp_lowering(self, metric):
        """Per-model dispatch + cross-group merge vs `_lower_select` on a
        three-model fleet (A100-80/H200-141/A100-40)."""
        spec = mig.ClusterSpec(
            ((mig.A100_80GB, 2), (mig.H200_141GB, 2), (mig.A100_40GB, 2))
        )
        tables = batched.spec_tables(spec)
        midx = jnp.asarray(spec.model_index)
        vg = tables.V[midx]
        for policy in ("mfi", "bf-bi"):
            pspec = resolve(policy, engine="batched")
            select_fn = batched.make_select_fn(
                spec, pspec, metric=metric, interpret=True
            )
            for seed in range(3):
                base, free, f = _random_state(spec, tables, 10 + seed, 0.5)
                if metric == "partial":
                    f = batched._frag_from_base(base, free, metric, vg)
                for pid in range(mig.NUM_PROFILES):
                    got = select_fn(base, free, f, pid)
                    want = batched._select(
                        pspec, base, free, f, metric, tables, midx, vg,
                        pid, cursor=jnp.int32(0),
                    )
                    assert tuple(int(x) for x in got) == tuple(
                        int(x) for x in want
                    ), (policy, seed, pid)

    def test_multi_tile_merge(self):
        """m > BLK_M: per-tile winner rows merge across tiles by
        ``(keys…, gpu, col)`` without perturbing the total order."""
        spec = mig.ClusterSpec.homogeneous(mig.A100_80GB, 516)
        tables = batched.spec_tables(spec)
        pspec = resolve("mfi", engine="batched")
        keys = batched._effective_keys(pspec)
        select_fn = batched.make_select_fn(spec, pspec, interpret=True)
        base, free, f = _random_state(spec, tables, 21, 0.6)
        arange_n = jnp.arange(int(tables.V.shape[-1]))
        pid = 3
        rowsel = tables.profile_rows[0, pid][None, :] == arange_n[:, None]
        got = select_fn(base, free, f, pid)
        want = select_from_base_ref(
            base, free, f, jnp.arange(516), tables.V[0],
            tables.maskwin[0, pid], tables.profile_mem[0, pid], rowsel,
            tables.profile_valid[0, pid], tables.profile_anchors[0, pid],
            keys,
        )
        assert tuple(int(x) for x in got) == tuple(int(x) for x in want)

    def test_request_scoped_keys_drop_out(self):
        """mfi-queued's tenant/priority/wait-age keys are request-scoped:
        the fused lowering drops them and must select exactly like mfi."""
        pspec_q = resolve("mfi-queued", engine="batched")
        assert batched._effective_keys(pspec_q) == batched._effective_keys(
            resolve("mfi", engine="batched")
        )
        spec = mig.ClusterSpec.homogeneous(mig.A100_80GB, 6)
        tables = batched.spec_tables(spec)
        fn_q = batched.make_select_fn(spec, pspec_q, interpret=True)
        fn_m = batched.make_select_fn(
            spec, resolve("mfi", engine="batched"), interpret=True
        )
        base, free, f = _random_state(spec, tables, 5, 0.5)
        for pid in range(mig.NUM_PROFILES):
            gq = fn_q(base, free, f, pid)
            gm = fn_m(base, free, f, pid)
            assert tuple(int(x) for x in gq) == tuple(int(x) for x in gm)


class TestFusedMigrateParity:
    """`migrate_refine`'s two passes vs the select oracle — the per-class
    top-2 equals the oracle's best (then best-with-winner-row-excluded) and
    the per-victim patched-row pass equals a one-row oracle call."""

    def _setup(self, model, seed, fill):
        spec = mig.ClusterSpec.homogeneous(model, 7)
        tables = batched.spec_tables(spec)
        pspec = resolve("mfi-defrag", engine="batched")
        keys = batched._effective_keys(pspec)
        fn = batched.make_migrate_fn(spec, pspec, interpret=True)
        base, free, f = _random_state(spec, tables, seed, fill)
        rng = np.random.default_rng(seed + 99)
        c = 5
        rg = jnp.asarray(rng.integers(0, spec.num_gpus, size=c), jnp.int32)
        rp = jnp.asarray(rng.integers(0, mig.NUM_PROFILES, size=c), jnp.int32)
        kc = jnp.zeros((c,), jnp.int32)
        vspec = mig.ClusterSpec.homogeneous(model, c)
        base2, free2, f2 = _random_state(vspec, tables, seed + 7, fill)
        return (spec, tables, keys, fn, base, free, f,
                (base2, free2, f2, rg, rp, kc))

    @pytest.mark.parametrize("model", DEVICE_MODELS, ids=lambda m: m.name)
    @pytest.mark.parametrize("seed,fill", [(0, 0.0), (1, 0.5), (2, 0.95)])
    def test_matches_oracle(self, model, seed, fill):
        (spec, tables, keys, fn, base, free, f,
         (base2, free2, f2, rg, rp, kc)) = self._setup(model, seed, fill)
        g1, ok1, a1, k1, g2, ok2, a2, k2, ap, okp, kp = fn(
            base, free, f, base2, free2, f2, rg, rp, kc
        )
        arange_n = jnp.arange(int(tables.V.shape[-1]))
        gidx = jnp.arange(spec.num_gpus)
        for p in range(mig.NUM_PROFILES):
            rowsel = tables.profile_rows[0, p][None, :] == arange_n[:, None]
            args = (
                tables.V[0], tables.maskwin[0, p], tables.profile_mem[0, p],
                rowsel, tables.profile_valid[0, p],
                tables.profile_anchors[0, p], keys,
            )
            w1 = select_from_base_ref(base, free, f, gidx, *args)
            assert (int(g1[p]), int(a1[p]), bool(ok1[p])) == (
                int(w1[0]), int(w1[1]), bool(w1[2])
            ), (model.name, p)
            # runner-up: best with the winner's row forced infeasible
            # (rows are independent, so patching row g1 is exact exclusion)
            b2 = base.at[w1[0]].set(1.0) if bool(w1[2]) else base
            w2 = select_from_base_ref(b2, free, f, gidx, *args)
            assert (int(g2[p]), int(a2[p]), bool(ok2[p])) == (
                int(w2[0]), int(w2[1]), bool(w2[2])
            ), (model.name, p)

        for c in range(int(rg.shape[0])):
            p = int(rp[c])
            rowsel = tables.profile_rows[0, p][None, :] == arange_n[:, None]
            wv = select_from_base_ref(
                base2[c][None], free2[c][None], f2[c][None], rg[c][None],
                tables.V[0], tables.maskwin[0, p], tables.profile_mem[0, p],
                rowsel, tables.profile_valid[0, p],
                tables.profile_anchors[0, p], keys,
            )
            assert (int(ap[c]), bool(okp[c])) == (int(wv[1]), bool(wv[2])), (
                model.name, c
            )

    def test_all_infeasible_class(self):
        """A fully packed fleet: every class all-infeasible in both passes,
        `(0, 0, False)` rows all the way through."""
        (_, _, _, fn, base, free, f,
         (base2, free2, f2, rg, rp, kc)) = self._setup(mig.A100_80GB, 3, 1.0)
        g1, ok1, a1, _, g2, ok2, a2, _, ap, okp, _ = fn(
            base, free, f, base2, free2, f2, rg, rp, kc
        )
        assert not np.asarray(ok1).any() and not np.asarray(ok2).any()
        assert not np.asarray(okp).any()
        np.testing.assert_array_equal(np.asarray(g1), 0)
        np.testing.assert_array_equal(np.asarray(g2), 0)
        np.testing.assert_array_equal(np.asarray(ap), 0)


class TestDecodeAttentionKernel:
    SHAPES = [
        # (batch, q_heads, kv_heads, head_dim, kv_len, blk_s)
        (2, 8, 2, 64, 300, 128),    # GQA, ragged tail block
        (1, 8, 1, 128, 1024, 512),  # MQA (paligemma-style)
        (3, 10, 5, 64, 77, 512),    # block larger than sequence
        (2, 4, 4, 256, 513, 256),   # MHA, gemma3 head_dim
        (1, 12, 4, 128, 2048, 512), # starcoder2-style ratio
    ]

    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        b, h, kh, d, s, blk = shape
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, s, kh, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, s, kh, d)), dtype)
        length = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
        got = decode_attention(q, k, v, length, blk_s=blk)
        ref = decode_attention_ref(q, k, v, length=length)
        tol = 2e-5 if dtype == jnp.float32 else 2.5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
        )

    def test_full_length_default(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
        length = jnp.full((2,), 256, jnp.int32)
        got = decode_attention(q, k, v, length)
        ref = decode_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_custom_scale(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((1, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
        length = jnp.full((1,), 128, jnp.int32)
        got = decode_attention(q, k, v, length, scale=0.1)
        ref = decode_attention_ref(q, k, v, scale=0.1, length=length)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_length_one(self):
        """Degenerate cache with a single valid entry -> output == v[0]."""
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 2, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
        length = jnp.asarray([1], jnp.int32)
        got = decode_attention(q, k, v, length)
        np.testing.assert_allclose(
            np.asarray(got)[0], np.asarray(v)[0, 0], atol=1e-6, rtol=1e-6
        )


class TestInterpretMode:
    """`repro.kernels.interpret_mode` is the one place a kernel's lowering
    is chosen: Mosaic on a TPU backend, the interpreter elsewhere, and
    never the interpreter on a TPU."""

    @pytest.mark.parametrize(
        "backend,requested,expected",
        [
            ("cpu", None, True),
            ("cpu", False, False),
            ("cpu", True, True),
            ("tpu", None, False),
            ("tpu", False, False),
        ],
    )
    def test_choice(self, monkeypatch, backend, requested, expected):
        import jax

        from repro.kernels import interpret_mode

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert interpret_mode(requested) is expected

    def test_interpreter_refused_on_tpu(self, monkeypatch):
        import jax

        from repro.kernels import interpret_mode

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="hide the device"):
            interpret_mode(True)
