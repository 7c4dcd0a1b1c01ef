"""Staged batched-engine core tests (the PR-4 tentpole).

Four layers of guarantees over :mod:`repro.sim.batched`'s staged pipeline
(``arrival → select → migrate → commit → expire → measure``):

* **bit-for-bit regression** — the steady homogeneous and mixed traces
  recorded *before* the monolithic event step was split into stages
  reproduce exactly through the staged pipeline (golden aggregates and
  SHA-256 trace hashes, captured at commit ``ca345a6``);
* **batched ``mfi-defrag``** — the factored migrate search equals the
  dense reference (the stage against the host scheduler, and the fused
  search, are in ``test_engine_defrag.py``);
* **cumulative protocol** — batched demand-grid traces match the Python
  simulator on the *same* per-replica RNG streams;
* **satellites** — per-model demand mixes, the non-8-slice H200-141GB
  geometry, and replica-axis sharding (subprocess, 8 host devices).
"""

import hashlib
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import mig
from repro.sim import SimConfig, request_probs, run_many
from repro.sim import batched, replay
from repro.sim.distributions import DISTRIBUTIONS, resolve_probs

PID = {name: i for i, name in enumerate(mig.PROFILE_NAMES)}

MIXED = mig.ClusterSpec(((mig.A100_80GB, 3), (mig.A100_40GB, 3)))
FOUR_MODEL = mig.ClusterSpec(
    (
        (mig.A100_80GB, 2),
        (mig.A100_40GB, 2),
        (mig.H100_96GB, 2),
        (mig.H100_80GB, 2),
    )
)
H200_MIX = mig.ClusterSpec(
    ((mig.A100_80GB, 2), (mig.H200_141GB, 2), (mig.A100_40GB, 1))
)


def _sim(policy, cfg, spec=None, runs=2, protocol="steady"):
    presample = (
        batched.presample_arrivals
        if protocol == "steady"
        else batched.presample_cumulative
    )
    events, meta, rr, rc = presample(cfg, runs=runs)
    kw = {}
    if spec is not None:
        kw = dict(
            midx=jnp.asarray(spec.model_index), tables=batched.spec_tables(spec)
        )
    final, trace = jax.device_get(
        batched._simulate(
            jax.tree.map(jnp.asarray, events),
            policy=policy,
            metric=cfg.metric,
            num_gpus=cfg.num_gpus,
            ring_rows=rr,
            ring_cols=rc,
            use_kernel=False,
            protocol=protocol,
            **kw,
        )
    )
    return events, meta, trace, final


# ---------------------------------------------------------------------------
# Bit-for-bit regression vs the pre-refactor monolithic event step
# ---------------------------------------------------------------------------


#: aggregates + decision-trace hashes recorded on the pre-refactor engine
#: (monolithic `_event_step`, commit ca345a6) — the staged pipeline must
#: reproduce them exactly, not approximately
GOLDEN_AGGREGATES = {
    ("homog_m6", "mfi"): {
        "acceptance_rate": 0.835978120978121,
        "active_gpus": 5.0,
        "allocated_workloads": 37.25,
        "frag_severity": 7.736111243565877,
        "utilization": 0.6440972222222222,
    },
    ("mixed_k2", "rr"): {
        "acceptance_rate": 0.705775877918735,
        "active_gpus": 5.583333333333333,
        "allocated_workloads": 31.25,
        "frag_severity": 8.333333651224772,
        "utilization": 0.6458333333333334,
    },
    ("four_k4", "bf-bi"): {
        "acceptance_rate": 0.8497768071971659,
        "active_gpus": 7.1875,
        "allocated_workloads": 53.25,
        "frag_severity": 7.015625,
        "utilization": 0.68359375,
    },
}

GOLDEN_CONFIGS = {
    "homog_m6": lambda: SimConfig(num_gpus=6, offered_load=0.9, seed=12),
    "mixed_k2": lambda: SimConfig(cluster_spec=MIXED, offered_load=0.9, seed=12),
    "four_k4": lambda: SimConfig(
        cluster_spec=FOUR_MODEL, offered_load=0.85, seed=3
    ),
}

GOLDEN_TRACE_HASHES = {
    "homog": "3f61871a2075ffe549c554a6820d3bccc437d8606c80dd6e471e9daa0ad00705",
    "mixed": "fc5a944c82ab6c74ca8a49b6a1ca19981d1d3fe8953f9b35cce26e67a8678d62",
}


class TestPreRefactorBitForBit:
    @pytest.mark.parametrize("tag,policy", sorted(GOLDEN_AGGREGATES))
    def test_steady_aggregates_reproduce_exactly(self, tag, policy):
        r = batched.run_batched(policy, GOLDEN_CONFIGS[tag](), runs=4)
        for key, want in GOLDEN_AGGREGATES[(tag, policy)].items():
            assert r[key] == want, f"{tag}/{policy}/{key}: {r[key]!r} != {want!r}"

    @pytest.mark.parametrize(
        "tag,cfg_fn,spec",
        [
            ("homog", lambda: SimConfig(num_gpus=5, offered_load=1.1, seed=7), None),
            (
                "mixed",
                lambda: SimConfig(cluster_spec=MIXED, offered_load=1.0, seed=9),
                MIXED,
            ),
        ],
    )
    def test_steady_decision_traces_hash_identically(self, tag, cfg_fn, spec):
        _, _, trace, _ = _sim("mfi", cfg_fn(), spec, runs=3)
        h = hashlib.sha256()
        for a in (
            trace.ok, trace.gpu, trace.aidx, trace.free_sum, trace.active,
            trace.frag,
        ):
            h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
        assert h.hexdigest() == GOLDEN_TRACE_HASHES[tag]


# ---------------------------------------------------------------------------
# Protocol descriptor
# ---------------------------------------------------------------------------


class TestProtocolDescriptor:
    def test_registry(self):
        steady = batched.resolve_protocol("steady")
        cumulative = batched.resolve_protocol("cumulative")
        assert steady.boundary_metrics and not steady.post_metrics
        assert cumulative.post_metrics and not cumulative.boundary_metrics
        assert batched.resolve_protocol(steady) is steady
        with pytest.raises(ValueError, match="unknown protocol"):
            batched.resolve_protocol("bursty")

    def test_trace_fields_follow_protocol(self):
        cfg = SimConfig(num_gpus=3, offered_load=0.8, seed=1)
        _, _, steady_trace, _ = _sim("ff", cfg, runs=2)
        assert steady_trace.free_sum is not None
        assert steady_trace.post_free is None and steady_trace.mig is None
        ccfg = SimConfig(num_gpus=3, protocol="cumulative", seed=1)
        _, _, cum_trace, _ = _sim("ff", ccfg, runs=2, protocol="cumulative")
        assert cum_trace.post_free is not None
        assert cum_trace.free_sum is None


# ---------------------------------------------------------------------------
# Cumulative protocol on the batched engine
# ---------------------------------------------------------------------------


class TestBatchedCumulative:
    @pytest.mark.parametrize("policy", ["mfi", "ff", "rr"])
    def test_traces_match_python_simulator_same_stream(self, policy):
        """Replica r consumes the same RNG stream as run_many's run r, so
        the demand-grid traces must agree to float tolerance — not just
        statistically."""
        cfg = SimConfig(num_gpus=4, protocol="cumulative", seed=5)
        rb = batched.run_batched(policy, cfg, runs=3)
        rp = run_many(policy, cfg, runs=3)
        for k in (
            "acceptance_rate", "allocated_workloads", "active_gpus",
            "utilization", "frag_severity",
        ):
            np.testing.assert_allclose(rb[k], rp[k], rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                rb["traces"][k], rp["traces"][k], rtol=1e-6, atol=1e-6
            )
        np.testing.assert_array_equal(rb["demand_grid"], rp["demand_grid"])
        np.testing.assert_allclose(
            rb["rejects_by_profile"], rp["rejects_by_profile"]
        )

    def test_mixed_fleet_same_stream_decisions(self):
        cfg = SimConfig(cluster_spec=MIXED, protocol="cumulative", seed=2)
        events, meta, trace, _ = _sim(
            "mfi", cfg, MIXED, runs=2, protocol="cumulative"
        )
        ok_ref, gpu_ref, _ = replay.host_decisions(
            events, meta, "mfi", cfg.num_gpus, spec=MIXED
        )
        ok = np.asarray(trace.ok)
        np.testing.assert_array_equal(ok, ok_ref)
        np.testing.assert_array_equal(np.asarray(trace.gpu)[ok], gpu_ref[ok])
        replay.replay(events, meta, trace, cfg.num_gpus, spec=MIXED)

    def test_cumulative_defrag_composes(self):
        """Protocol descriptor × defrag spec: both stages compile together;
        the host reference (with the cumulative migration fix) agrees."""
        cfg = SimConfig(num_gpus=2, protocol="cumulative", seed=8)
        rb = batched.run_batched("mfi-defrag", cfg, runs=2)
        rp = run_many("mfi-defrag", cfg, runs=2)
        np.testing.assert_allclose(
            rb["acceptance_rate"], rp["acceptance_rate"], rtol=1e-6
        )
        np.testing.assert_allclose(
            rb["traces"]["utilization"], rp["traces"]["utilization"],
            rtol=1e-6, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# Factored migrate-stage lowering (the PR-5 tentpole)
# ---------------------------------------------------------------------------


def _random_cluster(rng, spec=None, num_gpus=None, density=0.7):
    """A randomized occupancy state + its (gpu, pid, anchor) workload list."""
    cl = (
        mig.ClusterState(num_gpus)
        if spec is None
        else mig.ClusterState(spec=spec)
    )
    wid = 0
    for g in range(cl.num_gpus):
        for pid in rng.permutation(mig.NUM_PROFILES):
            if rng.random() < density:
                anchors = cl.gpus[g].feasible_anchors(int(pid))
                if anchors:
                    cl.allocate(wid, int(pid), g, int(rng.choice(anchors)))
                    wid += 1
    workloads = [
        (g.gpu_id, a.profile_id, a.anchor)
        for g in cl.gpus
        for a in g.allocations.values()
    ]
    return cl, workloads


def _search_args(cl, workloads, pid, ring_shape, rng, metric="blocked"):
    """Scatter the workloads into a random ring layout and derive the
    window-count state `_migrate_search` consumes."""
    spec = cl.spec
    tables = batched.spec_tables(spec)
    midx = jnp.asarray(spec.model_index)
    occ = cl.occupancy_matrix()
    base = jnp.einsum(
        "ms,mns->mn", jnp.asarray(occ, jnp.float32), tables.W[midx]
    )
    free = tables.slices[midx] - occ.sum(axis=1).astype(np.int32)
    vg = tables.V[midx]
    f = batched._frag_from_base(base, free, metric, vg)

    rows, cols = ring_shape
    s = int(tables.W.shape[2])
    ring_gpu = np.zeros((rows, cols), np.int32)
    ring_mask = np.zeros((rows, cols, s), np.int32)
    ring_pid = np.zeros((rows, cols), np.int32)
    ring_aidx = np.zeros((rows, cols), np.int32)
    slots = rng.choice(rows * cols, size=len(workloads), replace=False)
    for slot, (g, p, anchor) in zip(slots, workloads):
        model = spec.model_of(int(g))
        j = model.profiles[int(p)].anchors.index(int(anchor))
        r, c = divmod(int(slot), cols)
        ring_gpu[r, c] = g
        ring_mask[r, c, anchor:anchor + model.profiles[int(p)].mem] = 1
        ring_pid[r, c] = p
        ring_aidx[r, c] = j
    return dict(
        spec=batched.resolve("mfi-defrag"),
        metric=metric,
        tables=tables,
        midx=midx,
        vg=vg,
        base=base,
        free=free,
        f=f,
        ring_gpu=jnp.asarray(ring_gpu),
        ring_mask=jnp.asarray(ring_mask),
        ring_pid=jnp.asarray(ring_pid),
        ring_aidx=jnp.asarray(ring_aidx),
        pid_c=jnp.int32(pid),
        cursor=jnp.int32(0),
        want=jnp.asarray(True),
    )


#: the MigrationResult fields a migrate search must reproduce exactly
MIGRATION_FIELDS = [
    "gpu", "aidx", "vic_row", "vic_col", "vic_gpu", "vic_anchor",
    "vic_pid", "new_gpu", "new_aidx", "new_anchor", "old_mask",
    "old_mwin", "new_mask", "new_mwin",
]


class TestFactoredMigrateSearch:
    """The factored lowering must return the *same* MigrationResult as the
    dense (C, M, A) reference on arbitrary states — including rings much
    larger than the live-entry budget (the compaction path)."""

    FIELDS = MIGRATION_FIELDS

    @pytest.mark.parametrize("metric", ["blocked", "partial"])
    @pytest.mark.parametrize(
        "spec", [None, MIXED, H200_MIX], ids=["homog", "mixed", "h200"]
    )
    def test_equivalence_randomized(self, spec, metric):
        rng = np.random.default_rng(29)
        migrations = 0
        for trial in range(25):
            cl, workloads = _random_cluster(
                rng,
                spec=spec,
                num_gpus=int(rng.integers(2, 6)) if spec is None else None,
                density=rng.random() * 1.2,
            )
            if not workloads:
                continue
            pid = int(rng.integers(0, mig.NUM_PROFILES))
            # ring deliberately oversized: mostly dead slots -> the factored
            # search must compact them away without changing the decision
            rows = int(rng.integers(1, 40))
            cols = -(-max(1, len(workloads)) // rows) + int(rng.integers(0, 4))
            args = _search_args(cl, workloads, pid, (rows, cols), rng, metric)
            got = batched._migrate_search(**args)
            want = batched._migrate_search_dense(**args)
            assert bool(got.mig) == bool(want.mig), f"trial {trial}"
            if bool(want.mig):
                migrations += 1
                for field in self.FIELDS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(got, field)),
                        np.asarray(getattr(want, field)),
                        err_msg=f"trial {trial}: {field}",
                    )
        assert migrations >= 3  # the fuzz actually exercised the search

    def test_want_false_is_noop(self):
        rng = np.random.default_rng(5)
        cl, workloads = _random_cluster(rng, num_gpus=3)
        args = _search_args(cl, workloads, 0, (4, max(1, len(workloads))), rng)
        args["want"] = jnp.asarray(False)
        assert not bool(batched._migrate_search(**args).mig)

    def test_compaction_budget_bounds_live_entries(self):
        """Every running workload occupies >= 1 slice, so M*S bounds the
        live-entry count: a full cluster's workload list always fits the
        static budget."""
        rng = np.random.default_rng(11)
        cl, workloads = _random_cluster(rng, num_gpus=4, density=1.2)
        spec = cl.spec
        assert len(workloads) <= spec.num_gpus * spec.num_mem_slices


# ---------------------------------------------------------------------------
# Pallas kernel lowering of the ΔF hot path (use_kernel end to end)
# ---------------------------------------------------------------------------


class TestKernelLowering:
    """`use_kernel=True` (interpret mode on CPU) must reproduce the pure-jnp
    decisions bit-for-bit — homogeneous and mixed fleets, defrag included."""

    @pytest.mark.parametrize(
        "policy,spec",
        [("mfi", None), ("mfi", MIXED), ("mfi-defrag", None),
         ("mfi-defrag", H200_MIX)],
        ids=["mfi-homog", "mfi-mixed", "defrag-homog", "defrag-h200"],
    )
    def test_same_decisions_as_pure_jnp(self, policy, spec):
        cfg = (
            SimConfig(num_gpus=4, offered_load=1.0, seed=3)
            if spec is None
            else SimConfig(cluster_spec=spec, offered_load=1.0, seed=3)
        )
        cspec = cfg.spec()
        events, meta, rr, rc = batched.presample_arrivals(cfg, runs=2)
        dev = jax.tree.map(jnp.asarray, events)
        kw = dict(
            policy=policy, metric=cfg.metric, num_gpus=cfg.num_gpus,
            ring_rows=rr, ring_cols=rc,
            midx=jnp.asarray(cspec.model_index),
            tables=batched.spec_tables(cspec),
        )
        _, ref = jax.device_get(batched._simulate(dev, use_kernel=False, **kw))
        _, got = jax.device_get(
            batched._simulate(dev, use_kernel=True, kernel_spec=cspec, **kw)
        )
        ok = np.asarray(ref.ok)
        np.testing.assert_array_equal(np.asarray(got.ok), ok)
        np.testing.assert_array_equal(
            np.asarray(got.gpu)[ok], np.asarray(ref.gpu)[ok]
        )
        np.testing.assert_array_equal(np.asarray(got.frag), np.asarray(ref.frag))
        if ref.mig is not None:
            np.testing.assert_array_equal(
                np.asarray(got.mig), np.asarray(ref.mig)
            )
            m = np.asarray(ref.mig)
            np.testing.assert_array_equal(
                np.asarray(got.mig_to_gpu)[m], np.asarray(ref.mig_to_gpu)[m]
            )

    def test_run_batched_kernel_on_mixed_fleet(self):
        """The former homogeneous-only restriction is gone: mixed fleets
        dispatch the ΔF kernel per model group."""
        cfg = SimConfig(cluster_spec=MIXED, offered_load=0.9, seed=1)
        r_k = batched.run_batched("mfi", cfg, runs=2, use_kernel=True)
        r_j = batched.run_batched("mfi", cfg, runs=2, use_kernel=False)
        assert r_k["acceptance_rate"] == r_j["acceptance_rate"]

    def test_kernel_lowering_opt_out(self):
        from repro.core.policy import PolicySpec

        no_kernel = PolicySpec(
            name="no-kernel", keys=("frag-delta", "gpu", "anchor"),
            kernel_lowering=False,
        )
        cfg = SimConfig(num_gpus=2, offered_load=0.8, seed=0)
        with pytest.raises(ValueError, match="opts out of Pallas kernel"):
            batched.run_batched(no_kernel, cfg, runs=1, use_kernel=True)
        # auto never picks the kernel for an opted-out spec
        r = batched.run_batched(no_kernel, cfg, runs=1)
        assert 0.0 <= r["acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# Fused select/migrate lowering (in-kernel lexicographic argmin)
# ---------------------------------------------------------------------------


class TestFusedLowering:
    """The fused per-model select/migrate kernels behind `use_kernel=True`
    must reproduce every pinned golden artifact bit-for-bit — the `(M, A)`
    score table never leaving VMEM is a pure implementation detail."""

    @pytest.mark.parametrize(
        "tag,cfg_fn,spec",
        [
            ("homog", lambda: SimConfig(num_gpus=5, offered_load=1.1, seed=7), None),
            (
                "mixed",
                lambda: SimConfig(cluster_spec=MIXED, offered_load=1.0, seed=9),
                MIXED,
            ),
        ],
    )
    @pytest.mark.slow
    def test_fused_traces_reproduce_golden_hashes(self, tag, cfg_fn, spec):
        cfg = cfg_fn()
        cspec = cfg.spec()
        events, _, rr, rc = batched.presample_arrivals(cfg, runs=3)
        _, trace = jax.device_get(
            batched._simulate(
                jax.tree.map(jnp.asarray, events),
                policy="mfi", metric=cfg.metric, num_gpus=cfg.num_gpus,
                ring_rows=rr, ring_cols=rc,
                use_kernel=True, kernel_spec=cspec,
                midx=jnp.asarray(cspec.model_index),
                tables=batched.spec_tables(cspec),
            )
        )
        h = hashlib.sha256()
        for a in (
            trace.ok, trace.gpu, trace.aidx, trace.free_sum, trace.active,
            trace.frag,
        ):
            h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
        assert h.hexdigest() == GOLDEN_TRACE_HASHES[tag]

    @pytest.mark.slow
    def test_fused_golden_aggregates_reproduce(self):
        for tag, policy in [("homog_m6", "mfi")]:
            r = batched.run_batched(
                policy, GOLDEN_CONFIGS[tag](), runs=4, use_kernel=True
            )
            for key, want in GOLDEN_AGGREGATES[(tag, policy)].items():
                assert r[key] == want, f"{tag}/{policy}/{key}"

    @pytest.mark.slow
    def test_queued_fused_matches_jnp(self):
        cfg = SimConfig(
            num_gpus=4, offered_load=1.2, seed=7, protocol="steady-queued",
            wait_capacity=8, wait_patience=3,
        )
        cspec = cfg.spec()
        events, _, rr, rc = batched.presample_arrivals(cfg, runs=2, queued=True)
        kw = dict(
            policy="mfi-queued", metric=cfg.metric, num_gpus=cfg.num_gpus,
            ring_rows=rr, ring_cols=rc, protocol="steady-queued",
            wait_slots=cfg.wait_capacity, wait_patience=cfg.wait_patience,
            midx=jnp.asarray(cspec.model_index),
            tables=batched.spec_tables(cspec),
        )
        dev = jax.tree.map(jnp.asarray, events)
        _, ref = jax.device_get(batched._simulate(dev, use_kernel=False, **kw))
        _, got = jax.device_get(
            batched._simulate(dev, use_kernel=True, kernel_spec=cspec, **kw)
        )
        for field in ("ok", "gpu", "aidx", "frag", "free_sum", "active"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)),
                np.asarray(getattr(ref, field)), err_msg=field,
            )

    @pytest.mark.slow
    def test_delta_free_fusable_spec_lowers(self):
        """bf-bi consumes no ΔF table yet its keys are argmin-fusable: the
        fused select carries `use_kernel=True` alone (no delta_fn)."""
        core, _, _ = batched._build_core(
            policy="bf-bi", metric="blocked", num_gpus=4, use_kernel=True,
        )
        assert core.select_fn is not None and core.delta_fn is None
        cfg = SimConfig(num_gpus=4, offered_load=1.0, seed=3)
        r_k = batched.run_batched("bf-bi", cfg, runs=2, use_kernel=True)
        r_j = batched.run_batched("bf-bi", cfg, runs=2, use_kernel=False)
        assert {k: v for k, v in r_k.items() if np.isscalar(v)} == {
            k: v for k, v in r_j.items() if np.isscalar(v)
        }

    def test_build_core_dispatch_rules(self):
        """kernel_lowering picks the stage: "delta" stops at the ΔF kernel,
        True/"fused" wire select_fn (and migrate_fn on defrag specs)."""
        from repro.core.policy import PolicySpec

        mk = lambda **kw: batched._build_core(  # noqa: E731
            metric="blocked", num_gpus=4, use_kernel=True, **kw
        )[0]
        core = mk(policy="mfi")
        assert core.select_fn is not None and core.migrate_fn is None
        core = mk(policy="mfi-defrag")
        assert core.select_fn is not None and core.migrate_fn is not None
        delta_only = PolicySpec(
            name="mfi-delta-only", keys=("frag-delta", "gpu", "anchor"),
            kernel_lowering="delta",
        )
        core = mk(policy=delta_only)
        assert core.delta_fn is not None and core.select_fn is None
        assert core.migrate_fn is None

    @pytest.mark.slow
    def test_delta_lowering_matches_fused(self):
        """kernel_lowering="delta" (ΔF kernel + jnp argmin) and the fused
        path make identical decisions."""
        from repro.core.policy import PolicySpec

        delta_only = PolicySpec(
            name="mfi-delta-only", keys=("frag-delta", "gpu", "anchor"),
            kernel_lowering="delta",
        )
        cfg = SimConfig(num_gpus=4, offered_load=1.0, seed=3)
        r_d = batched.run_batched(delta_only, cfg, runs=2, use_kernel=True)
        r_f = batched.run_batched("mfi", cfg, runs=2, use_kernel=True)
        assert {k: v for k, v in r_d.items() if np.isscalar(v)} == {
            k: v for k, v in r_f.items() if np.isscalar(v)
        }


class TestLexTop2:
    """`_lex_top2` edge cases (the migrate stage's per-class best/runner-up
    reduction) — semantics the fused kernels' host merge must mirror."""

    def test_duplicate_best_keys(self):
        """Two columns with identical key tuples: best = lowest column,
        runner-up = the second tied column."""
        keys = jnp.asarray(
            [[[2.0, 1.0], [1.0, 0.0], [1.0, 0.0], [3.0, 9.0]]]
        )
        ok = jnp.ones((1, 4), bool)
        g1, ok1, g2, ok2 = batched._lex_top2(keys, ok)
        assert (int(g1[0]), bool(ok1[0])) == (1, True)
        assert (int(g2[0]), bool(ok2[0])) == (2, True)

    def test_all_infeasible_row(self):
        keys = jnp.zeros((1, 3, 2))
        ok = jnp.zeros((1, 3), bool)
        g1, ok1, g2, ok2 = batched._lex_top2(keys, ok)
        assert not bool(ok1[0]) and not bool(ok2[0])
        # no winner exists: the runner-up must NOT exclude the
        # placeholder column, so both carry argmax-of-empty-mask (0)
        assert int(g1[0]) == 0 and int(g2[0]) == 0

    def test_single_candidate_row(self):
        keys = jnp.asarray([[[5.0], [1.0], [7.0]]])
        ok = jnp.asarray([[False, True, False]])
        g1, ok1, g2, ok2 = batched._lex_top2(keys, ok)
        assert (int(g1[0]), bool(ok1[0])) == (1, True)
        assert not bool(ok2[0])

    def test_fused_merge_agrees_on_ties(self):
        """The fused path's cross-tile `_merge_top2` resolves duplicate-key
        ties to the same (lowest-gpu) pair as `_lex_top2`."""
        l = 2
        # two tiles' candidate rows for one class: [k0, k1, gpu, col, ok]
        cand = jnp.asarray(
            [[
                [1.0, 0.0, 4.0, 2.0, 1.0],   # tied best, higher gpu
                [2.0, 1.0, 0.0, 0.0, 1.0],
                [1.0, 0.0, 1.0, 3.0, 1.0],   # tied best, lowest gpu
                [3.0, 9.0, 2.0, 1.0, 1.0],
            ]]
        )
        g1, ok1, a1, _, g2, ok2, a2, _ = batched._merge_top2(cand, l)
        assert (int(g1[0]), int(a1[0]), bool(ok1[0])) == (1, 3, True)
        assert (int(g2[0]), int(a2[0]), bool(ok2[0])) == (4, 2, True)
        t1, tok1, t2, tok2 = batched._lex_top2(
            cand[..., :l + 1], cand[..., l + 2] > 0
        )
        # _lex_top2 ranks by column index; map through the gpu column
        assert int(cand[0, int(t1[0]), l]) == int(g1[0]) and bool(tok1[0])
        assert int(cand[0, int(t2[0]), l]) == int(g2[0]) and bool(tok2[0])


# ---------------------------------------------------------------------------
# Satellite: per-model request distributions
# ---------------------------------------------------------------------------


class TestPerModelDistributions:
    def test_mixture_is_capacity_weighted(self):
        spec = mig.ClusterSpec(((mig.A100_80GB, 1), (mig.A100_40GB, 3)))
        probs = resolve_probs(
            "uniform", spec, {"a100-40": "skew-small"}
        )
        want = (8 / 32) * DISTRIBUTIONS["uniform"] + (24 / 32) * DISTRIBUTIONS[
            "skew-small"
        ]
        np.testing.assert_allclose(probs, want)

    def test_default_is_exact_named_mix(self):
        cfg = SimConfig(num_gpus=4, distribution="skew-big")
        assert request_probs(cfg) is DISTRIBUTIONS["skew-big"]

    def test_validation(self):
        spec = mig.ClusterSpec.homogeneous(mig.A100_80GB, 2)
        with pytest.raises(ValueError, match="unknown device model"):
            resolve_probs("uniform", spec, {"v100": "uniform"})
        with pytest.raises(ValueError, match="not in the fleet"):
            resolve_probs("uniform", spec, {"h100-96": "uniform"})
        with pytest.raises(ValueError, match="unknown distribution"):
            resolve_probs("uniform", spec, {"a100-80": "weird"})

    def test_same_stream_parity_with_model_mixes(self):
        """Both engines draw from the same mixture, so decision-for-decision
        parity holds under per-model mixes too."""
        cfg = SimConfig(
            cluster_spec=MIXED,
            offered_load=0.9,
            seed=4,
            model_distributions={"a100-40": "skew-small", "a100-80": "skew-big"},
        )
        events, meta, trace, _ = _sim("mfi", cfg, MIXED, runs=2)
        ok_ref, gpu_ref, _ = replay.host_decisions(
            events, meta, "mfi", cfg.num_gpus, spec=MIXED
        )
        ok = np.asarray(trace.ok)
        np.testing.assert_array_equal(ok, ok_ref)
        np.testing.assert_array_equal(np.asarray(trace.gpu)[ok], gpu_ref[ok])

    def test_mix_shifts_the_sampled_classes(self):
        cfg_small = SimConfig(
            cluster_spec=MIXED, seed=0,
            model_distributions={m.name: "skew-small" for m in MIXED.models},
        )
        cfg_big = SimConfig(
            cluster_spec=MIXED, seed=0,
            model_distributions={m.name: "skew-big" for m in MIXED.models},
        )
        ev_s, *_ = batched.presample_arrivals(cfg_small, runs=4)
        ev_b, *_ = batched.presample_arrivals(cfg_big, runs=4)
        mean_s = mig.PROFILE_MEM[ev_s.pid[ev_s.pid >= 0]].mean()
        mean_b = mig.PROFILE_MEM[ev_b.pid[ev_b.pid >= 0]].mean()
        assert mean_s < mean_b  # small-skewed demand really is smaller


# ---------------------------------------------------------------------------
# Satellite: non-8-slice H200-141GB geometry
# ---------------------------------------------------------------------------


class TestH200Geometry:
    def test_registry_and_tables(self):
        assert mig.DEVICE_MODELS["h200-141"] is mig.H200_141GB
        m = mig.H200_141GB
        assert m.num_mem_slices == 12
        for prof in m.profiles:
            for a in prof.anchors:
                assert a + prof.mem <= 12
        np.testing.assert_array_equal(
            m.placement_masks.sum(axis=1), m.placement_mem
        )
        assert m.num_placements == 1 + 3 + 3 + 6 + 6 + 12
        assert m.max_anchors == 12

    def test_padded_width_tables(self):
        tables = batched.spec_tables(H200_MIX)
        assert tables.W.shape[2] == 12  # padded to the widest model
        assert H200_MIX.num_mem_slices == 12
        # A100 rows can never occupy the padding columns
        k_a100 = H200_MIX.models.index(mig.A100_80GB)
        assert np.asarray(tables.W)[k_a100, :, 8:].sum() == 0

    def test_mixed_fleet_same_stream_parity(self):
        cfg = SimConfig(cluster_spec=H200_MIX, offered_load=1.0, seed=6)
        for policy in ("mfi", "bf-bi"):
            events, meta, trace, _ = _sim(policy, cfg, H200_MIX, runs=2)
            ok_ref, gpu_ref, _ = replay.host_decisions(
                events, meta, policy, cfg.num_gpus, spec=H200_MIX
            )
            ok = np.asarray(trace.ok)
            np.testing.assert_array_equal(ok, ok_ref)
            np.testing.assert_array_equal(np.asarray(trace.gpu)[ok], gpu_ref[ok])
            replay.replay(events, meta, trace, cfg.num_gpus, spec=H200_MIX)
            _, drained = replay.drain_all(
                events, meta, trace, cfg.num_gpus, spec=H200_MIX
            )
            np.testing.assert_array_equal(drained, 0)


# ---------------------------------------------------------------------------
# Satellite: replica-axis sharding
# ---------------------------------------------------------------------------


class TestReplicaSharding:
    def test_single_device_fallbacks(self):
        if len(jax.devices()) > 1:
            pytest.skip("test targets the single-device fallback")
        events, _, _, _ = batched.presample_arrivals(
            SimConfig(num_gpus=2, seed=0), runs=2
        )
        dev = jax.tree.map(jnp.asarray, events)
        assert batched.shard_events(dev, 2, None) is dev  # auto: no-op
        assert batched.shard_events(dev, 2, False) is dev
        with pytest.raises(ValueError, match="only one device"):
            batched.shard_events(dev, 2, True)

    @pytest.mark.slow
    def test_multi_device_results_identical(self):
        """8 forced host devices: the sharded run must produce bitwise the
        same aggregates as the unsharded one (subprocess so the XLA_FLAGS
        override never pollutes this process)."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys, json
            sys.path.insert(0, "src")
            import jax
            from repro.sim import SimConfig
            from repro.sim.batched import run_batched
            assert len(jax.devices()) == 8
            cfg = SimConfig(num_gpus=4, offered_load=0.9, seed=2)
            r_sharded = run_batched("mfi", cfg, runs=8, shard=True)
            r_plain = run_batched("mfi", cfg, runs=8, shard=False)
            print(json.dumps({
                "sharded": {k: r_sharded[k] for k in
                            ("acceptance_rate", "utilization", "frag_severity")},
                "plain": {k: r_plain[k] for k in
                          ("acceptance_rate", "utilization", "frag_severity")},
            }))
        """)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["sharded"] == res["plain"]


# ---------------------------------------------------------------------------
# Queued protocol: the wait/park stages
# ---------------------------------------------------------------------------


def _sim_queued(policy, cfg, spec=None, runs=3):
    events, meta, rr, rc = batched.presample_arrivals(cfg, runs=runs, queued=True)
    kw = {}
    if spec is not None:
        kw = dict(
            midx=jnp.asarray(spec.model_index), tables=batched.spec_tables(spec)
        )
    final, trace = jax.device_get(
        batched._simulate(
            jax.tree.map(
                lambda x: jnp.asarray(x) if x is not None else None, events
            ),
            policy=policy,
            metric=cfg.metric,
            num_gpus=cfg.num_gpus,
            ring_rows=rr,
            ring_cols=rc,
            use_kernel=False,
            protocol="steady-queued",
            wait_slots=cfg.wait_capacity,
            wait_patience=cfg.wait_patience,
            **kw,
        )
    )
    return events, meta, trace, final


#: decision-trace hashes of the queued protocol at introduction — the wait
#: ring and park/admit stages must stay bit-for-bit reproducible
GOLDEN_QUEUED_TRACE_HASHES = {
    "homog": "e3d1a83fced05aaa968ff95c2d9e3ed5d71839e2e12d4c6634e0389f80918925",
    "mixed": "e368416188f84d500dbb7115410d3a24152fa06eac0dce525001032273a9f32f",
}


class TestQueuedEngine:
    def test_protocol_registered(self):
        proto = batched.resolve_protocol("steady-queued")
        assert proto.queued and proto.boundary_metrics and not proto.post_metrics
        assert not batched.resolve_protocol("steady").queued

    def test_steady_stream_unchanged_by_queued_draws(self):
        """Tenant/priority sampling happens strictly after the shared rng
        stream: the arrival stream itself must stay byte-identical, keeping
        every existing steady golden valid."""
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        ev_plain, meta_plain, *_ = batched.presample_arrivals(cfg, runs=3)
        ev_q, meta_q, *_ = batched.presample_arrivals(cfg, runs=3, queued=True)
        np.testing.assert_array_equal(ev_plain.pid, ev_q.pid)
        np.testing.assert_array_equal(ev_plain.exp_row, ev_q.exp_row)
        np.testing.assert_array_equal(meta_plain.slot, meta_q.slot)
        np.testing.assert_array_equal(meta_plain.end, meta_q.end)
        assert ev_plain.prio is None and ev_q.prio is not None

    @pytest.mark.parametrize(
        "tag,cfg_fn,spec,policy",
        [
            (
                "homog",
                lambda: SimConfig(num_gpus=5, offered_load=1.2, seed=7),
                None,
                "mfi",
            ),
            (
                "mixed",
                lambda: SimConfig(cluster_spec=MIXED, offered_load=1.1, seed=9),
                MIXED,
                "mfi-queued",
            ),
        ],
    )
    def test_same_stream_queued_host_parity(self, tag, cfg_fn, spec, policy):
        """Every in-place decision, park, wait-admission (origin AND
        placement) matches the independent host reference."""
        cfg = cfg_fn()
        events, meta, trace, _ = _sim_queued(policy, cfg, spec)
        ref = replay.queued_host_decisions(
            events, meta, policy, cfg.num_gpus, metric=cfg.metric, spec=spec,
            capacity=cfg.wait_capacity, patience=cfg.wait_patience,
        )
        np.testing.assert_array_equal(np.asarray(trace.ok), ref.ok)
        np.testing.assert_array_equal(np.asarray(trace.parked), ref.parked)
        acc = ref.ok
        np.testing.assert_array_equal(np.asarray(trace.gpu)[acc], ref.gpu[acc])
        np.testing.assert_array_equal(
            np.asarray(trace.wadm_eidx), ref.wadm_eidx
        )
        adm = ref.wadm_eidx >= 0
        np.testing.assert_array_equal(
            np.asarray(trace.wadm_gpu)[adm], ref.wadm_gpu[adm]
        )
        assert adm.sum() > 0, "stream exercised no wait admissions"

    def test_queued_replay_invariants(self):
        """The replay walk re-executes wait admissions (legal anchors, no
        double-booking, lease not expired) and drains cleanly."""
        cfg = SimConfig(num_gpus=5, offered_load=1.2, seed=7)
        events, meta, trace, _ = _sim_queued("mfi", cfg, None)
        replay.replay(events, meta, trace, cfg.num_gpus)
        _, drained = replay.drain_all(events, meta, trace, cfg.num_gpus)
        assert (drained == 0).all()

    def test_run_batched_queued_metrics(self):
        cfg = SimConfig(
            num_gpus=8, offered_load=1.2, seed=5, protocol="steady-queued"
        )
        r = batched.run_batched("mfi", cfg, runs=3)
        for k in ("wait_p50", "wait_p99", "fairness", "queue_admits"):
            assert k in r
        assert 0.0 <= r["wait_p50"] <= r["wait_p99"] <= cfg.wait_patience
        assert 0.0 < r["fairness"] <= 1.0
        assert r["acceptance_rate"] > 0.0
        # queueing can only help acceptance on the same stream shape
        plain = batched.run_batched(
            "mfi", SimConfig(num_gpus=8, offered_load=1.2, seed=5), runs=3
        )
        assert r["acceptance_rate"] >= plain["acceptance_rate"]

    def test_queued_rejects_defrag(self):
        cfg = SimConfig(
            num_gpus=4, offered_load=1.0, seed=1, protocol="steady-queued"
        )
        with pytest.raises(ValueError, match="defrag"):
            batched.run_batched("mfi-defrag", cfg, runs=2)

    def test_queued_requires_wait_slots(self):
        cfg = SimConfig(num_gpus=3, offered_load=1.0, seed=1)
        events, meta, rr, rc = batched.presample_arrivals(
            cfg, runs=2, queued=True
        )
        with pytest.raises(ValueError, match="wait_slots"):
            batched._simulate(
                jax.tree.map(
                    lambda x: jnp.asarray(x) if x is not None else None, events
                ),
                policy="mfi",
                metric=cfg.metric,
                num_gpus=cfg.num_gpus,
                ring_rows=rr,
                ring_cols=rc,
                use_kernel=False,
                protocol="steady-queued",
                wait_slots=0,
            )

    @pytest.mark.parametrize("tag", sorted(GOLDEN_QUEUED_TRACE_HASHES))
    def test_queued_decision_traces_hash_identically(self, tag):
        cfg, spec, policy = {
            "homog": (
                SimConfig(num_gpus=5, offered_load=1.2, seed=7), None, "mfi"
            ),
            "mixed": (
                SimConfig(cluster_spec=MIXED, offered_load=1.1, seed=9),
                MIXED,
                "mfi-queued",
            ),
        }[tag]
        _, _, trace, _ = _sim_queued(policy, cfg, spec)
        h = hashlib.sha256()
        for a in (
            trace.ok, trace.gpu, trace.aidx, trace.parked, trace.wadm_eidx,
            trace.wadm_gpu, trace.wadm_aidx, trace.free_sum, trace.active,
            trace.frag,
        ):
            h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
        assert h.hexdigest() == GOLDEN_QUEUED_TRACE_HASHES[tag]
