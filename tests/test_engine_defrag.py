"""Batched ``mfi-defrag``: the migrate stage against the host scheduler.

The migrate stage matches the host scheduler's canonical ``(total F,
victim gpu, victim anchor)`` search single-step AND decision-for-decision
over whole streams, migrations included, and migrated trajectories pass
the replay invariants (a migration never double-books or strands a
workload); the fused ``migrate_refine`` search reproduces the dense
reference oracle.  Kept apart from ``test_engine_core.py`` so the two
long randomized searches run on different test workers.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import mig
from repro.core.schedulers import MFIDefrag
from repro.sim import SimConfig
from repro.sim import batched, replay

from test_engine_core import (
    H200_MIX,
    MIGRATION_FIELDS,
    MIXED,
    PID,
    _random_cluster,
    _search_args,
    _sim,
)


# ---------------------------------------------------------------------------
# Batched mfi-defrag: the migrate stage
# ---------------------------------------------------------------------------


class TestBatchedDefrag:
    def test_single_step_matches_host_search(self):
        """The textbook scenario: a misplaced 1g.10gb blocks a 4g.40gb;
        both engines choose the same victim and target."""
        cl = mig.ClusterState(2)
        cl.allocate(1, PID["1g.10gb"], 0, 1)
        cl.allocate(2, PID["4g.40gb"], 1, 0)
        cl.allocate(3, PID["2g.20gb"], 1, 4)
        d = MFIDefrag(max_candidates=None)
        sel = d.select(cl, PID["4g.40gb"])
        assert sel is not None and d.pending_migration is not None
        vwid, vg, va = d.pending_migration
        workloads = [
            (g.gpu_id, a.profile_id, a.anchor)
            for g in cl.gpus
            for a in g.allocations.values()
        ]
        res = batched.policy_select_full(
            jnp.asarray(cl.occupancy_matrix()), jnp.int32(PID["4g.40gb"]),
            "mfi-defrag", workloads=workloads,
        )
        assert bool(res.ok) and bool(res.mig)
        assert (int(res.gpu), int(res.anchor)) == sel
        assert (int(res.new_gpu), int(res.new_anchor)) == (vg, va)
        assert (int(res.vic_gpu), int(res.vic_anchor)) == (0, 1)

    def test_randomized_single_step_parity(self):
        """Random clusters (homogeneous + mixed): decision AND migration
        agree with the host's canonical unbounded search."""
        rng = np.random.default_rng(17)
        migrations = 0
        for trial in range(40):
            spec = None if trial % 2 == 0 else MIXED
            cl = (
                mig.ClusterState(int(rng.integers(1, 6)))
                if spec is None
                else mig.ClusterState(spec=spec)
            )
            wid = 0
            density = rng.random() * 1.2
            for g in range(cl.num_gpus):
                for pid in rng.permutation(mig.NUM_PROFILES):
                    if rng.random() < density:
                        anchors = cl.gpus[g].feasible_anchors(int(pid))
                        if anchors:
                            cl.allocate(wid, int(pid), g, int(rng.choice(anchors)))
                            wid += 1
            pid = int(rng.integers(0, mig.NUM_PROFILES))
            d = MFIDefrag(max_candidates=None)
            ref = d.select(cl, pid)
            workloads = [
                (g.gpu_id, a.profile_id, a.anchor)
                for g in cl.gpus
                for a in g.allocations.values()
            ]
            res = batched.policy_select_full(
                jnp.asarray(cl.occupancy_matrix()), jnp.int32(pid),
                "mfi-defrag", spec=spec, workloads=workloads,
            )
            got = (int(res.gpu), int(res.anchor)) if bool(res.ok) else None
            assert got == ref, f"trial {trial}: host={ref} batched={got}"
            if d.pending_migration is not None:
                migrations += 1
                vwid, vg, va = d.pending_migration
                old = next(
                    (g.gpu_id, a.anchor)
                    for g in cl.gpus
                    for w, a in g.allocations.items()
                    if w == vwid
                )
                assert bool(res.mig)
                assert (int(res.vic_gpu), int(res.vic_anchor)) == old
                assert (int(res.new_gpu), int(res.new_anchor)) == (vg, va)
            else:
                assert not bool(res.mig)
        assert migrations >= 2  # the fuzz actually exercised the search

    @pytest.mark.parametrize("spec", [None, MIXED], ids=["homog", "mixed"])
    def test_same_stream_decisions_and_migrations_match(self, spec):
        cfg = (
            SimConfig(num_gpus=4, offered_load=1.1, seed=3)
            if spec is None
            else SimConfig(cluster_spec=spec, offered_load=1.0, seed=3)
        )
        events, meta, trace, _ = _sim("mfi-defrag", cfg, spec, runs=2)
        assert np.asarray(trace.mig).sum() > 0  # migrations actually happened
        ref = replay.host_decisions_full(
            events, meta, "mfi-defrag", cfg.num_gpus, spec=spec,
            max_candidates=None,
        )
        ok = np.asarray(trace.ok)
        np.testing.assert_array_equal(ok, ref.ok)
        np.testing.assert_array_equal(np.asarray(trace.gpu)[ok], ref.gpu[ok])
        np.testing.assert_array_equal(np.asarray(trace.mig), ref.mig)
        m = np.asarray(trace.mig)
        for dev, host in (
            (trace.mig_from_gpu, ref.mig_from_gpu),
            (trace.mig_from_anchor, ref.mig_from_anchor),
            (trace.mig_to_gpu, ref.mig_to_gpu),
            (trace.mig_to_anchor, ref.mig_to_anchor),
        ):
            np.testing.assert_array_equal(np.asarray(dev)[m], host[m])

    def test_migration_invariants_via_replay(self):
        """Deterministic form of the hypothesis invariant: a migration
        never double-books a slice and never strands a workload (the
        migrated victim still drains exactly from its new placement)."""
        for seed in (3, 5, 11):
            cfg = SimConfig(num_gpus=4, offered_load=1.2, seed=seed)
            events, meta, trace, final = _sim("mfi-defrag", cfg, runs=2)
            occ = replay.replay(events, meta, trace, cfg.num_gpus)
            w = np.asarray(mig.PLACEMENT_MASKS, np.float32)
            np.testing.assert_allclose(final.base, occ.astype(np.float32) @ w.T)
            _, drained = replay.drain_all(events, meta, trace, cfg.num_gpus)
            np.testing.assert_array_equal(drained, 0)

    def test_defrag_dominates_mfi_single_step(self):
        """At any fixed cluster state, mfi-defrag accepts whenever plain MFI
        does (it only ADDS acceptances via migration) — the single-step
        dominance property.  Run-level acceptance is not monotone (a greedy
        migration can worsen the future state), so this is the invariant.
        """
        rng = np.random.default_rng(23)
        extra = 0
        for _ in range(30):
            cl = mig.ClusterState(3)
            wid = 0
            for g in range(3):
                for pid in rng.permutation(mig.NUM_PROFILES):
                    if rng.random() < 0.7:
                        anchors = cl.gpus[g].feasible_anchors(int(pid))
                        if anchors:
                            cl.allocate(wid, int(pid), g, int(rng.choice(anchors)))
                            wid += 1
            occ = jnp.asarray(cl.occupancy_matrix())
            workloads = [
                (g.gpu_id, a.profile_id, a.anchor)
                for g in cl.gpus
                for a in g.allocations.values()
            ]
            for pid in range(mig.NUM_PROFILES):
                _, _, ok_mfi = batched.policy_select(occ, jnp.int32(pid), "mfi")
                _, _, ok_d = batched.policy_select(
                    occ, jnp.int32(pid), "mfi-defrag", workloads=workloads
                )
                assert bool(ok_d) >= bool(ok_mfi)
                extra += int(bool(ok_d) and not bool(ok_mfi))
        assert extra > 0  # the migration search actually rescued rejects

    def test_facade_runs_defrag_on_batched_engine(self):
        from repro import api

        r = api.simulate("mfi-defrag", engine="batched", num_gpus=3, runs=2)
        assert 0.0 < r["acceptance_rate"] <= 1.0


class TestFusedMigrateSearch:
    """`migrate_fn` plugged into `_migrate_search` must reproduce the dense
    reference oracle decision-for-decision (randomized occupancy, mixed and
    padded-geometry fleets included)."""

    @pytest.mark.slow
    @pytest.mark.parametrize(
        "spec", [None, MIXED, H200_MIX], ids=["homog", "mixed", "h200"]
    )
    def test_equivalence_randomized(self, spec):
        rng = np.random.default_rng(31)
        pspec = batched.resolve("mfi-defrag")
        migrations = 0
        for trial in range(10):
            cl, workloads = _random_cluster(
                rng,
                spec=spec,
                num_gpus=int(rng.integers(2, 6)) if spec is None else None,
                density=rng.random() * 1.2,
            )
            if not workloads:
                continue
            pid = int(rng.integers(0, mig.NUM_PROFILES))
            rows = int(rng.integers(1, 40))
            cols = -(-max(1, len(workloads)) // rows) + int(rng.integers(0, 4))
            args = _search_args(cl, workloads, pid, (rows, cols), rng)
            want = batched._migrate_search_dense(**args)
            args["migrate_fn"] = batched.make_migrate_fn(
                cl.spec, pspec, interpret=True
            )
            got = batched._migrate_search(**args)
            assert bool(got.mig) == bool(want.mig), f"trial {trial}"
            if bool(want.mig):
                migrations += 1
                for field in MIGRATION_FIELDS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(got, field)),
                        np.asarray(getattr(want, field)),
                        err_msg=f"trial {trial}: {field}",
                    )
        assert migrations >= 2
