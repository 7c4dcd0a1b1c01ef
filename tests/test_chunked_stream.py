"""Chunked streaming scan driver: bit-exact parity with the monolithic path.

The chunked driver (:func:`repro.sim.batched.simulate_chunked`) must be a
pure performance/memory restructuring — every pinned golden SHA-256 trace
hash and aggregate reproduces *exactly* through it for any chunk size,
including chunk size 1, a divisor of the stream length, and a non-divisor
forcing a ragged final chunk.  The carry holds all cross-event state, so
leases expiring exactly at a chunk boundary and queued wait-admissions
whose arrival and admission land in different chunks must come out
identical; checkpoint/resume through :mod:`repro.checkpoint.ckpt` must
rejoin the monolithic stream bit-for-bit.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import simulate
from repro.sim import SimConfig
from repro.sim import batched, replay

from test_engine_core import (
    GOLDEN_AGGREGATES,
    GOLDEN_CONFIGS,
    GOLDEN_QUEUED_TRACE_HASHES,
    GOLDEN_TRACE_HASHES,
    MIXED,
    _sim,
    _sim_queued,
)

#: (tag -> monolithic golden configuration) for the steady trace hashes
STEADY_GOLDEN = {
    "homog": (lambda: SimConfig(num_gpus=5, offered_load=1.1, seed=7), None, "mfi"),
    "mixed": (
        lambda: SimConfig(cluster_spec=MIXED, offered_load=1.0, seed=9),
        MIXED,
        "mfi",
    ),
}

#: (tag -> monolithic golden configuration) for the queued trace hashes
QUEUED_GOLDEN = {
    "homog": (lambda: SimConfig(num_gpus=5, offered_load=1.2, seed=7), None, "mfi"),
    "mixed": (
        lambda: SimConfig(cluster_spec=MIXED, offered_load=1.1, seed=9),
        MIXED,
        "mfi-queued",
    ),
}


def _statics(cfg, policy, spec=None, protocol="steady"):
    kw = dict(
        policy=policy, metric=cfg.metric, num_gpus=cfg.num_gpus,
        use_kernel=False, protocol=protocol,
    )
    if protocol == "steady-queued":
        kw.update(wait_slots=cfg.wait_capacity, wait_patience=cfg.wait_patience)
    if spec is not None:
        kw.update(
            midx=jnp.asarray(spec.model_index), tables=batched.spec_tables(spec)
        )
    return kw


def _presample(cfg, runs, protocol):
    if protocol == "cumulative":
        return batched.presample_cumulative(cfg, runs=runs)
    return batched.presample_arrivals(
        cfg, runs=runs, queued=(protocol == "steady-queued")
    )


def _chunked(policy, cfg, chunk_size, spec=None, runs=3, protocol="steady", **kw):
    events, meta, rr, rc = _presample(cfg, runs, protocol)
    state, trace = batched.simulate_chunked(
        events, chunk_size=chunk_size, ring_rows=rr, ring_cols=rc,
        **_statics(cfg, policy, spec, protocol), **kw,
    )
    return events, meta, jax.device_get(trace), jax.device_get(state)


def _chunk_sizes(e_max):
    """(1, a divisor of the stream length, a non-divisor → ragged last chunk)."""
    div = next((d for d in range(2, e_max) if e_max % d == 0), e_max)
    ragged = next(c for c in range(max(2, e_max // 3), e_max) if e_max % c)
    return 1, div, ragged


def _steady_hash(trace):
    h = hashlib.sha256()
    for a in (
        trace.ok, trace.gpu, trace.aidx, trace.free_sum, trace.active,
        trace.frag,
    ):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def _queued_hash(trace):
    h = hashlib.sha256()
    for a in (
        trace.ok, trace.gpu, trace.aidx, trace.parked, trace.wadm_eidx,
        trace.wadm_gpu, trace.wadm_aidx, trace.free_sum, trace.active,
        trace.frag,
    ):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def _assert_traces_equal(a, b):
    for name in type(a)._fields:
        fa, fb = getattr(a, name), getattr(b, name)
        assert (fa is None) == (fb is None), name
        if fa is not None:
            np.testing.assert_array_equal(
                np.asarray(fa), np.asarray(fb), err_msg=name
            )


# ---------------------------------------------------------------------------
# Golden parity: every pinned hash/aggregate through the chunked path
# ---------------------------------------------------------------------------


class TestChunkedGoldenParity:
    @pytest.mark.parametrize("tag", sorted(STEADY_GOLDEN))
    def test_steady_trace_hashes_all_chunk_sizes(self, tag):
        cfg_fn, spec, policy = STEADY_GOLDEN[tag]
        e_max = _presample(cfg_fn(), 3, "steady")[0].pid.shape[0]
        for cs in _chunk_sizes(e_max):
            _, _, trace, _ = _chunked(policy, cfg_fn(), cs, spec)
            assert _steady_hash(trace) == GOLDEN_TRACE_HASHES[tag], (
                f"{tag}: chunk_size={cs} drifted from the monolithic golden"
            )

    @pytest.mark.parametrize("tag", sorted(QUEUED_GOLDEN))
    def test_queued_trace_hashes_all_chunk_sizes(self, tag):
        cfg_fn, spec, policy = QUEUED_GOLDEN[tag]
        e_max = _presample(cfg_fn(), 3, "steady-queued")[0].pid.shape[0]
        for cs in _chunk_sizes(e_max):
            _, _, trace, _ = _chunked(
                policy, cfg_fn(), cs, spec, protocol="steady-queued"
            )
            assert _queued_hash(trace) == GOLDEN_QUEUED_TRACE_HASHES[tag], (
                f"{tag}: chunk_size={cs} drifted from the queued golden"
            )

    @pytest.mark.parametrize("tag,policy", sorted(GOLDEN_AGGREGATES))
    def test_golden_aggregates_through_chunked_run_batched(self, tag, policy):
        r = batched.run_batched(
            policy, GOLDEN_CONFIGS[tag](), runs=4, chunk_size=23
        )
        for key, want in GOLDEN_AGGREGATES[(tag, policy)].items():
            assert r[key] == want, f"{tag}/{policy}/{key}: {r[key]!r} != {want!r}"

    def test_cumulative_chunked_matches_monolithic(self):
        cfg = SimConfig(num_gpus=4, offered_load=1.0, seed=3)
        _, _, mono, final = _sim("mfi", cfg, runs=2, protocol="cumulative")
        _, _, trace, state = _chunked(
            "mfi", cfg, 17, runs=2, protocol="cumulative"
        )
        _assert_traces_equal(trace, mono)
        for fa, fb in zip(jax.tree.leaves(state), jax.tree.leaves(final)):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))

    def test_defrag_chunked_matches_monolithic(self):
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        _, _, mono, _ = _sim("mfi-defrag", cfg, runs=2)
        _, _, trace, _ = _chunked("mfi-defrag", cfg, 11, runs=2)
        _assert_traces_equal(trace, mono)

    def test_stream_false_keeps_device_trace_identical(self):
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        _, _, streamed, _ = _chunked("mfi", cfg, 13)
        _, _, resident, _ = _chunked("mfi", cfg, 13, stream=False)
        _assert_traces_equal(streamed, resident)


# ---------------------------------------------------------------------------
# Chunk-boundary semantics: state that must survive the cut
# ---------------------------------------------------------------------------


class TestChunkBoundarySemantics:
    def test_lease_expiring_exactly_at_boundary(self):
        """Cut the stream exactly where a lease expires: the expiry ring
        rides the carry, so the drain on the boundary event must behave as
        if the scan never stopped."""
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        _, _, mono, _ = _sim("mfi", cfg, runs=3)
        active = np.asarray(mono.active)[:, 0]
        drops = np.nonzero(np.diff(active) < 0)[0] + 1  # expiry fired here
        assert drops.size, "stream exercised no expiries"
        boundary = int(drops[drops > 1][0])
        _, _, trace, _ = _chunked("mfi", cfg, boundary)
        _assert_traces_equal(trace, mono)
        assert _steady_hash(trace) == GOLDEN_TRACE_HASHES["homog"]

    def test_wait_admission_spanning_chunks(self):
        """A request parked in chunk k and admitted from the wait ring in a
        later chunk: the ring (pids, deadlines, priorities) crosses the
        boundary inside the carry."""
        cfg = SimConfig(num_gpus=5, offered_load=1.2, seed=7)
        _, _, mono, _ = _sim_queued("mfi", cfg)
        wadm = np.asarray(mono.wadm_eidx)
        adm_evt, adm_run = np.nonzero(wadm >= 0)
        assert adm_evt.size, "stream exercised no wait admissions"
        arrivals = wadm[adm_evt, adm_run]
        span = adm_evt > arrivals  # parked strictly before the admitting event
        assert span.any(), "no admission separable from its arrival"
        e, a = int(adm_evt[span][0]), int(arrivals[span][0])
        boundary = a + 1  # arrival lands in chunk 0, admission in a later one
        assert boundary <= e
        _, _, trace, _ = _chunked(
            "mfi", cfg, boundary, protocol="steady-queued"
        )
        _assert_traces_equal(trace, mono)
        assert _queued_hash(trace) == GOLDEN_QUEUED_TRACE_HASHES["homog"]


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------


class TestCheckpointResume:
    def test_resume_reproduces_queued_golden_bit_for_bit(self, tmp_path):
        """Checkpoint mid-run, restore into a fresh template, resume the
        tail, splice onto the monolithic head: the pinned golden hash must
        come out unchanged."""
        cfg = SimConfig(num_gpus=5, offered_load=1.2, seed=7)
        events, meta, rr, rc = _presample(cfg, 3, "steady-queued")
        e_max = events.pid.shape[0]
        statics = _statics(cfg, "mfi", protocol="steady-queued")
        path = tmp_path / "carry"
        cs = 13
        batched.simulate_chunked(
            events, chunk_size=cs, ring_rows=rr, ring_cols=rc,
            checkpoint_path=path, checkpoint_every=3, **statics,
        )
        template = batched.init_carry(3, ring_rows=rr, ring_cols=rc, **statics)
        state, done = batched.load_stream_checkpoint(path, template)
        assert 0 < done < e_max, "checkpoint did not land mid-stream"
        assert done % (3 * cs) == 0
        _, tail = batched.simulate_chunked(
            events, chunk_size=cs, ring_rows=rr, ring_cols=rc,
            carry=state, start=done, **statics,
        )
        _, _, mono, _ = _sim_queued("mfi", cfg)
        head = jax.tree.map(
            lambda x: None if x is None else np.asarray(x)[:done], mono,
            is_leaf=lambda x: x is None,
        )
        spliced = batched._concat_traces([head, jax.device_get(tail)],
                                         np.concatenate)
        assert _queued_hash(spliced) == GOLDEN_QUEUED_TRACE_HASHES["homog"]

    def test_checkpoint_metadata_records_events_done(self, tmp_path):
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        events, meta, rr, rc = _presample(cfg, 2, "steady")
        statics = _statics(cfg, "mfi")
        state, _ = batched.simulate_chunked(
            events, chunk_size=events.pid.shape[0], ring_rows=rr,
            ring_cols=rc, **statics,
        )
        batched.save_stream_checkpoint(
            tmp_path / "c", state, 42, metadata={"seed": cfg.seed}
        )
        side = json.loads((tmp_path / "c.json").read_text())
        assert side["step"] == 42
        assert side["kind"] == "replica-carry"  # merged into the sidecar
        assert side["seed"] == cfg.seed

    def test_restore_rejects_mismatched_template(self, tmp_path):
        """A carry from one configuration must not restore into another:
        the flat-npz validation catches structure/shape drift loudly."""
        cfg = SimConfig(num_gpus=5, offered_load=1.2, seed=7)
        events, meta, rr, rc = _presample(cfg, 3, "steady-queued")
        statics = _statics(cfg, "mfi", protocol="steady-queued")
        state = batched.init_carry(3, ring_rows=rr, ring_cols=rc, **statics)
        batched.save_stream_checkpoint(tmp_path / "c", state, 0)
        wrong = batched.init_carry(
            3, ring_rows=rr, ring_cols=rc,
            **_statics(cfg, "mfi", protocol="steady"),
        )
        with pytest.raises(ValueError, match="mismatch"):
            batched.load_stream_checkpoint(tmp_path / "c", wrong)


# ---------------------------------------------------------------------------
# Replay validation over chunked traces
# ---------------------------------------------------------------------------


class TestChunkedReplayValidation:
    def test_steady_chunked_trace_passes_replay(self):
        cfg = SimConfig(num_gpus=5, offered_load=1.1, seed=7)
        events, meta, trace, _ = _chunked("mfi", cfg, 49)
        replay.replay(events, meta, trace, cfg.num_gpus)

    def test_queued_chunked_trace_passes_replay_and_drains(self):
        cfg = SimConfig(num_gpus=5, offered_load=1.2, seed=7)
        events, meta, trace, _ = _chunked(
            "mfi", cfg, 31, protocol="steady-queued"
        )
        replay.replay(events, meta, trace, cfg.num_gpus)
        _, drained = replay.drain_all(events, meta, trace, cfg.num_gpus)
        assert (drained == 0).all()


# ---------------------------------------------------------------------------
# Error paths
# ---------------------------------------------------------------------------


class TestChunkedErrorPaths:
    def _stream(self):
        cfg = SimConfig(num_gpus=3, offered_load=1.0, seed=1)
        events, meta, rr, rc = _presample(cfg, 2, "steady")
        return cfg, events, rr, rc

    def test_rejects_nonpositive_chunk_size(self):
        cfg, events, rr, rc = self._stream()
        with pytest.raises(ValueError, match="chunk_size"):
            batched.simulate_chunked(
                events, chunk_size=0, ring_rows=rr, ring_cols=rc,
                **_statics(cfg, "mfi"),
            )

    def test_rejects_start_outside_stream(self):
        cfg, events, rr, rc = self._stream()
        e_max = events.pid.shape[0]
        for start in (-1, e_max):
            with pytest.raises(ValueError, match="start"):
                batched.simulate_chunked(
                    events, chunk_size=8, ring_rows=rr, ring_cols=rc,
                    start=start, **_statics(cfg, "mfi"),
                )

    def test_rejects_carry_ring_geometry_mismatch(self):
        cfg, events, rr, rc = self._stream()
        statics = _statics(cfg, "mfi")
        bad = batched.init_carry(2, ring_rows=rr + 1, ring_cols=rc, **statics)
        with pytest.raises(ValueError, match="ring geometry"):
            batched.simulate_chunked(
                events, chunk_size=8, ring_rows=rr, ring_cols=rc,
                carry=bad, **statics,
            )

    def test_run_batched_rejects_stream_knobs_without_chunk_size(self):
        cfg = SimConfig(num_gpus=3, offered_load=1.0, seed=1)
        with pytest.raises(ValueError, match="chunk_size"):
            batched.run_batched("mfi", cfg, runs=2, stream=True)

    def test_chunked_record_spans_and_bytes(self):
        """A chunked call's record: each chunk's transfer, dispatch and fetch
        in the double-buffered order, and byte counters equal to the bytes
        of the staged chunks and of the fetched traces."""
        from repro import obs

        cfg, events, rr, rc = self._stream()
        e_max = events.pid.shape[0]
        chunk = e_max // 3 + 1  # three chunks, the last one ragged
        _, trace = batched.simulate_chunked(
            events, chunk_size=chunk, ring_rows=rr, ring_cols=rc,
            **_statics(cfg, "mfi"),
        )
        call = obs.calls()[-1]
        assert call.root.name == "simulate_chunked"
        kids = [s.name for s in call.spans if s.parent == 0]
        assert kids == ["transfer", "dispatch", "transfer", "dispatch", "fetch",
                        "transfer", "dispatch", "fetch", "fetch"]
        starts = [s.start_ns for s in call.spans]
        assert starts == sorted(starts)
        assert all(s.start_ns <= s.end_ns for s in call.spans)
        host = jax.tree.map(np.asarray, events)
        per_chunk = [
            sum(x[lo:lo + chunk].nbytes for x in jax.tree.leaves(host))
            for lo in range(0, e_max, chunk)
        ]
        assert call.counters["h2d_bytes"] == sum(per_chunk)
        assert call.counters["h2d_overlapped_bytes"] == sum(per_chunk[1:])
        assert call.counters["d2h_bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(trace)
        )

    def test_api_python_engine_rejects_chunk_size(self):
        with pytest.raises(ValueError, match="batched"):
            simulate(
                "mfi", engine="python", runs=1, num_gpus=3,
                offered_load=1.0, seed=1, chunk_size=8,
            )


# ---------------------------------------------------------------------------
# shard_events no-copy fix (multi-device, subprocess)
# ---------------------------------------------------------------------------


class TestShardEventsNoCopy:
    @pytest.mark.slow
    def test_resharding_already_placed_events_is_a_no_op(self):
        """``shard_events`` on a stream already committed to the replica
        mesh must return the *same* buffers, not re-run ``device_put``."""
        code = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            )
            import json
            import sys
            sys.path.insert(0, "src")
            import jax
            from repro.sim import SimConfig, batched

            cfg = SimConfig(num_gpus=4, offered_load=1.0, seed=0)
            events, *_ = batched.presample_arrivals(cfg, runs=8)
            ev1 = batched.shard_events(events, 8, shard=True)
            ev2 = batched.shard_events(ev1, 8, shard=True)
            l1 = [x for x in jax.tree.leaves(ev1)]
            l2 = [x for x in jax.tree.leaves(ev2)]
            # the chunked driver composes with the replica mesh: every
            # staged chunk is placed on it, results stay bitwise identical
            r_chunked = batched.run_batched(
                "mfi", cfg, runs=8, shard=True, chunk_size=19
            )
            r_plain = batched.run_batched("mfi", cfg, runs=8, shard=False)
            keys = ("acceptance_rate", "utilization", "frag_severity")
            print(json.dumps({
                "same_buffers": all(a is b for a, b in zip(l1, l2)),
                "committed": all(x.committed for x in l1),
                "num_leaves": len(l1),
                "chunked_sharded": {k: r_chunked[k] for k in keys},
                "plain": {k: r_plain[k] for k in keys},
            }))
            """
        )
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600, cwd=repo,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["committed"], "sharded events not committed to the mesh"
        assert out["same_buffers"], (
            "shard_events re-ran device_put on already-placed events"
        )
        assert out["num_leaves"] > 0
        assert out["chunked_sharded"] == out["plain"]
