"""The expire stage's two ring-drain forms agree bit for bit.

``repro.sim.batched._drain_ring_row`` reads (and clears) one row of an
expiry-ring plane either by a row gather or by a one-hot over the rows;
``ring_drain_onehot`` picks the form from the backend and the replicas per
device (the one-hot form on a TPU at scale, the gather on the CPU).  The
CPU runs both here: the helper against numpy, the vmapped expire stage
against the per-replica gather, and whole scans in the one-hot form
against the gather form, whose traces the golden hashes of
``test_engine_core.py`` pin.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import SimConfig, batched


def _random_expire_inputs(core, rng, runs=6, rows=7, cols=5):
    """A vmapped carry with random ring planes (defrag planes and window
    counts included), one drain row per replica — the trash row ``K + 1``
    among them — and ``new_slot`` false on some lanes."""
    st = batched._broadcast_init(core, runs, rows, cols, 0)
    m, n = st.base.shape[1:]
    s = st.ring_mask.shape[-1]
    ri = lambda hi, shape: jnp.asarray(rng.integers(0, hi, shape), jnp.int32)  # noqa: E731
    st = st._replace(
        base=jnp.asarray(rng.integers(0, 5, (runs, m, n)), jnp.float32),
        free=ri(9, (runs, m)),
        ring_gpu=ri(m, (runs, rows, cols)),
        ring_mask=ri(2, (runs, rows, cols, s)),
        ring_pid=ri(6, (runs, rows, cols)),
        ring_aidx=ri(7, (runs, rows, cols)),
    )
    drain_row = rng.integers(0, rows, runs).astype(np.int32)
    drain_row[0] = rows - 1  # the trash row
    new_slot = np.array([True, True, False, True, False, True][:runs])
    return st, jnp.asarray(drain_row), jnp.asarray(new_slot)


def _scan(policy, cfg, runs):
    events, _, rr, rc = batched.presample_arrivals(cfg, runs=runs)
    return jax.device_get(
        batched._simulate(
            jax.tree.map(jnp.asarray, events), policy=policy, metric=cfg.metric,
            num_gpus=cfg.num_gpus, ring_rows=rr, ring_cols=rc, use_kernel=False,
        )
    )


def test_cpu_keeps_the_row_gather():
    assert not batched.ring_drain_onehot(10**6)


@pytest.mark.parametrize("form", ["gather", "onehot"])
def test_drain_ring_row_reads_and_clears_the_row(form):
    drain = jax.jit(jax.vmap(
        functools.partial(batched._drain_ring_row, onehot=form == "onehot")
    ))
    rng = np.random.default_rng(4)
    plane = rng.integers(0, 9, (6, 7, 5, 8)).astype(np.int32)
    row = np.array([6, 0, 3, 3, 5, 1], np.int32)
    clear = np.array([1, 1, 0, 1, 0, 1], np.int32)
    got, cleared = drain(plane, row, clear)
    read, same = drain(plane[..., 0], row)
    want = plane.copy()
    for r in range(6):
        want[r, row[r]] *= 1 - clear[r]
        np.testing.assert_array_equal(got[r], plane[r, row[r]] * clear[r])
        np.testing.assert_array_equal(read[r], plane[r, row[r], :, 0])
    np.testing.assert_array_equal(cleared, want)
    np.testing.assert_array_equal(same, plane[..., 0])
    assert got.dtype == cleared.dtype == jnp.int32


@pytest.mark.parametrize("form", ["gather", "onehot"])
def test_expire_stage_states_agree(form):
    """The vmapped expire stage in ``form`` gives the per-replica state of
    the gather form exactly, every plane of the carry included."""
    core, _, _ = batched._build_core(
        policy="mfi-defrag", metric="blocked", num_gpus=6, use_kernel=False,
    )
    st, drain_row, new_slot = _random_expire_inputs(core, np.random.default_rng(11))
    one = jax.jit(core._stage_expire)
    want = [
        one(jax.tree.map(lambda x: x[r], st), drain_row[r], new_slot[r])
        for r in range(drain_row.shape[0])
    ]
    core = dataclasses.replace(core, drain_onehot=form == "onehot")
    got = jax.jit(jax.vmap(core._stage_expire))(st, drain_row, new_slot)
    for r, w in enumerate(want):
        for name in w._fields:
            if getattr(w, name) is not None:
                np.testing.assert_array_equal(
                    getattr(got, name)[r], getattr(w, name),
                    err_msg=f"replica {r}: {name}",
                )
    rows = np.arange(st.ring_mask.shape[1])
    for r in range(drain_row.shape[0]):
        drained = (rows == int(drain_row[r])) & bool(new_slot[r])
        np.testing.assert_array_equal(
            got.ring_mask[r], np.asarray(st.ring_mask[r]) * ~drained[:, None, None]
        )


@pytest.mark.parametrize(
    "policy,cfg",
    [
        ("mfi", SimConfig(num_gpus=5, offered_load=1.1, seed=7)),  # golden "homog"
        ("mfi-defrag", SimConfig(num_gpus=4, offered_load=1.1, seed=3)),
    ],
    ids=["mfi", "mfi-defrag"],
)
def test_onehot_scans_reproduce_the_gather_scans(monkeypatch, policy, cfg):
    """Whole scans in the one-hot form: trace and final carry equal to the
    gather form's.  The chooser reads the backend, the CPU here, so the
    test steers it (and clears the jit caches on both sides of that)."""
    want = _scan(policy, cfg, runs=3)
    jax.clear_caches()
    monkeypatch.setattr(batched, "ring_drain_onehot", lambda replicas: True)
    try:
        got = _scan(policy, cfg, runs=3)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(a, b)
    if policy == "mfi-defrag":
        assert np.asarray(want[1].mig).sum() > 0  # migrations actually happened
