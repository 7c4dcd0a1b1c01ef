"""Compile-only checks of the engine's main path for a TPU v5e chip.

Interpret mode cannot see Mosaic's rules (block tiling, VMEM limits), so
these tests lower the Pallas kernels exactly as the batched engine calls
them on a TPU (``make_*_fn(..., interpret=False)``, vmapped over replicas)
and compile them for one chip of a *described* ``v5e:2x2`` topology,
together with one compile of the pure-jnp ``_simulate`` scan.  Nothing
runs: a pass says the chip's compiler accepts the program, not that it is
fast or correct on the device.

The topology is described inside a fixture (the TPU library can be loaded
by one process at a time, and every test worker imports this module), and
JAX's persistent compilation cache is off while these compiles run: an
entry compiled for a described chip cannot be read back without one.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import mig
from repro.core.policy import resolve
from repro.kernels.fragscore import fragscore
from repro.sim import SimConfig, batched

R = 64  # replicas vmapped over, as in the engine's scan step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _state_shapes(sharding, m, n):
    """``(base, free, f, pid)`` of R replicas, as the select stage sees them."""
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding
    )
    return (
        s((R, m, n)), s((R, m), jnp.int32), s((R, m)), s((R,), jnp.int32)
    )


def _kernel_program(kernel, m, sharding):
    spec = batched._default_spec(m)
    tables = batched.spec_tables(spec)
    n = int(tables.V.shape[-1])
    state = _state_shapes(sharding, m, n)
    if kernel == "delta_from_base":
        return jax.vmap(batched.make_delta_fn(spec, interpret=False)), state
    if kernel.startswith("select_from_base"):
        policy = kernel.split(":")[1]
        fn = batched.make_select_fn(
            spec, resolve(policy, engine="batched"), interpret=False
        )
        return jax.vmap(fn), state
    if kernel == "migrate_refine":
        c = m * int(tables.W.shape[-1])  # the live-victim budget C = M·S
        fn = batched.make_migrate_fn(
            spec, resolve("mfi-defrag", engine="batched"), interpret=False
        )
        s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=sharding
        )
        victims = (
            s((R, c, n)), s((R, c), jnp.int32), s((R, c)),
            s((R, c), jnp.int32), s((R, c), jnp.int32), s((R, c), jnp.int32),
        )
        return jax.vmap(fn), state[:3] + victims
    assert kernel == "fragscore", kernel
    # the engine rescores the touched rows of each replica's occupancy
    fn = batched.make_frag_fn("blocked", True, mig.A100_80GB, interpret=False)
    s_ = int(tables.W.shape[-1])
    occ = jax.ShapeDtypeStruct((R, m, s_), jnp.int32, sharding=sharding)
    return jax.vmap(fn), (occ,)


@pytest.mark.parametrize("m", [100, 1000])
@pytest.mark.parametrize(
    "kernel",
    [
        "delta_from_base",
        "select_from_base:mfi",
        "select_from_base:ff",
        "migrate_refine",
        "fragscore",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, m):
    """M=100 is the paper's fleet; M=1000 spans several BLK_M row tiles."""
    fn, shapes = _kernel_program(kernel, m, one_chip)
    text = _compile(fn, *shapes).as_text()
    assert "tpu_custom_call" in text, f"{kernel}: no Mosaic kernel in the program"
    # a profile names the kernel's op after its pallas_call, which the
    # benchmark's kernel metrics match by name
    name = kernel.split(":")[0]
    assert re.search(rf"^\s*(ROOT )?%{name}\.\d+ = .* custom-call\(", text, re.M)


def test_jnp_scan_compiles_for_v5e(one_chip):
    """The whole pure-jnp event scan at the paper's fleet, 64 replicas."""
    cfg = SimConfig(num_gpus=100, seed=0)
    events, _, ring_rows, ring_cols = batched.presample_arrivals(cfg, R)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        events,
    )
    compiled = _compile(
        lambda ev: batched._simulate(
            ev, policy="mfi", metric="blocked", num_gpus=100,
            ring_rows=ring_rows, ring_cols=ring_cols, use_kernel=False,
        ),
        shapes,
    )
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30  # fits one v5e chip's HBM


def test_sharded_kernel_scan_compiles_for_four_chips(topo, monkeypatch):
    """The replica-sharded scan with Mosaic kernels on a 2x2 host.

    XLA cannot partition a Mosaic kernel, so ``_simulate`` maps the scan
    over the replica mesh; without the mesh the compiler refuses.  The
    engine picks Mosaic from the backend, which is the CPU here, so the
    test steers the kernels to Mosaic itself; the kernels' jit caches are
    keyed on ``interpret=None``, so they are cleared on both sides of the
    steering.
    """
    jax.clear_caches()
    monkeypatch.setattr(fragscore, "interpret_mode", lambda interpret=None: False)
    try:
        _compile_sharded(topo)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _compile_sharded(topo):
    mesh = Mesh(
        np.array(topo.devices), (batched.REPLICAS,),
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    sharding = NamedSharding(mesh, PartitionSpec(None, batched.REPLICAS))
    prog = batched.batched_program(
        "mfi", SimConfig(num_gpus=100, seed=0), R, use_kernel=True
    )
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        prog.events,
    )
    text = batched._simulate.lower(
        shapes, mesh=mesh, **prog.kwargs
    ).compile().as_text()
    assert "tpu_custom_call" in text
    with pytest.raises(NotImplementedError, match="shard_map"):
        batched._simulate.lower(shapes, **prog.kwargs).compile()
