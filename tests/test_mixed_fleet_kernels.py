"""The whole steady scan on a mixed fleet through the kernel path.

On a mixed fleet the engine's select stage makes one ``select_from_base``
launch per device model (:func:`repro.sim.batched.make_select_fn`) and
rescores touched GPUs from the window counts (``_frag_from_base``, as on
every fleet).  Here the kernels run in interpret mode on the
CPU, over the benchmark's ``mixed100`` fleet and over a fleet whose
repeated model is split across entries (a model group's rows are then not
contiguous), and every event must equal the host reference's decision and
the pure-jnp scan's trace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mig
from repro.sim import SimConfig, batched, replay

FLEETS = {
    "mixed100": "a100-80:30,a100-40:30,h100-96:20,h100-80:20",
    "split": "a100-80:8,a100-40:8,a100-80:8",
}


def _trace(prog, use_kernel):
    kwargs = dict(prog.kwargs, use_kernel=use_kernel,
                  kernel_spec=prog.spec if use_kernel else None)
    core, _, _ = batched._build_core(
        policy=kwargs["policy"], metric=kwargs["metric"], num_gpus=kwargs["num_gpus"],
        use_kernel=use_kernel, kernel_spec=kwargs["kernel_spec"],
        midx=kwargs["midx"], tables=kwargs["tables"],
    )
    if use_kernel:  # the path under test: per-group select, base-derived rescoring
        assert core.select_fn is not None
    _, trace = batched._simulate(jax.tree.map(jnp.asarray, prog.events), **kwargs)
    return jax.device_get(trace)


@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_kernel_scan_matches_host_and_jnp(fleet):
    spec = mig.ClusterSpec.parse(FLEETS[fleet])
    cfg = SimConfig(cluster_spec=spec, offered_load=0.95, seed=11,
                    warmup_horizons=0, measure_horizons=1)
    prog = batched.batched_program("mfi", cfg, 2, use_kernel=True)
    groups = spec.model_groups()
    if fleet == "split":
        assert any(np.any(np.diff(rows) > 1) for _, rows in groups)
    got = _trace(prog, True)
    want = _trace(prog, False)
    for field in ("ok", "gpu", "aidx", "free_sum", "active", "frag"):
        np.testing.assert_array_equal(getattr(got, field), getattr(want, field), err_msg=field)

    events, meta = prog.events, prog.meta
    ok_ref, gpu_ref, anchor_ref = replay.host_decisions(
        events, meta, "mfi", cfg.num_gpus, spec=spec
    )
    ok = np.asarray(got.ok)
    assert ok.sum() > 0 and (~ok & (np.asarray(events.pid) >= 0)).sum() > 0
    np.testing.assert_array_equal(ok, ok_ref)
    np.testing.assert_array_equal(np.asarray(got.gpu)[ok], gpu_ref[ok])
    tables = batched.spec_tables(spec)
    anchors = np.asarray(tables.profile_anchors)[
        spec.model_index[np.asarray(got.gpu)], np.maximum(np.asarray(events.pid), 0),
        np.asarray(got.aidx),
    ]
    np.testing.assert_array_equal(anchors[ok], anchor_ref[ok])
