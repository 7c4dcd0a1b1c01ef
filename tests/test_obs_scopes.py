"""The engine's ``rescore`` and ``dispatch`` scopes (:func:`repro.obs.scope_map`)
and its per-model dispatch counters, on the CPU at small sizes."""

import contextlib

import jax
import pytest

from repro import obs
from repro.core import mig
from repro.sim import SimConfig, batched

MIXED100 = mig.ClusterSpec.parse("a100-80:30,a100-40:30,h100-96:20,h100-80:20")
SMALL_MIXED = mig.ClusterSpec.parse("a100-80:4,a100-40:4,h100-96:3,h100-80:3")


def _program(spec, use_kernel, policy="mfi"):
    cfg = SimConfig(cluster_spec=spec, num_gpus=16, seed=5,
                    warmup_horizons=0, measure_horizons=1)
    return batched.batched_program(policy, cfg, 2, use_kernel=use_kernel)


class _Unscoped(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _text(prog, scoped=True):
    """The compiled scan's text; ``scoped=False`` compiles it with the
    :data:`obs.SCOPES` scopes left out (the jit caches cleared on both
    sides, as a trace cached with the scopes would be reused)."""
    if scoped:
        return prog.lower().compile().as_text()
    real = jax.named_scope
    jax.clear_caches()
    jax.named_scope = lambda name: _Unscoped() if name in obs.SCOPES else real(name)
    try:
        return prog.lower().compile().as_text()
    finally:
        jax.named_scope = real
        jax.clear_caches()


@pytest.mark.parametrize("fleet", ["homogeneous", "mixed"])
def test_scope_map_finds_rescore_and_dispatch(fleet):
    """Rescoring (``_frag_from_base`` on either fleet) is scoped; the mixed
    fleet's per-group kernel launches are wrapped in ``dispatch``."""
    spec = None if fleet == "homogeneous" else SMALL_MIXED
    prog = _program(spec, use_kernel=fleet == "mixed")
    smap = obs.scopes_of_text(_text(prog))
    assert smap == obs.scope_map(prog)
    assert set(smap.values()) <= set(obs.SCOPES) | {obs.UNSTAGED}
    assert "rescore" in smap.values()
    assert ("dispatch" in smap.values()) == (fleet == "mixed")


@pytest.mark.parametrize("fleet", ["homogeneous", "mixed"])
def test_scopes_leave_the_stage_map_unchanged(fleet):
    """Every instruction keeps its stage with and without the sub-scopes,
    and no sub-scope is left in the program compiled without them."""
    prog = _program(None if fleet == "homogeneous" else SMALL_MIXED, use_kernel=True)
    scoped, plain = _text(prog), _text(prog, scoped=False)
    assert set(obs.scopes_of_text(plain).values()) == {obs.UNSTAGED}
    assert obs.stages_of_text(scoped) == obs.stages_of_text(plain)


def test_scope_of_reads_the_innermost_scope():
    assert obs._scope_of("jit(f)/while/body/vmap(stage.commit)/rescore/gather") == "rescore"
    assert obs._scope_of("jit(f)/vmap(stage.select)/vmap(dispatch)/rescore/x") == "rescore"
    assert obs._scope_of("jit(f)/vmap(stage.select)/dispatch/concatenate") == "dispatch"
    assert obs._scope_of("jit(f)/stage.dispatch/_rescore/rescored") == obs.UNSTAGED


@pytest.mark.parametrize("fleet, use_kernel, want", [
    ("paper", True, (1, 104)),
    ("mixed100", True, (4, 112)),
    ("mixed100", False, (0, 0)),
])
def test_dispatch_counters(fleet, use_kernel, want):
    """``model_groups`` / ``kernel_rows`` per call: one launch over 100 GPUs
    padded to 104 rows on the paper fleet, four over 30/30/20/20 padded to
    32/32/24/24 on ``mixed100``, none on the jnp lowering."""
    cfg = SimConfig(cluster_spec=None if fleet == "paper" else MIXED100, num_gpus=100,
                    seed=5, warmup_horizons=0, measure_horizons=1)
    with obs.span("run_batched"):
        batched.batched_program("mfi", cfg, 2, use_kernel=use_kernel)
    c = obs.calls()[-1].counters
    assert (c["model_groups"], c["kernel_rows"]) == want
