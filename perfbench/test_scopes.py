"""The readers of the program's ``rescore`` and ``dispatch`` scopes
(``scan.rescore.ms``, ``select.dispatch.ms``), on a synthetic trace with
known answers.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/test_scopes.py
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.lib import program  # noqa: E402
from perfbench.lib import trace as tracelib  # noqa: E402
from perfbench.run import Context, read_metric  # noqa: E402
from perfbench.test_trace import _xspace  # noqa: E402

READERS = ("scan.rescore.ms", "select.dispatch.ms")


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """One call [100, 1100) on the trace's clock.  The device runs a while
    op [200, 900) that holds a rescoring fusion [200, 300), a dispatch
    gather [300, 340), the select kernel under dispatch [340, 540), the merge
    under dispatch [540, 560) and a commit fusion in no sub-scope
    [600, 650), and a ``fragscore`` kernel under rescore [700, 760)."""
    ops = [
        ("%while.1 = (s32[]) while(s32[])", 200, 700),
        ("%fusion.2 = f32[8] fusion(f32[8])", 200, 100),
        ("%gather.3 = f32[8] gather(f32[8])", 300, 40),
        ("%select_from_base.4 = f32[8] custom-call(f32[8])", 340, 200),
        ("%reduce.5 = f32[8] reduce(f32[8])", 540, 20),
        ("%fusion.6 = f32[8] fusion(f32[8])", 600, 50),
        ("%fragscore.7 = f32[8] custom-call(f32[8])", 700, 60),
    ]
    spans = [("perfbench.call", 100, 1000), ("perfbench.aggregate", 1000, 90)]
    tr = tracelib.load(_xspace(tmp_path, {"/device:TPU:0": ops}, spans))
    smap = {"while.1": "none", "fusion.2": "rescore", "gather.3": "dispatch",
            "select_from_base.4": "dispatch", "reduce.5": "dispatch", "fusion.6": "none",
            "fragscore.7": "rescore"}
    compiled = []
    fake = SimpleNamespace(calls=lambda: [],
                           scope_map=lambda prog: compiled.append(prog) or smap)
    monkeypatch.setattr(program, "obs", lambda: fake)
    ctx = Context(cell=None, make_cfg=None, batched=None,
                  captured=[("prog", None, None)], trace=tr)
    ctx.compiled = compiled
    return ctx


def test_readers_split_scopes_and_leave_kernels_out_of_dispatch(traced):
    got = {name: read_metric(name, traced) for name in READERS}
    assert got["scan.rescore.ms"] == pytest.approx((100 + 60) * 1e-6)
    assert got["select.dispatch.ms"] == pytest.approx((40 + 20) * 1e-6)
    assert traced.compiled == ["prog"]  # one scope map for both readers


def test_scopes_of_two_devices_read_the_worst(tmp_path, monkeypatch, traced):
    ops = {"/device:TPU:0": [("%fusion.2 = f32[8] fusion(f32[8])", 200, 100)],
           "/device:TPU:1": [("%fusion.2 = f32[8] fusion(f32[8])", 200, 300)]}
    tr = tracelib.load(_xspace(tmp_path, ops, [("perfbench.call", 100, 1000)]))
    ctx = Context(cell=None, make_cfg=None, batched=None,
                  captured=[("prog", None, None)], trace=tr)
    assert read_metric("scan.rescore.ms", ctx) == pytest.approx(300e-6)
    assert read_metric("select.dispatch.ms", ctx) is None


def test_program_without_a_scope_map_reads_nothing(traced, monkeypatch):
    monkeypatch.setattr(program, "obs", lambda: SimpleNamespace(calls=lambda: []))
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[("prog", None, None)],
                  trace=traced.trace)
    assert all(read_metric(name, ctx) is None for name in READERS)


def test_no_trace_reads_nothing(traced):
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=None)
    assert all(read_metric(name, ctx) is None for name in READERS)
