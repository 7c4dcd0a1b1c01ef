"""The readers of the program's own spans, counters and stage map, on
synthetic traces and records with known answers.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/test_program.py
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

#: the trace's clock runs this far ahead of the program's perf_counter_ns
OFFSET = 1_000_000

NEW = ("stage.expire.ms", "stage.select.ms", "stage.commit.ms", "stage.measure.ms",
       "stage.migrate.ms", "stage.fault.ms", "stage.queue.ms", "scan.unstaged.ms",
       "host.transfer_s", "host.fetch_s", "device.idle_unattributed_share",
       "scan.non_arrival_share")


def _span(name, start, end, parent):
    return SimpleNamespace(name=name, start_ns=start, end_ns=end, parent=parent,
                           seconds=(end - start) * 1e-9)


def _call(spans, counters):
    spans = [_span(*s) for s in spans]
    return SimpleNamespace(root=spans[0], spans=spans, counters=counters,
                           find=lambda n: [s for s in spans if s.name == n])


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """One call [100, 1100) on the trace's clock.  The device runs a while
    op [300, 800) holding two stage ops, and one op [900, 950) outside it.
    The program's spans (program clock = trace clock - OFFSET): presample
    [110, 250) (the harness's presample starts 10 ns later, after
    validation), transfer [250, 280), dispatch [280, 290), scan [290, 820),
    fetch [820, 880), aggregate [1000, 1090).  The device idles over
    [100, 300), [800, 900) and [950, 1100); no phase span is open over
    [100, 110), [880, 900), [950, 1000) and [1090, 1100): 90 ns of 1000."""
    ops = [
        ("%while.1 = (s32[]) while(s32[])", 300, 500),
        ("%fusion.2 = f32[8] fusion(f32[8])", 300, 200),
        ("%select_from_base.3 = f32[8] custom-call(f32[8])", 550, 150),
        ("%copy.4 = f32[8] copy(f32[8])", 900, 50),
    ]
    spans = [("perfbench.call", 100, 1000), ("perfbench.presample", 120, 120),
             ("perfbench.aggregate", 1000, 90)]
    tr = tracelib.load(_xspace(tmp_path, {"/device:TPU:0": ops}, spans))
    p = -OFFSET
    warm = _call([("run_batched", 0, 50, None)], {"lanes": 1, "arrival_lanes": 1})
    call = _call([
        ("run_batched", 100 + p, 1100 + p, None),
        ("presample", 110 + p, 250 + p, 0),
        ("transfer", 250 + p, 280 + p, 0),
        ("dispatch", 280 + p, 290 + p, 0),
        ("scan", 290 + p, 820 + p, 0),
        ("fetch", 820 + p, 880 + p, 0),
        ("aggregate", 1000 + p, 1090 + p, 0),
    ], {"lanes": 200, "arrival_lanes": 172, "heartbeat_lanes": 20, "padding_lanes": 8})
    later = _call([("presample", 2000, 2100, None)], {})  # a reader's own call
    smap = {"while.1": "none", "fusion.2": "expire", "select_from_base.3": "select",
            "copy.4": "commit"}
    fake = SimpleNamespace(calls=lambda: [warm, call, later], stage_map=lambda prog: smap)
    monkeypatch.setattr(program, "obs", lambda: fake)
    return Context(cell=None, make_cfg=None, batched=None,
                   captured=[("prog", None, None)], trace=tr)


def test_traced_call_is_picked_by_its_root(traced):
    calls = program.traced_calls(traced)
    assert len(calls) == 1 and calls[0].counters["lanes"] == 200


def test_anchor_places_spans_on_the_trace_clock(traced, capsys):
    off = program.offsets(traced)
    assert off["aggregate"] == OFFSET
    assert off["presample"] == OFFSET + 10  # the validation before presampling
    spans = dict((n, (s, e)) for n, s, e in program.phase_spans(traced))
    assert spans["scan"] == (290, 820)
    assert spans["aggregate"] == (1000, 1090)
    assert "disagree_ms" in capsys.readouterr().err


def test_stage_times_partition_self_time(traced):
    split = program.stages(traced)
    tr = traced.trace
    ops = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    assert split == pytest.approx({"none": 150e-6, "expire": 200e-6, "select": 150e-6,
                                   "commit": 50e-6})
    total = sum(tracelib.self_times(ops, lo, hi).values()) * 1e-6
    assert sum(split.values()) == pytest.approx(total)
    assert total == pytest.approx(tracelib.busy(ops, lo, hi) * 1e-6)


def test_unmapped_ops_are_kept_apart(traced):
    tr = traced.trace
    split = program.stage_times(tr, {"fusion.2": "expire"}, 2)
    assert split["expire"] == pytest.approx(100e-6)
    assert split["unmapped"] == pytest.approx((150 + 150 + 50) / 2 * 1e-6)


def test_new_readers(traced):
    got = {name: read_metric(name, traced) for name in NEW}
    assert got["stage.expire.ms"] == pytest.approx(200e-6)
    assert got["stage.select.ms"] == pytest.approx(150e-6)
    assert got["stage.commit.ms"] == pytest.approx(50e-6)
    assert got["scan.unstaged.ms"] == pytest.approx(150e-6)
    for stage in ("measure", "migrate", "fault", "queue"):
        assert got[f"stage.{stage}.ms"] is None
    assert got["host.transfer_s"] == pytest.approx(30e-9)
    assert got["host.fetch_s"] == pytest.approx(60e-9)
    assert got["device.idle_unattributed_share"] == pytest.approx(100 * 90 / 1000)
    assert got["scan.non_arrival_share"] == pytest.approx(100 * (1 - 172 / 200))


def test_program_without_a_record_reads_nothing(traced, monkeypatch):
    monkeypatch.setattr(program, "obs", lambda: None)
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[("prog", None, None)],
                  trace=traced.trace)
    assert all(read_metric(name, ctx) is None for name in NEW)


def test_no_trace_reads_nothing(traced):
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=None)
    assert all(read_metric(name, ctx) is None for name in NEW)
