"""The trace reduction on small traces with known answers.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/test_trace.py
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.lib import trace as tracelib  # noqa: E402
from perfbench.run import breakdown  # noqa: E402


def _event(meta, start_ns, dur_ns, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} {stats} }}")


def _xspace(tmp_path, device_ops, host_spans):
    """An ``.xplane.pb`` with one ``XLA Ops`` line per device and one host line.
    ``device_ops``: {plane: [(name, start, dur)]}; ``host_spans``:
    [(name, start, dur)]; times in ns."""
    from jax.profiler import ProfileData

    planes = []
    for pid, (plane, ops) in enumerate(device_ops.items(), start=1):
        names = sorted({o[0] for o in ops})
        ids = {n: i + 1 for i, n in enumerate(names)}
        events = " ".join(_event(ids[n], s, d) for n, s, d in ops)
        metas = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                         for n, i in ids.items())
        planes.append(
            f'planes {{ id: {pid} name: "{plane}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_event(1, 0, 10**6)} }} '
            f'{metas} }}')
    names = sorted({n for n, _, _ in host_spans} | {"other"})
    ids = {n: i + 1 for i, n in enumerate(names)}
    events = " ".join(_event(ids[n], s, d) for n, s, d in host_spans + [("other", 0, 5)])
    metas = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for n, i in ids.items())
    planes.append(f'planes {{ id: 99 name: "/host:CPU" '
                  f'lines {{ id: 1 name: "python" timestamp_ns: 0 {events} }} {metas} }}')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
    return path


@pytest.fixture
def two_calls(tmp_path):
    # two calls [100, 600) and [700, 1200); the first call's ops nest in a
    # while op, two ops of the second overlap, and one runs past its end
    sel = "%vmap_jit_select_from_base__.8 = f32[2,1,1,6] custom-call(f32[2,16,8])"
    mig = "%vmap_jit_migrate_refine__.20 = f32[2,1,6,12] custom-call(f32[2,16,8])"
    fus = "%fusion.7 = f32[8] fusion(f32[8])"
    ops = [
        ("%while.3 = (s32[]) while(s32[])", 150, 300),  # [150, 450), holds:
        (sel, 150, 100),                                # [150, 250)
        (fus, 250, 100),                                # [250, 350)
        (sel, 400, 50),                                 # [400, 450)
        (fus, 750, 100),                                # [750, 850)
        (mig, 800, 100),                                # [800, 900)
        (fus, 1000, 300),                               # [1000, 1300)
    ]
    spans = [("perfbench.call", 100, 500), ("perfbench.presample", 110, 30),
             ("perfbench.call", 700, 500), ("perfbench.aggregate", 1150, 40)]
    return tracelib.load(_xspace(tmp_path, {"/device:TPU:0": ops}, spans))


def test_load_keeps_ops_and_harness_spans(two_calls):
    tr = two_calls
    assert list(tr.devices) == ["/device:TPU:0"]
    assert len(tr.devices["/device:TPU:0"]) == 7  # the XLA Modules line is not an op
    assert tr.calls() == [(100, 600), (700, 1200)]
    assert tr.window() == (100, 1200)
    assert {n for n, _, _ in tr.spans} == {"perfbench.call", "perfbench.presample",
                                          "perfbench.aggregate"}


def test_busy_is_the_union_clipped_to_the_window(two_calls):
    ops = two_calls.devices["/device:TPU:0"]
    # [150, 450) + [750, 900) + [1000, 1200) = 300 + 150 + 200
    assert tracelib.busy(ops, 100, 1200) == 650
    assert tracelib.busy(ops, 100, 600) == 300
    assert tracelib.busy(ops, 700, 1200) == 350
    assert tracelib.gaps(ops, 100, 1200) == [(100, 150), (450, 750), (900, 1000)]


def test_idle_share_reader(two_calls):
    from perfbench.run import Context, read_metric

    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=two_calls)
    assert read_metric("device.idle_share", ctx) == pytest.approx(100 * (1 - 650 / 1100))
    assert read_metric("scan.device_s", ctx) == pytest.approx((300 + 350) / 2 * 1e-9)


def test_kernel_time_by_name_pattern(two_calls):
    from perfbench.run import Context, read_metric

    ops = two_calls.devices["/device:TPU:0"]
    assert tracelib.op_time(ops, "select_from_base", 100, 1200) == 150
    assert tracelib.op_time(ops, "migrate_refine", 100, 1200) == 100
    assert tracelib.op_time(ops, "no_such_kernel", 100, 1200) is None
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=two_calls)
    assert read_metric("kernel.select.ms", ctx) == pytest.approx(150 / 2 * 1e-6)
    assert read_metric("kernel.migrate.ms", ctx) == pytest.approx(100 / 2 * 1e-6)


def test_no_device_reads_nothing(tmp_path):
    from perfbench.run import Context, read_metric

    tr = tracelib.load(_xspace(tmp_path, {}, [("perfbench.call", 0, 100)]))
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=tr)
    for name in ("device.idle_share", "scan.device_s", "kernel.select.ms", "kernel.migrate.ms"):
        assert read_metric(name, ctx) is None


def test_four_devices_report_the_worst(tmp_path):
    from perfbench.run import Context, read_metric

    ops = {f"/device:TPU:{d}": [("%fusion.1 = f32[8]", 0, 100 * (d + 1))] for d in range(4)}
    tr = tracelib.load(_xspace(tmp_path, ops, [("perfbench.call", 0, 1000)]))
    ctx = Context(cell=None, make_cfg=None, batched=None, captured=[], trace=tr)
    assert read_metric("device.idle_share", ctx) == pytest.approx(90.0)
    assert read_metric("scan.device_s", ctx) == pytest.approx(400e-9)


def test_breakdown_self_time_and_gap_labels(two_calls):
    b = breakdown(two_calls)
    # the while op keeps only what its nested ops leave: 300 - 100 - 100 - 50
    assert b["device_ops"] == [["fusion.7", pytest.approx(500e-9)],
                               ["vmap_jit_select_from_base__.8", pytest.approx(150e-9)],
                               ["vmap_jit_migrate_refine__.20", pytest.approx(100e-9)],
                               ["while.3", pytest.approx(50e-9)]]
    assert b["idle_gaps"] == [["between calls", pytest.approx(300e-9)],
                              ["call, between device ops", pytest.approx(100e-9)],
                              ["presample", pytest.approx(50e-9)]]


def test_gap_labels_before_and_after_the_device(tmp_path):
    ops = [("%while.1 = (s32[]) while(s32[])", 300, 400)]
    tr = tracelib.load(_xspace(tmp_path, {"/device:TPU:0": ops},
                               [("perfbench.call", 100, 800)]))
    b = breakdown(tr)
    assert b["idle_gaps"] == [["call, before its device ops", pytest.approx(200e-9)],
                              ["call, after its device ops", pytest.approx(200e-9)]]


#: recorded on one TPU v5e chip: ``api.simulate("mfi", engine="batched",
#: runs=2)`` on 16 GPUs, one warm-up horizon and one measured (147 events
#: per replica), two calls in ``perfbench.call`` spans
RECORDED = Path(__file__).resolve().parent / "testdata" / "engine_small.xplane.pb.gz"


def _sweep_union(intervals):
    """Covered length by counting open intervals at each endpoint."""
    points = sorted([(s, 1) for s, e in intervals] + [(e, -1) for s, e in intervals])
    covered, depth, last = 0.0, 0, None
    for t, d in points:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    return covered


def test_recorded_tpu_trace(tmp_path):
    import gzip

    path = tmp_path / "recorded.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    tr = tracelib.load(path)
    assert list(tr.devices) == ["/device:TPU:0"]
    assert len(tr.calls()) == 2
    ops = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    clipped = [(max(o.start, lo), min(o.end, hi)) for o in ops if o.end > lo and o.start < hi]
    busy = tracelib.busy(ops, lo, hi)
    assert busy == pytest.approx(_sweep_union(clipped), rel=1e-12)
    assert 0 < busy < hi - lo
    # the fused select kernel runs once per event step: 147 steps, 2 calls
    sel = [o for o in ops if "select_from_base" in o.name]
    assert len(sel) == 2 * 147
    assert tracelib.op_time(ops, "select_from_base", lo, hi) == sum(o.dur for o in sel)
    # each lies inside the scan's while op, which the self times subtract
    whiles = [o for o in ops if tracelib.short(o.name).startswith("while")]
    assert all(any(w.start <= o.start and o.end <= w.end for w in whiles) for o in sel)
    times = tracelib.self_times(ops, lo, hi)
    assert sum(times.values()) <= busy * (1 + 1e-9)
