"""Benchmark of the batched Monte-Carlo engine on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of a cell of ``BENCHMARK.json``.  Set-up turns on JAX's persistent
compilation cache at a fixed path in the checkout, checks the harness's
copy of the load arithmetic and of the device tables against the
program's, maps the run's seed to the program's
(:func:`perfbench.lib.stream.program_seed`: every seed of a mix gets the
same stream shape; the search is harness work and is not counted in
``setup_s``) and makes one whole warm-up call.  The window then repeats
whole calls of the user's entry,
``repro.api.simulate(policy, cfg, engine="batched", runs=R, **mix keys)``, on that
seed with a fresh ``SimConfig`` each time, until ``--seconds`` have
passed; the call in flight is finished.  Once the window has closed the
harness draws the same stream itself, replays it through its own host
reference (``lib/reference.py`` with the policy's and the protocol's files
of ``reference/``) and holds every call's stream, per-event decisions and
returned numbers against it (``lib/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs a
window of :data:`TRACED_CALLS` whole call under the JAX profiler and
reports the cell's per-layer metrics, each read by ``metrics/<name>.py``.
The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error and
the last key of that object.  Without a TPU (or with another number of
chips than the cell asks for) the run exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench.lib import cell as cells  # noqa: E402
from perfbench.lib import check, device, stream  # noqa: E402
from perfbench.lib import trace as tracelib  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".perfbench_cache" / "jax"
#: whole calls a traced window holds at most: a call's trace holds every op of
#: every scan step (0.6M to 1.7M ops per chip), and reading one takes a minute
TRACED_CALLS = 1
#: the event JAX records once per executable it builds (compiled or fetched)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Probe:
    """Wraps the program's ``BatchedProgram.aggregate`` (the reduction that
    ``run_batched`` applies to the fetched per-event trace) to keep each
    call's program and trace, and puts harness spans around it and around
    ``presample_arrivals``.  :meth:`close` restores both."""

    def __init__(self, batched):
        import jax

        self.batched = batched
        self.calls: List[tuple] = []  # (program, trace, returned)
        self._agg = batched.BatchedProgram.aggregate
        self._pre = batched.presample_arrivals
        probe, agg, pre = self, self._agg, self._pre

        def aggregate(prog, trace):
            with jax.profiler.TraceAnnotation(tracelib.SPAN_PREFIX + "aggregate"):
                out = agg(prog, trace)
            probe.calls.append((prog, trace, out))
            return out

        def presample_arrivals(*a, **kw):
            with jax.profiler.TraceAnnotation(tracelib.SPAN_PREFIX + "presample"):
                return pre(*a, **kw)

        batched.BatchedProgram.aggregate = aggregate
        batched.presample_arrivals = presample_arrivals

    def close(self) -> None:
        self.batched.BatchedProgram.aggregate = self._agg
        self.batched.presample_arrivals = self._pre


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: cells.Cell
    make_cfg: Callable
    batched: object                  # the program's ``repro.sim.batched``
    captured: List[tuple]            # (program, trace, returned) per call
    trace: Optional[tracelib.Trace]  # the profiler trace of the window


def replay(cell: cells.Cell, seed: int, dtype=None):
    """The stream of program seed ``seed`` as the harness draws it, the
    reference's per-event decisions on it (sums in ``dtype``, float64 by
    default), and their aggregates: ``(stream, decisions, aggregates)``."""
    import numpy as np

    from perfbench.lib import reference

    if cell.sim["metric"] != "blocked":
        raise SystemExit(f"perfbench: no reference for the metric {cell.sim['metric']!r}")
    fault = stream.Fault(**cell.fault) if cell.fault else None
    s = stream.presample(cell.fleet, cell.sim, cell.replicas, seed, fault)
    ref, agg = reference.replay(s, cell.fleet, cell.policy, cell.sim, fault,
                                np.float64 if dtype is None else dtype)
    return s, ref, agg


def reference_numbers(cell: cells.Cell, seed: int, probe: Probe):
    """Hold every call the probe kept against the reference on program seed
    ``seed``.  Returns the numbers of :mod:`perfbench.lib.check` and the
    reference's aggregates."""
    s, ref, ref_agg = replay(cell, seed)
    calls = [(p.events, p.meta, tr, out) for p, tr, out in probe.calls]
    return check.compare(calls, s, ref, ref_agg, cell.fleet.num_gpus), ref_agg


def control_numbers(cell: cells.Cell, seed: int):
    """The control: the reference with its fragmentation sums in bfloat16,
    the precision below the float32 the configuration states, put in the
    program's place and held against the float64 reference."""
    from types import SimpleNamespace

    import ml_dtypes

    seed = stream.program_seed(cell.fleet, cell.sim, cell.replicas, seed, cell.shape)
    s, ctl, ctl_agg = replay(cell, seed, ml_dtypes.bfloat16)
    _, ref, ref_agg = replay(cell, seed)
    meta = SimpleNamespace(slot=s.slot, end=s.end)
    return check.compare([(s, meta, SimpleNamespace(**ctl), ctl_agg)], s, ref, ref_agg,
                         cell.fleet.num_gpus)


def _tuples(x):
    """JSON lists as the tuples a frozen configuration object holds."""
    return tuple(_tuples(v) for v in x) if isinstance(x, list) else x


def program(cell: cells.Cell):
    """Import the system under test and check the harness's load arithmetic,
    demand mix and device tables against it: a difference would mean the
    traffic or the hardware itself changed."""
    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))
    from repro import api
    from repro.core.mig import ClusterSpec, FaultModel
    from repro.sim import SimConfig, batched, simulator

    spec = ClusterSpec.parse(cell.fleet.spec_text())

    def make_cfg(seed):
        fm = FaultModel(**{k: _tuples(v) for k, v in cell.fault.items()}) if cell.fault else None
        return SimConfig(**cell.sim, cluster_spec=spec, seed=seed, fault_model=fm)

    fleet, cfg = cell.fleet, make_cfg(0)
    differs = []
    mine = stream.steady_params(fleet, cell.sim)
    if mine != tuple(simulator.steady_params(cfg)):
        differs.append(f"load {mine} vs {tuple(simulator.steady_params(cfg))}")
    probs = fleet.probs(cell.sim["distribution"], cell.sim.get("model_distributions"))
    if not np.array_equal(probs, simulator.request_probs(cfg)):
        differs.append(f"demand mix {probs} vs {simulator.request_probs(cfg)}")
    for k, name in enumerate(fleet.models):
        model = [m for m in spec.models if m.name == name]
        theirs = model and (model[0].num_mem_slices,
                            [(p.mem, tuple(p.anchors)) for p in model[0].profiles])
        ours = (int(fleet.slices[k]), [(int(fleet.mem[k, p]), fleet.anchors[k][p])
                                       for p in range(fleet.num_classes)])
        if theirs != ours:
            differs.append(f"device {name}: {ours} vs {theirs}")
    if differs:
        raise SystemExit("perfbench: harness and program differ: " + "; ".join(differs))
    return api, batched, make_cfg


def window(call: Callable, seconds: float, max_calls: Optional[int] = None) -> List[tuple]:
    """Whole calls until ``seconds`` have passed (or ``max_calls`` are made);
    ``(start, end)`` of each."""
    spans = []
    t0 = time.perf_counter()
    while True:
        s = time.perf_counter()
        call()
        e = time.perf_counter()
        spans.append((s, e))
        if e - t0 >= seconds or len(spans) == max_calls:
            return spans


def read_metric(name: str, ctx: Context):
    return cells.module("metrics", name).read(ctx)


def breakdown(tr: tracelib.Trace) -> dict:
    """The device ops that took most time, less the ops nested in them
    (seconds in the window, averaged over devices), and the longest idle
    gaps, by what the host was doing."""
    lo, hi = tr.window()
    totals = {}
    for ops in tr.devices.values():
        for k, v in tracelib.self_times(ops, lo, hi).items():
            totals[k] = totals.get(k, 0.0) + v * 1e-9 / len(tr.devices)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((e - s, s, e, ops) for ops in tr.devices.values()
                   for s, e in tracelib.gaps(ops, lo, hi)), key=lambda g: -g[0])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[tracelib.gap_label(tr, ops, s, e), n * 1e-9] for n, s, e, ops in idle]}


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, for every program
    however small.  No size limit: the limit's LRU bookkeeping (an access
    time file per entry) failed to write on the chip's host."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result line."""
    import jax

    if require_tpu:
        device.require(cell.chips)
    enable_cache()
    compiles = [0]

    def on_event(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    api, batched, make_cfg = program(cell)
    t0 = time.perf_counter()
    seed = stream.program_seed(cell.fleet, cell.sim, cell.replicas, seed, cell.shape)
    search_s = time.perf_counter() - t0
    probe = Probe(batched)

    def call():
        with jax.profiler.TraceAnnotation(tracelib.CALL_SPAN):
            return api.simulate(cell.policy, make_cfg(seed), engine="batched",
                                runs=cell.replicas, **cell.simulate)

    call()  # warm-up: compiles (or fetches from the cache) every program
    # the seed's shape search is the harness's, not work a user's call does
    setup_s = time.perf_counter() - T_START - search_s
    probe.calls.clear()

    trace_dir = Path(tempfile.mkdtemp(prefix="perfbench-trace-")) if trace else None
    before = compiles[0]
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    spans = window(call, seconds, TRACED_CALLS if trace else None)
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles[0] - before
    probe.close()
    dev = device.record()
    elapsed = spans[-1][1] - spans[0][0]
    print(json.dumps({"program_seed": seed, "shape_search_s": search_s,
                      "window_calls": len(spans), "window_s": elapsed,
                      "compilations_in_window": in_window,
                      "peak_bytes_in_use": dev["memory_peak_bytes"]}), flush=True)

    numbers, ref_agg = reference_numbers(cell, seed, probe)
    if probe.calls:  # which returned number is off, should one be
        gaps = check.aggregate_gaps(probe.calls[-1][2], ref_agg)
        print("aggregate gaps " + json.dumps(gaps), file=sys.stderr)
    captured_all = len(probe.calls) == len(spans)
    correct = check.passed(numbers) and captured_all and in_window == 0
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    result = {"correct": correct, "attempted": len(spans),
              "failed": 0 if correct else len(spans), "metrics": metrics, "device": dev}
    if not trace:
        per_call = stream.arrivals_per_call(cell.fleet, cell.sim, cell.replicas)
        values = {"sim_arrivals_per_s": len(spans) * per_call / elapsed, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        xplane = tracelib.find_xplane(trace_dir)
        tr = tracelib.load(xplane) if xplane else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(cell, lambda: make_cfg(seed), batched, probe.calls, tr)
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        if tr is not None and tr.devices and tr.window():
            lo, hi = tr.window()
            busy = [tracelib.busy(ops, lo, hi) for ops in tr.devices.values()]
            dev["busy_s"] = sum(busy) / len(busy) * 1e-9
            dev["window_s"] = (hi - lo) * 1e-9
            result["breakdown"] = breakdown(tr)
    checks = {k: {"value": v, "limit": check.LIMITS[k]} for k, v in numbers.items()}
    checks["calls_checked"] = {"value": len(probe.calls), "limit": len(spans)}
    checks["compilations_in_window"] = {"value": in_window, "limit": 0}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    result = run(cell, args.seed % 2**63, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
