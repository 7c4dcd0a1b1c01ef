"""The engine's spans, counters and stage map (:mod:`repro.obs`), on the CPU
at tiny sizes."""

import json
import subprocess
import sys
import textwrap

import pytest

from repro import obs
from repro.core.mig import FaultModel
from repro.sim import SimConfig, batched

#: (policy, SimConfig keywords) per protocol, and the stages each scopes
PROTOCOLS = {
    "steady": ("mfi", {}, {"measure", "expire", "select", "commit"}),
    "faulted": (
        "mfi",
        dict(protocol="steady-faulted", offered_load=1.1,
             fault_model=FaultModel(mtbf=20.0, mttr=5.0)),
        {"measure", "expire", "fault", "queue", "select", "commit"},
    ),
    "defrag": ("mfi-defrag", {}, {"measure", "expire", "select", "migrate", "commit"}),
}


def _program(kind, runs=2):
    policy, kw, _ = PROTOCOLS[kind]
    cfg = SimConfig(num_gpus=16, seed=5, warmup_horizons=1, measure_horizons=1, **kw)
    return batched.batched_program(policy, cfg, runs)


def _scan_instructions(text):
    """Instruction names of the entry and of the scan's loop body: the
    computations whose ops a profile shows (fused computations are not)."""
    fused = set()
    for line in text.splitlines():
        if " fusion(" in line and "calls=" in line:
            fused.add(line.split("calls=")[1].split(",")[0].strip().lstrip("%"))
    names, keep = [], False
    for line in text.splitlines():
        head = obs._COMPUTATION.match(line)
        if head is not None and obs._INSTRUCTION.match(line) is None:
            keep = head.group(1) not in fused
            continue
        m = obs._INSTRUCTION.match(line)
        if m is not None and keep:
            names.append(m.group(2))
    return names


@pytest.mark.parametrize("kind", sorted(PROTOCOLS))
def test_stage_map_covers_the_scan(kind):
    prog = _program(kind)
    text = prog.lower().compile().as_text()
    smap = obs.stages_of_text(text)
    assert smap == obs.stage_map(prog)
    want = PROTOCOLS[kind][2]
    assert want <= set(smap.values())
    assert set(smap.values()) <= set(obs.STAGES) | {obs.UNSTAGED}
    if "migrate" not in want:
        assert "migrate" not in smap.values()
    names = _scan_instructions(text)
    assert names and set(names) <= set(smap)
    whiles = [n for n in names if n.startswith("while")]
    assert whiles and all(smap[n] == obs.UNSTAGED for n in whiles)


def test_stages_of_text_reads_fusion_roots():
    text = textwrap.dedent('''\
        HloModule m, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

        %fused_computation.1 (p.1: f32[4]) -> f32[4] {
          %p.1 = f32[4]{0} parameter(0)
          %neg.1 = f32[4]{0} negate(%p.1), metadata={op_name="jit(f)/vmap(stage.expire)/neg"}
          ROOT %add.1 = f32[4]{0} add(%neg.1, %p.1), metadata={op_name="jit(f)/while/body/vmap(stage.commit)/add"}
        }

        %fused_computation.2 (p.2: f32[4]) -> (f32[4], f32[4]) {
          %p.2 = f32[4]{0} parameter(0)
          ROOT %tuple.2 = (f32[4]{0}, f32[4]{0}) tuple(%p.2, %p.2)
        }

        %fused_computation.3 (p.3: f32[4]) -> f32[4] {
          %p.3 = f32[4]{0} parameter(0)
          %neg.3 = f32[4]{0} negate(%p.3), metadata={op_name="jit(f)/vmap(stage.commit)/neg"}
          %abs.3 = f32[4]{0} abs(%neg.3), metadata={op_name="jit(f)/vmap(stage.expire)/abs"}
          ROOT %scatter.3 = f32[4]{0} add(%abs.3, %p.3)
        }

        ENTRY %main.9 (a.1: f32[4]) -> f32[4] {
          %a.1 = f32[4]{0} parameter(0)
          %fusion.1 = f32[4]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation.1
          %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%a.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/stage.select/stage.queue/x"}
          %fusion.3 = f32[4]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/x"}
          %copy.3 = f32[4]{0} copy(%fusion.1)
          ROOT %select_from_base.4 = f32[4]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/vmap(stage.select)/pallas_call"}
        }
        ''')
    smap = obs.stages_of_text(text)
    assert smap["fusion.1"] == "commit"       # its root's op_name
    assert smap["fusion.2"] == "queue"        # no fused op names one: its own, innermost
    assert smap["fusion.3"] == "expire"       # root names none: the last fused op that does
    assert smap["copy.3"] == obs.UNSTAGED
    assert smap["select_from_base.4"] == "select"
    assert smap["a.1"] == obs.UNSTAGED


def test_monolithic_record_spans_and_counters():
    cfg = SimConfig(num_gpus=16, seed=5, warmup_horizons=1, measure_horizons=1)
    batched.run_batched("mfi", cfg, runs=2)
    call = obs.calls()[-1]
    assert call.root.name == "run_batched" and call.root.parent is None
    kids = [s.name for s in call.spans if s.parent == 0]
    assert kids == ["presample", "transfer", "dispatch", "scan", "fetch", "aggregate"]
    assert all(s.call == call.id for s in call.spans)
    for s in call.spans[1:]:
        assert call.root.start_ns <= s.start_ns <= s.end_ns <= call.root.end_ns
    ends = [s.end_ns for s in call.spans[1:]]
    assert ends == sorted(ends)

    events, meta, _, _ = batched.presample_arrivals(cfg, 2)
    c = call.counters
    assert c["lanes"] == events.pid.size
    assert c["arrival_lanes"] == int((events.pid >= 0).sum())
    # per replica one sentinel, the rest of the tail padding to the longest
    total_slots = int(meta.slot.max())
    real = (meta.slot < total_slots).sum(axis=0)
    assert c["padding_lanes"] == int((events.pid.shape[0] - real).sum())
    assert c["lanes"] == c["arrival_lanes"] + c["heartbeat_lanes"] + c["padding_lanes"]
    assert c["heartbeat_lanes"] == int(((events.pid < 0) & (meta.slot < total_slots)).sum())
    assert c["h2d_bytes"] == sum(x.nbytes for x in events if x is not None)
    assert c["d2h_bytes"] > 0


def test_spans_open_calls_nest_and_stay_bounded():
    before = obs.calls()
    with obs.span("outer") as root:
        obs.count("things", 2)
        with obs.span("inner"):
            obs.count("things", 3)
    obs.count("dropped", 1)  # no call open: no record
    call = obs.calls()[-1]
    assert call.root is root and call not in before
    assert [(s.name, s.parent) for s in call.spans] == [("outer", None), ("inner", 0)]
    assert call.counters == {"things": 5}
    for k in range(obs.MAX_CALLS + 3):
        with obs.span(f"c{k}"):
            pass
    kept = obs.calls()
    assert len(kept) == obs.MAX_CALLS
    assert kept[-1].root.name == f"c{obs.MAX_CALLS + 2}"
    assert [c.id for c in kept] == sorted(c.id for c in kept)


def test_sharded_stage_map_is_the_launched_executable():
    """On four host devices ``run_batched`` splits the replicas over the
    mesh; the stage map then comes from the very executable it launched
    (no compile), and that executable is the ``shard_map``-ped one."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        )
        import json, sys
        sys.path.insert(0, "src")
        import jax
        from repro import obs
        from repro.sim import SimConfig, batched

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, s, **k: compiles.append(e)
            if e == "/jax/core/compile/backend_compile_duration" else None
        )
        cfg = SimConfig(num_gpus=16, seed=5, warmup_horizons=1, measure_horizons=1)
        batched.run_batched("mfi", cfg, runs=4)
        launched = len(compiles)
        prog = batched.batched_program("mfi", cfg, 4)
        smap = obs.stage_map(prog)
        events, kw = prog.launch()
        print(json.dumps({
            "devices": len(events.pid.sharding.device_set),
            "mesh": kw["mesh"] is not None,
            "compiles": len(compiles) - launched,
            "stages": sorted(set(smap.values())),
            "partitions": "num_partitions=4" in prog.lower().compile().as_text(),
        }))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4 and got["mesh"]
    assert got["compiles"] == 0
    assert {"measure", "expire", "select", "commit"} <= set(got["stages"])
    assert got["partitions"]

