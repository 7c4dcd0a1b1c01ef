"""Smoke run of the batched Monte-Carlo engine on a TPU, at the paper's size.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: replica sharding only

The deployment is the paper's: 100 A100-80GB GPUs, the uniform Table-II
mix, 85% offered load, 500 replicas (``SimConfig`` defaults, ``runs=500``).
Every phase runs the engine's own program with the default ``use_kernel``
(``batched_program`` -> ``_simulate`` -> ``BatchedProgram.aggregate``, the
path of ``run_batched``), so on a TPU the Pallas kernels compile through
Mosaic.  Phases on one chip:

* ``mfi``, ``ff`` and ``rr`` on ``steady``; ``mfi`` on ``steady-queued``
  at load 1.1; ``mfi`` on ``steady-faulted`` (MTBF 60, MTTR 10, load 1.1)
  and ``mfi-defrag`` on ``steady`` at :data:`CUT_RUNS` replicas.
* Per-event decisions of the first :data:`PARITY_RUNS` replicas of every
  phase equal the host reference on the same presampled stream
  (``repro.sim.replay``).
* ``mfi`` and ``mfi-defrag`` contain ``tpu_custom_call`` and give the same
  aggregates through ``run_batched(use_kernel=False)``; a chunked
  ``run_batched`` of ``mfi`` equals the monolithic run.

``--chips 4`` runs ``run_batched`` of ``mfi`` with ``shard=True`` against
``shard=False`` on the same host (monolithic and chunked), asserts equal
aggregates, and asserts that the sharded event arrays span every device.

Aggregates compare bitwise, except ``frag_severity``: it averages each
event's ``sum(F) / M`` in float32, and the TPU's float32 division is not
bit-stable across programs, so it may differ in the last bits between two
programs whose decisions agree; it is held to :data:`FRAG_RTOL`.

Each phase prints one JSON record; the last line of standard output is
``{"ok": true, "device": {...}}``.  Any failed check raises, and the
script then exits non-zero without that line.  It refuses to run without
a TPU.  The timings are records of this run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

RUNS = 500          # the paper's replica count
NUM_GPUS = 100      # the paper's fleet
#: replicas of the faulted and mfi-defrag phases: at 500 replicas one run
#: takes 383 s (faulted) and 618 s (mfi-defrag) on a v5e chip, while
#: ``mfi`` takes 11 s
CUT_RUNS = 8
PARITY_RUNS = 8     # replicas checked event-by-event against the host
CHUNK_SIZE = 512    # events per chunk of the chunked run
#: ``frag_severity`` tolerance between programs (a few float32 ulps)
FRAG_RTOL = 1e-6


def _require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform!r}")


def _device_record():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _check(ok, *what):
    """A failed check ends the run (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _assert_same_aggregates(a, b, what):
    """Bitwise equality of every aggregate but ``frag_severity`` (see the
    module docstring).  Returns whether ``frag_severity`` was bitwise too."""
    _check(a.keys() == b.keys(), what, sorted(a.keys() ^ b.keys()))
    for k in a:
        if k == "frag_severity":
            _check(np.isclose(a[k], b[k], rtol=FRAG_RTOL, atol=0), what, k, a[k], b[k])
        else:
            _check(np.array_equal(np.asarray(a[k]), np.asarray(b[k])),
                   what, k, a[k], b[k])
    return bool(a.get("frag_severity") == b.get("frag_severity"))


def _first(tree, n):
    return jax.tree.map(lambda x: np.asarray(x)[:, :n], tree)


def _check_host_parity(prog, trace, policy, cfg, fault_model):
    """Per-event decisions of the first PARITY_RUNS replicas vs the host."""
    from repro.sim import batched, replay

    ev, meta, tr = (_first(x, PARITY_RUNS) for x in (prog.events, prog.meta, trace))
    eq = np.testing.assert_array_equal
    proto = prog.protocol
    if proto.faulted:
        ref = replay.faulted_host_decisions(
            ev, meta, policy, cfg.num_gpus, metric=cfg.metric,
            capacity=cfg.wait_capacity, patience=cfg.wait_patience,
            max_retries=fault_model.max_retries,
            backoff_base=fault_model.backoff_base,
        )
        names = ("ok", "parked", "wadm_eidx", "evicted", "evict_lost", "evict_esum")
        _check(ref.evicted.sum() > 0, "no evictions exercised")
    elif proto.queued:
        ref = replay.queued_host_decisions(
            ev, meta, policy, cfg.num_gpus, metric=cfg.metric,
            capacity=cfg.wait_capacity, patience=cfg.wait_patience,
        )
        names = ("ok", "parked", "wadm_eidx")
    else:
        ref = replay.host_decisions_full(
            ev, meta, policy, cfg.num_gpus, metric=cfg.metric,
            **({"max_candidates": None} if prog.kwargs["policy"].defrag else {}),
        )
        names = ("ok", "mig") if prog.kwargs["policy"].defrag else ("ok",)
    for name in names:
        eq(getattr(tr, name), getattr(ref, name), err_msg=name)
    ok = ref.ok
    eq(tr.gpu[ok], ref.gpu[ok], err_msg="gpu")
    # the device records anchor indexes, the host anchor values
    anchors = np.asarray(batched.spec_tables(prog.spec).profile_anchors[0])
    eq(anchors[ev.pid[ok], tr.aidx[ok]], ref.anchor[ok], err_msg="anchor")
    if proto.queued:
        adm = ref.wadm_eidx >= 0
        _check(adm.any(), "no wait-ring admissions exercised")
        eq(tr.wadm_gpu[adm], ref.wadm_gpu[adm], err_msg="wadm_gpu")
    if "mig" in names:
        m = ref.mig
        _check(m.any(), "no migrations exercised")
        for name in ("mig_from_gpu", "mig_from_anchor", "mig_to_gpu", "mig_to_anchor"):
            eq(getattr(tr, name)[m], getattr(ref, name)[m], err_msg=name)


def run_phase(name, policy, cfg, runs=None, fault_model=None, need_kernel=False):
    """Compile the engine's program, run it once, reduce it as ``run_batched``
    does, and check its decisions against the host.  Returns the
    aggregates."""
    from repro.sim import batched

    runs = runs or RUNS
    prog = batched.batched_program(policy, cfg, runs)
    events = jax.tree.map(jnp.asarray, prog.events)
    t0 = time.perf_counter()
    compiled = batched._simulate.lower(events, **prog.kwargs).compile()
    compile_s = time.perf_counter() - t0
    kernels = "tpu_custom_call" in compiled.as_text()
    _check(kernels or not need_kernel, name, "no Pallas kernel in the program")

    t0 = time.perf_counter()
    _, trace = jax.device_get(compiled(
        events, midx=prog.kwargs["midx"], tables=prog.kwargs["tables"]
    ))
    warm_s = time.perf_counter() - t0
    del events
    agg = prog.aggregate(trace)
    t0 = time.perf_counter()
    _check_host_parity(prog, trace, policy, cfg, fault_model)
    host_ref_s = time.perf_counter() - t0
    print(json.dumps({
        "phase": name, "runs": runs, "events": int(prog.events.pid.shape[0]),
        "use_kernel": prog.kwargs["use_kernel"], "tpu_custom_call": kernels,
        "compile_s": compile_s, "warm_s": warm_s,
        "acceptance_rate": float(agg["acceptance_rate"]),
        "host_parity_replicas": PARITY_RUNS, "host_ref_s": host_ref_s,
        "peak_bytes_in_use": _peak_bytes(),
    }), flush=True)
    return agg


def timed_run(name, policy, cfg, same_as=None, runs=None, **kw):
    """One ``run_batched`` call, compile included; its aggregates must
    equal ``same_as`` where given."""
    from repro.sim.batched import run_batched

    runs = runs or RUNS
    t0 = time.perf_counter()
    agg = run_batched(policy, cfg, runs=runs, **kw)
    record = {
        "phase": name, "runs": runs, "first_call_s": time.perf_counter() - t0,
        "acceptance_rate": float(agg["acceptance_rate"]),
        "peak_bytes_in_use": _peak_bytes(), **kw,
    }
    if same_as is not None:
        record["frag_bitwise"] = _assert_same_aggregates(same_as, agg, name)
    print(json.dumps(record), flush=True)
    return agg


def one_chip():
    from repro.core.mig import FaultModel
    from repro.sim import SimConfig

    paper = SimConfig(num_gpus=NUM_GPUS, offered_load=0.85)
    mfi = run_phase("mfi@steady", "mfi", paper, need_kernel=True)
    for policy in ("ff", "rr"):
        run_phase(f"{policy}@steady", policy, paper)
    run_phase(
        "mfi@steady-queued", "mfi",
        SimConfig(num_gpus=NUM_GPUS, offered_load=1.1, protocol="steady-queued"),
    )
    fm = FaultModel(mtbf=60.0, mttr=10.0)
    run_phase(
        "mfi@steady-faulted", "mfi",
        SimConfig(num_gpus=NUM_GPUS, offered_load=1.1,
                  protocol="steady-faulted", fault_model=fm),
        runs=CUT_RUNS, fault_model=fm,
    )
    timed_run("mfi@steady jnp", "mfi", paper, same_as=mfi, use_kernel=False)
    timed_run("mfi@steady chunked", "mfi", paper, same_as=mfi,
              chunk_size=CHUNK_SIZE)
    defrag = run_phase("mfi-defrag@steady", "mfi-defrag", paper,
                       runs=CUT_RUNS, need_kernel=True)
    timed_run("mfi-defrag@steady jnp", "mfi-defrag", paper, same_as=defrag,
              runs=CUT_RUNS, use_kernel=False)


def four_chips():
    from repro.sim import SimConfig, batched

    n = len(jax.devices())
    _check(n == 4, "--chips 4 needs four devices", n)
    paper = SimConfig(num_gpus=NUM_GPUS, offered_load=0.85)
    prog = batched.batched_program("mfi", paper, RUNS)
    placed = batched.shard_events(jax.tree.map(jnp.asarray, prog.events), RUNS, True)
    for leaf in jax.tree.leaves(placed):
        devs = {s.device for s in leaf.addressable_shards}
        _check(devs == set(jax.devices()), "events not spread", devs)
        _check({s.data.shape[1] for s in leaf.addressable_shards} == {RUNS // n},
               "uneven replica shards")
    print(json.dumps({"phase": "placement", "event_devices": n,
                      "replicas_per_device": RUNS // n}), flush=True)
    del placed

    plain = timed_run("mfi@steady shard=False", "mfi", paper, shard=False)
    timed_run("mfi@steady shard=True", "mfi", paper, same_as=plain, shard=True)
    timed_run("mfi@steady shard=True chunked", "mfi", paper, same_as=plain,
              shard=True, chunk_size=CHUNK_SIZE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica-sharding phase on four chips")
    args = ap.parse_args(argv)
    _require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "device": _device_record()}), flush=True)
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    print(json.dumps({"total_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"ok": True, "device": _device_record()}))


if __name__ == "__main__":
    main()
