"""Replica-throughput benchmark: batched JAX engine vs the Python reference.

Measures both engines back-to-back on the same point — by default the
paper-scale heavy-load point (M=100, uniform, 85% offered load) with 64
replicas — and reports replicas/second.  The batched engine is reported
twice: *cold* (first call, includes XLA compilation — what a one-shot
script sees) and *steady-state* (what any sweep beyond one point sees:
the compiled program is reused across loads, distributions and seeds,
only shapes recompile).  The headline speedup is the steady-state number;
the acceptance bar is >= 10x on CPU.

``--smoke`` shrinks the point (M=16, 8 replicas) so CI can track the perf
trajectory per-PR in ~a minute; ``--json PATH`` dumps the metrics for the
workflow artifact.  Smoke mode records the numbers without enforcing the
10x bar (tiny clusters under-utilize the batched engine by design), and
additionally sweeps **every registered batched-capable policy**
(``repro.core.policy.list_policies(engine="batched")``) for warm per-policy
throughput — ``mfi-defrag``'s migrate stage included — plus one
**cumulative-protocol** run, one **steady-queued** run (above
saturation, recording p50/p99 wait, fairness and queue admits next to
throughput) and one **steady-faulted** run (the same point overlaid with
a deterministic hot fault process, recording goodput, evictions,
recovered fraction and TTR p99 — all gated against the baseline, since
they are seed-deterministic), so the uploaded artifact tracks the perf
trajectory of every
engine configuration, including policies registered after this benchmark
was written (``--sweep``/``--no-sweep`` overrides).

The smoke sweep also records a **chunked streaming** point (same
seed/load as the headline point, ``chunk_size`` ≪ the stream length,
through ``run_batched(chunk_size=...)``): chunking is bit-exact, so its
acceptance must equal the monolithic point exactly and its warm
throughput must stay within 10% — both gated by ``--baseline`` — and the
recorded ``h2d_overlap_frac`` tracks how much of the host→device event
feed overlapped chunk compute.

``--baseline PATH`` diffs the run against a committed reference artifact
(``benchmarks/BENCH_baseline.json``): the headline ``speedup_warm`` (the
batched-vs-python ratio, machine-normalized) must not regress by more than
20%, per-policy warm-throughput ratios are recorded under ``vs_baseline``
in the payload, and the process exits non-zero on a gate failure — this is
the CI perf-trajectory gate.

``--compile-cache`` turns on JAX's persistent compilation cache through
:func:`repro.compile_cache.enable_compile_cache` (``JAX_COMPILATION_CACHE_DIR``
when set — CI points it at its workflow cache — else ``<repo>/.jax_cache``),
so the *cold* call hits compiled programs on disk instead of re-lowering
from scratch — ``speedup_cold`` then measures dispatch, not compilation.  ``--stress``
runs only the memory-bound chunked stress point (≥ 20k events per
replica; CI caps ``XLA_PYTHON_CLIENT_MEM_FRACTION`` and skips the
monolithic path, which would materialize the full event/trace tensors).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.core.policy import list_policies
from repro.sim import SimConfig, run_many
from repro.sim.batched import run_batched

#: maximum tolerated relative drop of speedup_warm vs the baseline artifact
REGRESSION_GATE = 0.20

#: queue metrics are deterministic for a fixed seed/config — tolerate only
#: float noise, so behavioral drift in the wait/park stages fails the gate
QUEUED_METRIC_TOL = 1e-6

#: the chunked smoke point must stay within this of the monolithic point's
#: warm throughput (same run, same machine — per-chunk dispatch overhead is
#: the only legitimate cost) and match its acceptance bit-for-bit
CHUNKED_WARM_TOL = 0.10


def sweep_policies(cfg: SimConfig, runs: int):
    """Warm replica throughput of every registered batched-capable policy."""
    out = {}
    for policy in list_policies(engine="batched"):
        run_batched(policy, cfg, runs=runs)  # compile + warm the cache
        t0 = time.perf_counter()
        r = run_batched(policy, cfg, runs=runs)
        dt = time.perf_counter() - t0
        out[policy] = {
            "warm_rps": runs / dt,
            "acceptance_rate": float(r["acceptance_rate"]),
        }
    return out


def bench_cumulative(cfg: SimConfig, runs: int):
    """Warm throughput of one cumulative-protocol batched run (mfi)."""
    ccfg = dataclasses.replace(cfg, protocol="cumulative")
    run_batched("mfi", ccfg, runs=runs)  # compile + warm the cache
    t0 = time.perf_counter()
    r = run_batched("mfi", ccfg, runs=runs)
    dt = time.perf_counter() - t0
    return {
        "warm_rps": runs / dt,
        "acceptance_rate": float(r["acceptance_rate"]),
        "final_utilization": float(r["utilization"]),
    }


def bench_queued(cfg: SimConfig, runs: int):
    """Warm throughput + queue metrics of one steady-queued batched run.

    Run above saturation (load >= 1.1) so the wait ring actually cycles;
    the metrics are deterministic for a fixed seed/config, so the baseline
    diff can gate on them tightly — a silent change to the wait/park
    stages shows up as metric drift here before any parity test runs.
    """
    qcfg = dataclasses.replace(
        cfg, protocol="steady-queued", offered_load=max(cfg.offered_load, 1.1)
    )
    run_batched("mfi", qcfg, runs=runs)  # compile + warm the cache
    t0 = time.perf_counter()
    r = run_batched("mfi", qcfg, runs=runs)
    dt = time.perf_counter() - t0
    return {
        "warm_rps": runs / dt,
        "acceptance_rate": float(r["acceptance_rate"]),
        "wait_p50": float(r["wait_p50"]),
        "wait_p99": float(r["wait_p99"]),
        "fairness": float(r["fairness"]),
        "queue_admits": float(r["queue_admits"]),
    }


def bench_faulted(cfg: SimConfig, runs: int):
    """Warm throughput + fault stats of one steady-faulted batched run.

    The queued benchmark's above-saturation point overlaid with a hot
    fault process (MTBF 60 slots, MTTR 10) so evictions, backoff
    re-queues and recoveries all fire within the smoke horizon.  Like the
    queued point the metrics are seed-deterministic, so the baseline diff
    gates on them tightly — behavioral drift in the fault/wait stages
    fails CI here before any parity test runs.
    """
    from repro.core.mig import FaultModel

    fcfg = dataclasses.replace(
        cfg, protocol="steady-faulted",
        offered_load=max(cfg.offered_load, 1.1),
        fault_model=FaultModel(mtbf=60.0, mttr=10.0),
    )
    run_batched("mfi", fcfg, runs=runs)  # compile + warm the cache
    t0 = time.perf_counter()
    r = run_batched("mfi", fcfg, runs=runs)
    dt = time.perf_counter() - t0
    return {
        "warm_rps": runs / dt,
        "acceptance_rate": float(r["acceptance_rate"]),
        "goodput": float(r["goodput"]),
        "evictions": float(r["evictions"]),
        "recovered_fraction": float(r["recovered_fraction"]),
        "ttr_p99": float(r["ttr_p99"]),
    }


def bench_fused(cfg: SimConfig, runs: int, policies=("mfi", "mfi-defrag")):
    """Warm throughput of the fused Pallas select/migrate lowering vs jnp.

    Interleaved best-of-3 per policy (same-machine comparison, so the
    ``speedup_vs_jnp`` ratio is machine-normalized and the baseline gate
    can compare it across runners).  The fused kernels are a pure lowering
    change, so the acceptance rate must match the jnp path bit-for-bit —
    ``acceptance_identical`` is a hard gate under ``--baseline``.  On CPU
    the kernels run in interpret mode (traced to XLA inside jit); on TPU
    they compile to real Mosaic launches.
    """
    out = {}
    for policy in policies:
        run_batched(policy, cfg, runs=runs, use_kernel=True)  # compile
        run_batched(policy, cfg, runs=runs, use_kernel=False)
        dt_k = dt_j = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            rj = run_batched(policy, cfg, runs=runs, use_kernel=False)
            dt_j = min(dt_j, time.perf_counter() - t0)
            t0 = time.perf_counter()
            rk = run_batched(policy, cfg, runs=runs, use_kernel=True)
            dt_k = min(dt_k, time.perf_counter() - t0)
        out[policy] = {
            "warm_rps": runs / dt_k,
            "jnp_warm_rps": runs / dt_j,
            "speedup_vs_jnp": dt_j / dt_k,
            "acceptance_rate": float(rk["acceptance_rate"]),
            "acceptance_identical": (
                float(rk["acceptance_rate"]) == float(rj["acceptance_rate"])
            ),
        }
    return out


def bench_chunked(cfg: SimConfig, runs: int, chunk_size: int | None = None):
    """Warm throughput of the chunked streaming driver on the smoke point.

    Same seed/load/policy as the monolithic headline point, with the event
    scan split into ``chunk_size``-event chunks (default: two chunks with a
    ragged tail — a smoke-sized stream is too short to amortize a deep
    chunk pipeline; the ``chunk_size`` ≪ T regime is what ``--stress``
    exercises).  Chunking is bit-exact, so the acceptance rate must equal
    the monolithic point *exactly*; the recorded ``h2d_overlap_frac`` is
    the fraction of host→device bytes staged while a chunk compute was in
    flight.

    The throughput gate compares against ``monolithic_warm_rps`` measured
    *here*, interleaved best-of-5 with the chunked pass: shared CI runners
    drift by tens of percent over a bench run, so comparing two
    single-sample timings taken minutes apart gates noise, not code.
    """
    from repro.sim import batched

    events, _, _, _ = batched.presample_arrivals(cfg, runs)
    e_max = events.pid.shape[0]
    if chunk_size is None:
        chunk_size = max(1, e_max // 2 + 1)
    run_batched("mfi", cfg, runs=runs, chunk_size=chunk_size)  # compile + warm
    run_batched("mfi", cfg, runs=runs)
    dt_chunked = dt_mono = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        run_batched("mfi", cfg, runs=runs)
        dt_mono = min(dt_mono, time.perf_counter() - t0)
        t0 = time.perf_counter()
        r = run_batched("mfi", cfg, runs=runs, chunk_size=chunk_size)
        dt_chunked = min(dt_chunked, time.perf_counter() - t0)
    return {
        "warm_rps": runs / dt_chunked,
        "monolithic_warm_rps": runs / dt_mono,
        "acceptance_rate": float(r["acceptance_rate"]),
        "chunk_size": chunk_size,
        "events": e_max,
        **_chunked_record(),
    }


def _chunked_record():
    """The last chunked call's chunk count and ``h2d_overlap_frac``: the
    share of its host→device bytes staged while a chunk's scan was in
    flight, from the call's :mod:`repro.obs` counters."""
    from repro import obs

    call = obs.calls()[-1]
    c = call.counters
    return {
        "chunks": len(call.find("dispatch")),
        "h2d_overlap_frac": c.get("h2d_overlapped_bytes", 0) / c["h2d_bytes"],
    }


def bench_stress(num_gpus: int = 16, load: float = 0.85, runs: int = 2,
                 chunk_size: int = 512, min_events: int = 20000):
    """Memory-bound stress point: a chunked run over >= ``min_events`` events.

    Scales the measurement window until the presampled stream holds at
    least ``min_events`` events per replica, then drives it through the
    chunked path only — device memory stays bounded by ``chunk_size``
    (one carry + two staged chunks) while the monolithic path would
    materialize the full ``(E, R)`` event and trace tensors; run under a
    capped ``XLA_PYTHON_CLIENT_MEM_FRACTION`` in CI, where the monolithic
    equivalent is deliberately skipped.
    """
    import dataclasses as _dc

    from repro.sim import batched

    cfg = SimConfig(
        num_gpus=num_gpus, distribution="uniform", offered_load=load, seed=0
    )
    while True:
        events, _, _, _ = batched.presample_arrivals(cfg, runs)
        e_max = events.pid.shape[0]
        if e_max >= min_events:
            break
        grow = min_events / e_max
        cfg = _dc.replace(
            cfg,
            measure_horizons=max(
                cfg.measure_horizons + 1,
                int(cfg.measure_horizons * grow * 1.05) + 1,
            ),
        )
    t0 = time.perf_counter()
    r = run_batched("mfi", cfg, runs=runs, chunk_size=chunk_size)
    dt = time.perf_counter() - t0
    record = _chunked_record()
    chunk_frac = chunk_size / e_max
    return {
        "events": e_max,
        "runs": runs,
        "num_gpus": num_gpus,
        "measure_horizons": cfg.measure_horizons,
        "chunk_size": chunk_size,
        "chunks": record["chunks"],
        "device_feed_fraction": chunk_frac,  # staged chunk vs full tensor
        "cold_rps": runs / dt,
        "acceptance_rate": float(r["acceptance_rate"]),
        "h2d_overlap_frac": record["h2d_overlap_frac"],
        "completed": True,
    }


def compare_baseline(payload: dict, baseline_path: str, gate: float = REGRESSION_GATE):
    """Diff this run against a committed baseline artifact.

    Returns ``(vs_baseline, ok)``: the comparison dict recorded in the JSON
    payload, and whether the headline ``speedup_warm`` (machine-normalized:
    batched warm throughput over the same host's Python engine) stayed
    within ``gate`` of the baseline.  Per-policy raw warm-rps ratios are
    informational (they compare across machines when the artifact was
    recorded elsewhere).
    """
    with open(baseline_path) as fh:
        base = json.load(fh)
    cur, ref = payload["speedup_warm"], base["speedup_warm"]
    vs = {
        "baseline_path": baseline_path,
        "speedup_warm": {"baseline": ref, "current": cur, "ratio": cur / ref},
        "gate": gate,
    }
    mismatch = {
        k: {"baseline": base.get(k), "current": payload.get(k)}
        for k in ("num_gpus", "runs", "load", "smoke")
        if base.get(k) != payload.get(k)
    }
    if mismatch:  # different problem size — ratios are meaningless, no gate
        vs["config_mismatch"] = mismatch
        vs["pass"] = True
        print(
            f"# vs baseline {baseline_path}: CONFIG MISMATCH "
            f"({', '.join(sorted(mismatch))}) — comparison recorded, "
            "regression gate skipped"
        )
        return vs, True
    pol = {}
    for name, p in (payload.get("policies") or {}).items():
        b = (base.get("policies") or {}).get(name)
        if b:
            pol[name] = {
                "baseline_rps": b["warm_rps"],
                "current_rps": p["warm_rps"],
                "ratio": p["warm_rps"] / b["warm_rps"],
            }
    if pol:
        vs["policies"] = pol
    ok = cur >= (1.0 - gate) * ref
    ch = payload.get("chunked")
    if ch is not None:
        # chunking is bit-exact and near-free: acceptance must equal the
        # monolithic point exactly, warm throughput must stay within
        # CHUNKED_WARM_TOL of the interleaved monolithic comparator
        # (measured back-to-back inside bench_chunked — the headline
        # warm_rps was timed minutes earlier under different load)
        mono_rps = ch["monolithic_warm_rps"]
        acc_match = ch["acceptance_rate"] == payload["acc_batched"]
        thr_ok = ch["warm_rps"] >= (1.0 - CHUNKED_WARM_TOL) * mono_rps
        vs["chunked"] = {
            "acceptance": {
                "monolithic": payload["acc_batched"],
                "chunked": ch["acceptance_rate"],
                "identical": acc_match,
            },
            "warm_rps": {
                "monolithic": mono_rps,
                "chunked": ch["warm_rps"],
                "ratio": ch["warm_rps"] / mono_rps,
            },
            "tolerance": CHUNKED_WARM_TOL,
            "pass": acc_match and thr_ok,
        }
        if not (acc_match and thr_ok):
            ok = False
    fb, fc = base.get("fused"), payload.get("fused")
    if fc is not None:
        # the fused lowering is bit-exact by construction: acceptance drift
        # is a correctness failure, and the machine-normalized
        # speedup_vs_jnp ratio must not regress past the gate
        entries, fok = {}, True
        for name, p in sorted(fc.items()):
            e = {
                "speedup_vs_jnp": p["speedup_vs_jnp"],
                "acceptance_identical": p["acceptance_identical"],
            }
            if not p["acceptance_identical"]:
                fok = False
            b = (fb or {}).get(name)
            if b:
                e["baseline_speedup_vs_jnp"] = b["speedup_vs_jnp"]
                e["ratio"] = p["speedup_vs_jnp"] / b["speedup_vs_jnp"]
                if e["ratio"] < 1.0 - gate:
                    fok = False
            entries[name] = e
        vs["fused"] = {"gate": gate, "entries": entries, "pass": fok}
        if not fok:
            ok = False
    qb, qc = base.get("queued"), payload.get("queued")
    if qb and qc:
        # queue metrics are seed-deterministic: any drift means the wait or
        # park stage changed behavior, not just performance
        drift = {
            k: {"baseline": qb[k], "current": qc[k]}
            for k in (
                "acceptance_rate", "wait_p50", "wait_p99", "fairness",
                "queue_admits",
            )
            if k in qb
            and abs(qc[k] - qb[k]) > QUEUED_METRIC_TOL * max(1.0, abs(qb[k]))
        }
        vs["queued"] = {"tolerance": QUEUED_METRIC_TOL, "drift": drift,
                        "pass": not drift}
        if drift:
            ok = False
    fb2, fc2 = base.get("faulted"), payload.get("faulted")
    if fb2 and fc2:
        # fault stats are seed-deterministic too: drift means the fault,
        # wait or park stage changed eviction/re-queue behavior
        drift = {
            k: {"baseline": fb2[k], "current": fc2[k]}
            for k in (
                "acceptance_rate", "goodput", "evictions",
                "recovered_fraction", "ttr_p99",
            )
            if k in fb2
            and abs(fc2[k] - fb2[k]) > QUEUED_METRIC_TOL * max(1.0, abs(fb2[k]))
        }
        vs["faulted"] = {"tolerance": QUEUED_METRIC_TOL, "drift": drift,
                         "pass": not drift}
        if drift:
            ok = False
    vs["pass"] = ok
    return vs, ok


def bench_point(policy: str, cfg: SimConfig, runs: int, py_runs: int):
    t0 = time.perf_counter()
    rp = run_many(policy, cfg, runs=py_runs)
    t_python = (time.perf_counter() - t0) / py_runs  # sec / replica

    t0 = time.perf_counter()
    rb = run_batched(policy, cfg, runs=runs)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_batched(policy, cfg, runs=runs)
    t_warm = time.perf_counter() - t0

    return {
        "python_rps": 1.0 / t_python,
        "cold_rps": runs / t_cold,
        "warm_rps": runs / t_warm,
        "speedup_cold": t_python * runs / t_cold,
        "speedup_warm": t_python * runs / t_warm,
        "acc_python": rp["acceptance_rate"],
        "acc_batched": rb["acceptance_rate"],
    }


def main(runs: int = 64, num_gpus: int = 100, load: float = 0.85,
         policy: str = "mfi", py_runs: int = 3, smoke: bool = False,
         json_path: str | None = None, sweep: bool | None = None,
         baseline: str | None = None,
         compile_cache: bool = False, stress: bool = False):
    compile_cache = enable_compile_cache() if compile_cache else None
    if stress:  # memory-bound chunked stress point only (CI runs it under a
        # capped XLA_PYTHON_CLIENT_MEM_FRACTION; the monolithic path is
        # skipped by design at this stream length)
        s = bench_stress()
        print(
            f"stress,batched-chunked,mfi,{s['num_gpus']},{s['runs']},"
            f"{s['cold_rps']:.3f},{s['acceptance_rate']:.4f}"
        )
        print(
            f"# chunked stress: {s['events']} events x {s['runs']} replicas "
            f"in {s['chunks']} chunks of {s['chunk_size']} "
            f"(device feed = {s['device_feed_fraction']:.1%} of the stream), "
            f"h2d_overlap_frac={s['h2d_overlap_frac']:.2f} -> COMPLETED"
        )
        if json_path:
            with open(json_path, "w") as fh:
                json.dump(
                    dict(s, compile_cache=compile_cache),
                    fh, indent=2, sort_keys=True,
                )
            print(f"# wrote {json_path}")
        return s
    if smoke:
        runs, num_gpus, py_runs = min(runs, 8), min(num_gpus, 16), min(py_runs, 2)
    if sweep is None:
        sweep = smoke  # CI artifact tracks all batched-capable policies
    cfg = SimConfig(
        num_gpus=num_gpus, distribution="uniform", offered_load=load, seed=0
    )
    print("table,engine,policy,num_gpus,runs,replicas_per_sec,speedup")
    r = bench_point(policy, cfg, runs, py_runs)
    print(f"engine,python,{policy},{num_gpus},{py_runs},{r['python_rps']:.2f},1.0")
    print(
        f"engine,batched-cold,{policy},{num_gpus},{runs},"
        f"{r['cold_rps']:.2f},{r['speedup_cold']:.1f}"
    )
    print(
        f"engine,batched,{policy},{num_gpus},{runs},"
        f"{r['warm_rps']:.2f},{r['speedup_warm']:.1f}"
    )
    print(
        f"# acceptance parity: python={r['acc_python']:.4f} "
        f"batched={r['acc_batched']:.4f}"
    )
    ok = smoke or r["speedup_warm"] >= 10.0
    print(
        f"# replica-throughput speedup (steady-state) @ "
        f"(M={num_gpus}, runs={runs}, uniform, {load:.2f} load): "
        f"{r['speedup_warm']:.1f}x (cold incl. compile: {r['speedup_cold']:.1f}x) "
        f"-> {'PASS' if ok else 'FAIL'}"
        f"{' (smoke mode: recorded, not enforced)' if smoke else ' (>= 10x required)'}"
    )
    per_policy = cumulative = None
    if sweep:
        per_policy = sweep_policies(cfg, runs)
        print("table,engine,policy,num_gpus,runs,replicas_per_sec,acceptance")
        for name, p in per_policy.items():
            print(
                f"sweep,batched,{name},{num_gpus},{runs},"
                f"{p['warm_rps']:.2f},{p['acceptance_rate']:.4f}"
            )
        cumulative = bench_cumulative(cfg, runs)
        print(
            f"sweep,batched-cumulative,mfi,{num_gpus},{runs},"
            f"{cumulative['warm_rps']:.2f},{cumulative['acceptance_rate']:.4f}"
        )
        queued = bench_queued(cfg, runs)
        print(
            f"sweep,batched-queued,mfi,{num_gpus},{runs},"
            f"{queued['warm_rps']:.2f},{queued['acceptance_rate']:.4f}"
        )
        print(
            f"# queued point: wait_p50={queued['wait_p50']:.2f} "
            f"wait_p99={queued['wait_p99']:.2f} "
            f"fairness={queued['fairness']:.4f} "
            f"queue_admits={queued['queue_admits']:.2f}"
        )
        faulted = bench_faulted(cfg, runs)
        print(
            f"sweep,batched-faulted,mfi,{num_gpus},{runs},"
            f"{faulted['warm_rps']:.2f},{faulted['acceptance_rate']:.4f}"
        )
        print(
            f"# faulted point: goodput={faulted['goodput']:.4f} "
            f"evictions={faulted['evictions']:.2f} "
            f"recovered_fraction={faulted['recovered_fraction']:.4f} "
            f"ttr_p99={faulted['ttr_p99']:.2f}"
        )
        chunked = bench_chunked(cfg, runs)
        print(
            f"sweep,batched-chunked,mfi,{num_gpus},{runs},"
            f"{chunked['warm_rps']:.2f},{chunked['acceptance_rate']:.4f}"
        )
        print(
            f"# chunked point: {chunked['chunks']} chunks of "
            f"{chunked['chunk_size']} over {chunked['events']} events, "
            f"h2d_overlap_frac={chunked['h2d_overlap_frac']:.2f}, "
            f"interleaved monolithic {chunked['monolithic_warm_rps']:.2f} rps"
        )
        fused = bench_fused(cfg, runs)
        for name, p in sorted(fused.items()):
            print(
                f"sweep,batched-fused,{name},{num_gpus},{runs},"
                f"{p['warm_rps']:.2f},{p['acceptance_rate']:.4f}"
            )
            print(
                f"# fused {name}: {p['speedup_vs_jnp']:.2f}x vs jnp "
                f"({p['jnp_warm_rps']:.2f} rps), acceptance "
                f"{'identical' if p['acceptance_identical'] else 'DRIFTED'}"
            )
    else:
        queued = faulted = chunked = fused = None
    payload = dict(
        r, policy=policy, num_gpus=num_gpus, runs=runs, load=load, smoke=smoke,
        compile_cache=compile_cache,
    )
    if per_policy is not None:
        payload["policies"] = per_policy
    if cumulative is not None:
        payload["cumulative"] = cumulative
    if queued is not None:
        payload["queued"] = queued
    if faulted is not None:
        payload["faulted"] = faulted
    if chunked is not None:
        payload["chunked"] = chunked
    if fused is not None:
        payload["fused"] = fused
    gate_ok = True
    if baseline:
        vs, gate_ok = compare_baseline(payload, baseline)
        c = vs.get("chunked")
        if c is not None and not c["pass"] and c["acceptance"]["identical"]:
            # throughput-only chunked failure: the interleaved ratio sits
            # a few percent above the gate in expectation but its sampling
            # noise straddles it — one re-measure drops the flake rate by
            # an order of magnitude without weakening the gate
            print(
                f"# chunked warm {c['warm_rps']['ratio']:.2f}x below gate, "
                "re-measuring once"
            )
            payload["chunked"] = bench_chunked(cfg, runs)
            vs, gate_ok = compare_baseline(payload, baseline)
        payload["vs_baseline"] = vs
        s = vs["speedup_warm"]
        print(
            f"# vs baseline {baseline}: speedup_warm {s['current']:.1f}x / "
            f"{s['baseline']:.1f}x = {s['ratio']:.2f} "
            f"-> {'PASS' if gate_ok else 'FAIL'} "
            f"(>= {1 - REGRESSION_GATE:.2f} required)"
        )
        for name, p in sorted(vs.get("policies", {}).items()):
            print(
                f"# vs baseline {name}: {p['current_rps']:.2f} rps / "
                f"{p['baseline_rps']:.2f} rps = {p['ratio']:.2f}x"
            )
        fz = vs.get("fused")
        if fz is not None:
            for name, e in sorted(fz["entries"].items()):
                ratio = (
                    f", {e['ratio']:.2f}x of baseline" if "ratio" in e else ""
                )
                print(
                    f"# vs baseline fused {name}: "
                    f"{e['speedup_vs_jnp']:.2f}x vs jnp{ratio}, acceptance "
                    f"{'identical' if e['acceptance_identical'] else 'DRIFTED'}"
                )
            print(
                f"# fused gate -> {'PASS' if fz['pass'] else 'FAIL'} "
                f"(acceptance identical + >= {1 - fz['gate']:.2f} of "
                "baseline speedup_vs_jnp)"
            )
        q = vs.get("queued")
        if q is not None:
            drifted = ", ".join(sorted(q["drift"])) or "none"
            print(
                f"# vs baseline queued point: drifted metrics: {drifted} "
                f"-> {'PASS' if q['pass'] else 'FAIL'} "
                f"(tolerance {q['tolerance']:g})"
            )
        f = vs.get("faulted")
        if f is not None:
            drifted = ", ".join(sorted(f["drift"])) or "none"
            print(
                f"# vs baseline faulted point: drifted metrics: {drifted} "
                f"-> {'PASS' if f['pass'] else 'FAIL'} "
                f"(tolerance {f['tolerance']:g})"
            )
        c = vs.get("chunked")
        if c is not None:
            print(
                f"# chunked vs monolithic: acceptance "
                f"{'identical' if c['acceptance']['identical'] else 'DRIFTED'}, "
                f"warm {c['warm_rps']['ratio']:.2f}x "
                f"-> {'PASS' if c['pass'] else 'FAIL'} "
                f"(>= {1 - CHUNKED_WARM_TOL:.2f} required)"
            )
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {json_path}")
    if not gate_ok:
        sys.exit(
            f"FAIL: perf or queued-metric regression vs {baseline} "
            f"(speedup_warm gate {REGRESSION_GATE:.0%}; queued metric "
            f"tolerance {QUEUED_METRIC_TOL:g})"
        )
    return r


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=64)
    ap.add_argument("--num-gpus", type=int, default=100)
    ap.add_argument("--load", type=float, default=0.85)
    ap.add_argument("--policy", default="mfi")
    ap.add_argument("--py-runs", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized point (M=16, 8 replicas); records without "
                         "enforcing the 10x bar (--baseline can still fail "
                         "the run on a regression)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write metrics JSON here (workflow artifact)")
    ap.add_argument("--sweep", dest="sweep", action="store_true", default=None,
                    help="per-policy warm throughput over every registered "
                         "batched-capable policy (default: on in smoke mode)")
    ap.add_argument("--no-sweep", dest="sweep", action="store_false")
    ap.add_argument("--baseline", default=None,
                    help="diff against a committed artifact (e.g. "
                         "benchmarks/BENCH_baseline.json); exits non-zero on "
                         ">20%% speedup_warm regression")
    ap.add_argument("--compile-cache", action="store_true",
                    help="enable JAX's persistent compilation cache "
                         "(JAX_COMPILATION_CACHE_DIR when set, else "
                         "<repo>/.jax_cache) so cold calls hit disk instead "
                         "of recompiling")
    ap.add_argument("--stress", action="store_true",
                    help="memory-bound chunked stress point only: stream "
                         ">= 20k events per replica through the chunked "
                         "driver (run under a capped "
                         "XLA_PYTHON_CLIENT_MEM_FRACTION in CI)")
    args = ap.parse_args()
    main(
        runs=args.runs, num_gpus=args.num_gpus, load=args.load,
        policy=args.policy, py_runs=args.py_runs, smoke=args.smoke,
        json_path=args.json_path, sweep=args.sweep,
        baseline=args.baseline,
        compile_cache=args.compile_cache, stress=args.stress,
    )
