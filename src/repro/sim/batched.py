"""Batched on-device Monte-Carlo simulation engine (staged scan pipeline).

The Python reference in :mod:`repro.sim.simulator` runs replicas one at a
time through a ``ClusterState``/``heapq`` event loop; at the paper's scale
(500 replicas per point, §VI) a load sweep takes hours.  This module runs
**R replicas × T slots as one** ``lax.scan`` **over a vmapped replica axis**
so the whole Monte-Carlo average is a single XLA program.

Staged pipeline (the :class:`EngineCore`)
    The scan body is composed from small *stages* —
    ``arrival → select → migrate → commit → expire → measure`` — and two
    static descriptors decide which stages are compiled in:

    * the :class:`Protocol` descriptor (``steady`` | ``cumulative``)
      selects the *measure* semantics: slot-boundary sampling for the
      steady protocol (paper §VI), post-commit sampling on the cumulative
      demand grid for the paper-literal cumulative protocol;
    * the :class:`~repro.core.policy.PolicySpec` selects the decision
      stages: the select lowering (:func:`_lower_select`, or — under
      ``use_kernel`` for argmin-fusable specs — the fused Pallas
      :func:`~repro.kernels.fragscore.fragscore.select_from_base` launch
      via :func:`make_select_fn`), the optional *migrate* stage
      (``spec.defrag`` — the beyond-paper ``mfi-defrag``
      single-migration search, see below; fused counterpart
      :func:`make_migrate_fn`), and the rotation-cursor update.  See
      ``docs/KERNELS.md`` for the kernel dispatch rules.

    Because the descriptors are static jit arguments, a configuration
    compiles exactly the stages it needs: the steady/non-defrag pipeline
    emits the same computation as the original monolithic event step
    (pre-refactor traces reproduce bit-for-bit).

Event stream
    Arrivals are pre-sampled on host (Poisson counts, profile ids and
    durations per slot for the steady protocol; one arrival per slot for
    the cumulative protocol) and flattened into one *event stream* per
    replica: one event per arrival, plus one synthetic heartbeat event for
    every empty slot so consecutive events never skip a slot.  Streams are
    padded to the longest replica (``pid = -1`` lanes are no-ops), and
    everything slot-dependent (release ring row, metric-sample flags,
    measurement window membership) is precomputed host-side, so the device
    step is pure tensor algebra with no clock arithmetic.

Heterogeneous fleets
    A :class:`repro.core.mig.ClusterSpec` (``SimConfig.cluster_spec``) may
    mix device models.  All per-model placement tables are stacked into one
    :class:`SpecTables` pytree — ``(K, N, ...)`` arrays padded to a common
    placement count ``N`` and anchor count ``A`` — and a static ``(M,)``
    model-index array ``midx`` gathers each GPU's tables inside the scan
    step.  The MFI ΔF table becomes a per-model gather plus one batched
    matmul (``einsum('mn,man->ma')``), so the scan stays fully jittable;
    the paper's homogeneous setup is the trivial ``K = 1`` spec and
    reproduces the previous engine bit-for-bit.  Non-8-slice geometries
    (e.g. the stylized H200-141GB) ride the same padded-width path.

Replica state (fixed-capacity struct-of-arrays pytree)
    * ``occ (M, S) int32`` — cluster occupancy bitmap (materialized only
      when the Pallas-kernel scoring path needs it; otherwise ``base``
      carries the full information);
    * ``base (M, N) float32`` — occupied-slice count per placement window
      of each GPU's own model, ``occ @ W[midx]ᵀ``.  Window counts are
      *linear* in occupancy, so ``base`` is maintained incrementally (row
      add on commit, row subtract on release) and every fragmentation
      quantity — F(m), the full MFI ΔF table, feasibility — derives from
      it without per-arrival matmuls over hypothetical occupancies;
    * ``free (M,) int32`` / ``f (M,) float32`` — free-slice counts and
      per-GPU fragmentation scores, recomputed only for rows a drain or
      commit touched;
    * ``rr () int32`` — RoundRobin cursor (next GPU to try first); carried
      through the scan so RR is an ordinary batched policy;
    * an expiry ring buffer ``ring_gpu (K+2, E) int32`` /
      ``ring_mask (K+2, E, S) int32`` keyed by end slot modulo
      ``K = T + 1``: row ``e % K`` holds the (gpu, placement-window) rows
      of workloads expiring at slot ``e``.  Durations are drawn from
      ``[1, T]``, so an end slot is strictly less than one ring revolution
      ahead and each row is drained (masked scatter-subtract) exactly when
      the clock reaches it, before it can be re-targeted.  Within-row
      columns are assigned on host (arrival rank among same-end-slot
      arrivals), so inserts never collide; row ``K + 1`` is a write-only
      trash row for padding lanes.  For defrag specs two parallel planes
      ``ring_pid`` / ``ring_aidx`` additionally record each running
      workload's demand class and anchor index — **the ring doubles as the
      allocation table** the migration search needs.

Migrate stage (batched ``mfi-defrag``)
    When ``spec.defrag`` and the arrival was rejected, the stage evaluates
    every running workload (= live ring entry) as a migration victim with
    masked tensor ops: hypothetically evacuate it, re-select the request on
    the freed GPU (the only GPU where it can have become feasible), then
    re-place the victim anywhere via the spec's own key list, scoring each
    candidate by the total cluster fragmentation after both moves.  The
    winner is the lexicographic minimum of ``(total F, victim gpu, victim
    anchor)`` — exactly the canonical order the host search
    (:class:`repro.core.schedulers.MFIDefrag`) enumerates — so the two
    engines agree single-step whenever the host's candidate budget does
    not bind (the batched search is always exhaustive: it is vectorized,
    a budget would save no work).  All scores are integer-valued, hence
    exact in float32.

Replica sharding
    The replica axis is embarrassingly parallel: :func:`run_batched`
    splits it across all visible devices (``NamedSharding`` over a 1-D
    ``replicas`` mesh, the scan mapped over it with ``shard_map`` since
    XLA cannot partition a Mosaic kernel) whenever more than one device
    is available and ``runs`` divides evenly — results are bitwise
    identical to the single-device run (no cross-replica arithmetic
    happens on device).  Single-device setups are unchanged.

Chunked streaming driver (``chunk_size``)
    By default the whole ``(E_max, R)`` event stream ships to device and
    the whole trace comes back — one program, fastest when it fits.
    :func:`simulate_chunked` (``run_batched(..., chunk_size=c)``) instead
    streams the scan: the carry stays device-resident and is **donated**
    into each chunk (:func:`_scan_chunk`), the host ``device_put``\\ s
    chunk ``k+1`` while chunk ``k`` computes (double-buffered), and each
    chunk's trace is fetched back and concatenated host-side — device
    memory is bounded by ``c``, not ``E_max``.  The carry holds every
    cross-event datum, so chunking is bit-for-bit the monolithic scan at
    any chunk size (golden hashes enforced in
    ``tests/test_chunked_stream.py``), and the carry checkpoints/restores
    through :mod:`repro.checkpoint.ckpt` for bit-exact resume
    (:func:`save_stream_checkpoint` / :func:`load_stream_checkpoint` /
    :func:`init_carry`).

Policies are **compiled from declarative**
:class:`repro.core.policy.PolicySpec` **registry entries** — the same specs
the host engine interprets (:mod:`repro.core.schedulers`), so the two
engines cannot drift by construction.  :func:`_lower_select` lowers a
spec's ordered lexicographic key list to a masked refinement over the
``(M, A)`` feasibility tensor (each key narrows the candidate mask to its
minimizers; the first surviving flat index supplies the implicit
``(gpu, anchor)`` tie-break), with the ΔF table computed only for specs
whose keys ask for it.  The spec itself is the static jit argument, so any
newly registered batched-capable policy runs without touching this module.
Acceptance, utilization, active-GPU and fragmentation-severity metrics
accumulate inside the scan; :func:`run_batched` returns the same aggregate
dict as :func:`repro.sim.simulator.run_many` — demand-grid traces included
for the cumulative protocol.

Parity guarantees vs the Python reference (``tests/test_batched_sim.py``,
``tests/test_heterogeneous.py``, ``tests/test_engine_core.py``):

* single-step decisions of every batched-capable registered policy match
  their host-compiled ``Scheduler.select`` counterparts *exactly*
  (including rejects, tie-breaks and defrag migrations — every scoring-key
  value is integer-valued, hence exact in float32), on homogeneous and
  mixed specs;
* whole-run acceptance rates agree within Monte-Carlo tolerance on the
  steady protocol (the two engines consume their RNG streams differently);
  driving the Python schedulers over the *same* presampled event stream
  matches decision-for-decision (:func:`repro.sim.replay.host_decisions`);
* cumulative-protocol runs consume the *identical* per-replica RNG streams
  as ``run_many`` (seed ``cfg.seed + r * 9973``), so the demand-grid
  traces match the Python simulator to float tolerance on the same stream.

On TPU, per-GPU fragmentation rescoring (the rows each drain/commit
touches, which feed both MFI and the severity metric) routes through the
Pallas ``fragscore`` kernel (``interpret=False``) — homogeneous specs only
(the kernel bakes in one placement table); on CPU and on mixed fleets the
``base``-derived pure-jnp scoring is used.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import cluster as jcluster
from repro.core import mig
from repro.core.policy import (
    REQUEST_KEYS,
    PolicyLike,
    PolicySpec,
    key_base,
    list_policies,
    queue_order,
    resolve,
)
from repro.sim import distributions
from repro.sim.simulator import (
    SAMPLE_EVERY,
    SimConfig,
    jain_fairness,
    request_probs,
    steady_params,
)

#: batched-capable registered policies at import time (back-compat alias;
#: `repro.core.policy.list_policies(engine="batched")` is the live view)
POLICIES = list_policies(engine="batched")

_BIG = jnp.float32(1e9)


# ---------------------------------------------------------------------------
# Protocol descriptors — static configuration of the measure stage
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Protocol:
    """Static load-protocol descriptor compiled into the scan body.

    ``boundary_metrics`` samples utilization / active-GPU / fragmentation
    at slot boundaries *before* the drain (the steady protocol's
    time-averaged metrics, reduced host-side against the ``sample``
    flags); ``post_metrics`` samples them *after* the commit of every
    event (the cumulative protocol's demand-grid traces); ``queued``
    compiles the wait-ring stages into the step (the ``steady-queued``
    protocol: rejected arrivals park in a fixed-capacity wait ring with a
    patience budget and re-enter selection ahead of later arrivals — see
    :meth:`EngineCore._stage_wait`).  ``faulted`` (implies ``queued``)
    additionally compiles the fault stage: presampled GPU fail/recover
    lanes mask GPUs out of feasibility, evict their live expiry-ring
    entries into the wait ring, and patience overruns re-arm with
    exponential backoff instead of dropping — up to ``fault_retries``
    re-queues of ``fault_backoff * 2**(k-1)`` slots each (see
    :meth:`EngineCore._stage_fault` and ``docs/FAULTS.md``).  Instances
    are frozen/hashable so a protocol doubles as a jit static argument.
    """

    name: str
    boundary_metrics: bool
    post_metrics: bool
    queued: bool = False
    faulted: bool = False
    fault_retries: int = 2
    fault_backoff: int = 2


PROTOCOLS: Dict[str, Protocol] = {
    "steady": Protocol("steady", boundary_metrics=True, post_metrics=False),
    "cumulative": Protocol("cumulative", boundary_metrics=False, post_metrics=True),
    "steady-queued": Protocol(
        "steady-queued", boundary_metrics=True, post_metrics=False, queued=True
    ),
    "steady-faulted": Protocol(
        "steady-faulted", boundary_metrics=True, post_metrics=False,
        queued=True, faulted=True,
    ),
}


def resolve_protocol(protocol: Union[str, Protocol]) -> Protocol:
    """Name-or-descriptor -> :class:`Protocol` (single validation path)."""
    if isinstance(protocol, Protocol):
        return protocol
    if protocol not in PROTOCOLS:
        raise ValueError(
            f"unknown protocol {protocol!r}; options: {tuple(sorted(PROTOCOLS))}"
        )
    return PROTOCOLS[protocol]


# ---------------------------------------------------------------------------
# Stacked per-model placement tables
# ---------------------------------------------------------------------------


class SpecTables(NamedTuple):
    """Per-model placement tables of a ClusterSpec, stacked and padded.

    Axis glossary: ``K`` distinct models, ``N`` common (padded) placement
    count, ``A`` common (padded) anchor count, ``P`` demand classes,
    ``S`` memory slices.  Padded placement rows have all-zero windows and
    ``V = 0`` so they never count toward any score; padded anchor columns
    are marked invalid in ``profile_valid``.
    """

    W: jax.Array               # (K, N, S) float32 — placement windows
    V: jax.Array               # (K, N) float32 — window sizes (0 where padded)
    slices: jax.Array          # (K,) int32 — memory slices per model
    profile_rows: jax.Array    # (K, P, A) int32 — row into W/V per anchor
    profile_masks: jax.Array   # (K, P, A, S) int32 — anchor window bitmask
    profile_anchors: jax.Array  # (K, P, A) int32 — anchor index (-1 pad)
    profile_valid: jax.Array   # (K, P, A) bool — anchor validity
    profile_mem: jax.Array     # (K, P) float32 — slice demand per class
    maskwin: jax.Array         # (K, P, A, N) float32 — slices each anchor adds per window
    maskpos: jax.Array         # (K, P, A, N) float32 — (maskwin > 0)


@functools.lru_cache(maxsize=None)
def spec_tables(spec: mig.ClusterSpec) -> SpecTables:
    """Build (and cache) the stacked device tables of a cluster spec."""
    models = spec.models
    K = len(models)
    P = mig.NUM_PROFILES
    N = max(m.num_placements for m in models)
    A = max(m.max_anchors for m in models)
    S = spec.num_mem_slices

    W = np.zeros((K, N, S), np.float32)
    V = np.zeros((K, N), np.float32)
    slices = np.array([m.num_mem_slices for m in models], np.int32)
    rows_t = np.zeros((K, P, A), np.int32)
    masks_t = np.zeros((K, P, A, S), np.int32)
    anchors_t = np.full((K, P, A), -1, np.int32)
    valid_t = np.zeros((K, P, A), bool)
    mem_t = np.zeros((K, P), np.float32)
    for k, m in enumerate(models):
        n = m.num_placements
        W[k, :n, : m.num_mem_slices] = m.placement_masks
        V[k, :n] = m.placement_mem
        pm, pa, pv = jcluster._np_profile_tables(m, max_anchors=A)
        masks_t[k, :, :, : m.num_mem_slices] = pm
        anchors_t[k] = pa
        valid_t[k] = pv
        mem_t[k] = m.profile_mem
        for pid in range(P):
            s = m.profile_placement_rows(pid)
            rows_t[k, pid, : s.stop - s.start] = np.arange(s.start, s.stop)
    # occupied-slice count each profile anchor adds to every placement window
    maskwin = np.einsum("kpas,kns->kpan", masks_t.astype(np.float32), W)
    # the cache may be populated from inside a jit trace (e.g. `_simulate`
    # building its default tables): force concrete device arrays so no
    # tracer ever escapes into the cache
    with jax.ensure_compile_time_eval():
        return SpecTables(
            W=jnp.asarray(W),
            V=jnp.asarray(V),
            slices=jnp.asarray(slices),
            profile_rows=jnp.asarray(rows_t),
            profile_masks=jnp.asarray(masks_t),
            profile_anchors=jnp.asarray(anchors_t),
            profile_valid=jnp.asarray(valid_t),
            profile_mem=jnp.asarray(mem_t),
            maskwin=jnp.asarray(maskwin),
            maskpos=jnp.asarray((maskwin > 0).astype(np.float32)),
        )


def _default_spec(num_gpus: int) -> mig.ClusterSpec:
    return mig.ClusterSpec.homogeneous(mig.A100_80GB, num_gpus)


# ---------------------------------------------------------------------------
# Fragmentation scoring from the window-count state
# ---------------------------------------------------------------------------


def _frag_from_base(base: jax.Array, free: jax.Array, metric: str, v: jax.Array) -> jax.Array:
    """F(m) per GPU from window counts ``base (M, N)`` and per-GPU window
    sizes ``v (M, N)`` (= ``V[midx]``): (M,) float32."""
    if metric == "partial":
        counted = (base > 0) & (base < v)
    else:  # blocked
        counted = base > 0
    eligible = v <= free[..., None].astype(jnp.float32)
    return jnp.sum(jnp.where(counted & eligible, v, 0.0), axis=-1)


def _delta_from_base(
    base: jax.Array,
    free: jax.Array,
    metric: str,
    v: jax.Array,
    mw: jax.Array,
    mp: jax.Array,
    mem_g: jax.Array,
    f_before: jax.Array,
) -> jax.Array:
    """ΔF of every anchor dry-run of the request: (M, A) float32.

    ``v (M, N)``, ``mw/mp (M, A, N)`` and ``mem_g (M,)`` are the per-GPU
    gathers ``V[midx]``, ``maskwin/maskpos[midx, pid]`` and
    ``profile_mem[midx, pid]``.  Window counts after placement are
    ``base + mw`` (exact for feasible placements — the window is disjoint
    from current occupancy), so for the "blocked" metric the
    counted-predicate decomposes as ``(base > 0) | (mw > 0)`` and the whole
    (M, A) table reduces to one batched (M, N) × (M, N, A) matmul;
    "partial" needs the dense (M, A, N) elementwise form.  All scores are
    integer-valued — exact in float32.
    """
    freef = free.astype(jnp.float32)
    free_after = freef - mem_g  # (M,) — same for every anchor
    elig = v <= free_after[:, None]  # (M, N)
    if metric == "partial":
        ba = base[:, None, :] + mw  # (M, A, N)
        counted = (ba > 0) & (ba < v[:, None, :])
        f_after = jnp.sum(
            jnp.where(counted & elig[:, None, :], v[:, None, :], 0.0), axis=-1
        )
    else:  # blocked: counted_after = (base > 0) | (mw > 0)
        cb = base > 0  # (M, N)
        s_occ = jnp.sum(jnp.where(cb & elig, v, 0.0), axis=-1)  # (M,)
        cross = jnp.einsum("mn,man->ma", jnp.where(~cb & elig, v, 0.0), mp)  # (M, A)
        f_after = s_occ[:, None] + cross
    return f_after - f_before[:, None]


def _delta_from_base_all(
    base: jax.Array,
    free: jax.Array,
    metric: str,
    v: jax.Array,
    mw_all: jax.Array,
    mp_all: jax.Array,
    mem_all: jax.Array,
    f_before: jax.Array,
) -> jax.Array:
    """ΔF of every anchor dry-run of EVERY demand class: (P, M, A) float32.

    The class-batched form of :func:`_delta_from_base` — ``mw_all/mp_all
    (P, M, A, N)`` and ``mem_all (P, M)`` carry a leading class axis and the
    whole table is one batched einsum over it (no per-class Python loop).
    Bitwise identical to stacking the per-class calls: every contraction
    sums the same integer-valued float32 terms.
    """
    freef = free.astype(jnp.float32)
    free_after = freef[None, :] - mem_all           # (P, M)
    elig = v[None] <= free_after[..., None]         # (P, M, N)
    if metric == "partial":
        ba = base[None, :, None, :] + mw_all        # (P, M, A, N)
        counted = (ba > 0) & (ba < v[None, :, None, :])
        f_after = jnp.sum(
            jnp.where(counted & elig[:, :, None, :], v[None, :, None, :], 0.0),
            axis=-1,
        )
    else:  # blocked: counted_after = (base > 0) | (mw > 0)
        cb = base > 0                               # (M, N)
        s_occ = jnp.sum(jnp.where(cb[None] & elig, v[None], 0.0), axis=-1)  # (P, M)
        cross = jnp.einsum(
            "pmn,pman->pma", jnp.where(~cb[None] & elig, v[None], 0.0), mp_all
        )  # (P, M, A)
        f_after = s_occ[..., None] + cross
    return f_after - f_before[None, :, None]


def make_frag_fn(
    metric: str = "blocked",
    use_kernel: bool = False,
    model: mig.DeviceModel = mig.A100_80GB,
    interpret: Optional[bool] = None,
):
    """(N, S) occupancy -> (N,) F scores; Pallas kernel when ``use_kernel``
    (``interpret`` as in :func:`repro.kernels.interpret_mode`)."""
    if use_kernel:
        from repro.kernels.fragscore import fragscore as _k

        w = jnp.asarray(model.placement_masks, dtype=jnp.float32)
        v = jnp.asarray(model.placement_mem, dtype=jnp.float32)
        return lambda occ: _k.fragscore(occ, w, v, metric=metric, interpret=interpret)
    tables = jcluster.tables_for(model)
    return functools.partial(jcluster.frag_scores, metric=metric, tables=tables)


def make_delta_fn(
    spec: mig.ClusterSpec,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
):
    """Fused Pallas ΔF dispatch: ``(base, free, f, pid) -> (M, A)``.

    Lowers the engine's dry-run ΔF table to the
    :func:`repro.kernels.fragscore.fragscore.delta_from_base` kernel with
    **per-model dispatch**: one launch per distinct
    :class:`~repro.core.mig.DeviceModel` of ``spec`` (the group's GPU ids
    are static, so each launch sees one placement table with static
    shapes), scattered back into the padded ``(M, A)`` layout the
    masked-refinement select consumes.  This is how ``use_kernel`` works on
    *mixed* fleets — the occupancy-based ``fragscore`` kernel still
    requires a homogeneous spec (it bakes in one table), but the ΔF path
    only needs per-group window counts.  ``interpret`` as in
    :func:`repro.kernels.interpret_mode`.
    """
    from repro.kernels.fragscore import fragscore as _k

    tables = spec_tables(spec)
    groups = spec.model_groups()  # static (model, numpy GPU-id array) pairs
    a = int(tables.profile_rows.shape[-1])

    def delta_fn(base, free, f, pid):
        out = jnp.zeros((base.shape[0], a), jnp.float32)
        for k, (_, rows) in enumerate(groups):
            ridx = jnp.asarray(rows)
            d = _k.delta_from_base(
                base[ridx],
                free[ridx],
                tables.V[k],
                tables.maskwin[k, pid],
                tables.maskpos[k, pid],
                tables.profile_mem[k, pid],
                f[ridx],
                metric=metric,
                interpret=interpret,
            )
            out = out.at[ridx].set(d)
        return out

    return delta_fn


def _effective_keys(pspec: PolicySpec):
    """Static ``((base, sign), …)`` kernel encoding of a spec's keys.

    Request-scoped keys (:data:`~repro.core.policy.REQUEST_KEYS` bases) are
    constant over one request's candidate table — they never narrow the
    refinement and never vary a winner-key comparison — so the fused
    kernels drop them (``PolicySpec.argmin_fusable`` guarantees everything
    else packs).
    """
    return tuple(
        (key_base(k), -1.0 if k.startswith("-") else 1.0)
        for k in pspec.keys
        if key_base(k) not in REQUEST_KEYS
    )


def _lex_pick_rows(cand: jax.Array, l: int):
    """Merge fused-select winner rows ``(ΣT, L+3)`` to ``(gpu, col, ok)``.

    Rows are ``[signed keys…, gpu, col, ok]`` per tile (keys BIG when not
    ok); the lexicographic refinement over ``(keys…, gpu, col)`` reproduces
    :func:`_lower_select`'s total order — within a tile the kernel already
    resolved ties by ascending ``(gpu, col)``, and across tiles/groups the
    explicit gpu/col columns do.  All-infeasible events resolve to
    ``(0, 0, False)``, exactly like the jnp lowering.
    """
    ok = cand[:, l + 2] > 0
    mask = ok
    for i in range(l + 2):
        masked = jnp.where(mask, cand[:, i], _BIG)
        mask = mask & (masked == masked.min())
    j = jnp.argmax(mask)
    any_ok = ok.any()
    gpu = jnp.where(any_ok, cand[j, l], 0.0).astype(jnp.int32)
    col = jnp.where(any_ok, cand[j, l + 1], 0.0).astype(jnp.int32)
    return gpu, col, any_ok


def make_select_fn(
    spec: mig.ClusterSpec,
    pspec: PolicySpec,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
):
    """Fused Pallas select dispatch: ``(base, free, f, pid) -> (gpu, aidx, ok)``.

    Lowers the whole select stage — ΔF table *and* the masked lexicographic
    argmin — to :func:`repro.kernels.fragscore.fragscore.select_from_base`
    with per-model dispatch over ``spec.model_groups()`` (one launch per
    distinct :class:`~repro.core.mig.DeviceModel`, padded H200-141GB
    included).  Each launch returns only per-tile winner rows; the
    ``(M, A)`` score table never round-trips through HBM.  Requires
    ``pspec.argmin_fusable`` (every key base packable in-kernel).
    """
    from repro.kernels.fragscore import fragscore as _k

    tables = spec_tables(spec)
    groups = spec.model_groups()
    keys = _effective_keys(pspec)
    l = len(keys)
    arange_n = jnp.arange(int(tables.V.shape[-1]), dtype=jnp.int32)

    def select_fn(base, free, f, pid):
        cand = []
        for k, (_, rows) in enumerate(groups):
            ridx = jnp.asarray(rows)
            rowsel = tables.profile_rows[k, pid][None, :] == arange_n[:, None]
            cand.append(
                _k.select_from_base(
                    base[ridx],
                    free[ridx],
                    f[ridx],
                    jnp.asarray(rows, dtype=jnp.float32),
                    tables.V[k],
                    tables.maskwin[k, pid],
                    tables.maskpos[k, pid],
                    tables.profile_mem[k, pid],
                    rowsel,
                    tables.profile_valid[k, pid],
                    tables.profile_anchors[k, pid],
                    keys=keys,
                    metric=metric,
                    interpret=interpret,
                )
            )
        return _lex_pick_rows(jnp.concatenate(cand, axis=0), l)

    return select_fn


def _merge_top2(cand: jax.Array, l: int):
    """Merge fused migrate candidate pairs ``(P, Q, L+3)`` to per-class
    best + runner-up.

    The cross-tile form of :func:`_lex_top2`: candidates compare by
    ``(keys…, gpu)`` (the kernel resolved in-tile row ties by ascending
    gpu, and gpu values are globally unique across tiles/groups), and the
    runner-up excludes the best row's *gpu* — guarded on ``ok1`` so an
    all-infeasible class keeps the jnp path's ``(0, False)`` shape.
    Returns ``(g1, ok1, a1, k1, g2, ok2, a2, k2)``.
    """
    ok = cand[..., l + 2] > 0                  # (P, Q)
    gpu = cand[..., l]                         # (P, Q) float gpu values
    pa = jnp.arange(cand.shape[0])

    def best(mask):
        for i in range(l):
            masked = jnp.where(mask, cand[..., i], _BIG)
            mask = mask & (masked == masked.min(axis=-1, keepdims=True))
        masked = jnp.where(mask, gpu, _BIG)
        mask = mask & (masked == masked.min(axis=-1, keepdims=True))
        j = jnp.argmax(mask, axis=-1)          # (P,)
        okb = mask.any(axis=-1)
        g = jnp.where(okb, gpu[pa, j], 0.0).astype(jnp.int32)
        aw = jnp.where(okb, cand[pa, j, l + 1], 0.0).astype(jnp.int32)
        return g, okb, aw, cand[pa, j, :l]

    g1, ok1, a1, k1 = best(ok)
    excl = ok & (~ok1[:, None] | (gpu != g1.astype(jnp.float32)[:, None]))
    g2, ok2, a2, k2 = best(excl)
    return g1, ok1, a1, k1, g2, ok2, a2, k2


def make_migrate_fn(
    spec: mig.ClusterSpec,
    pspec: PolicySpec,
    metric: str = "blocked",
    interpret: Optional[bool] = None,
):
    """Fused Pallas migrate-search dispatch for defrag specs.

    Returns ``migrate_fn(base, free, f, base2, free2, f2, rg, rp, kc)``
    producing the per-class top-2 untouched rows *and* the per-victim
    patched-row refinements that :func:`_migrate_search` consumes —
    ``(g1, ok1, a1, k1, g2, ok2, a2, k2, ap, okp, kp)``.  One
    :func:`repro.kernels.fragscore.fragscore.migrate_refine` launch per
    model group; the per-victim pass rides as grid pass 1 of the first
    group's launch (victims gather their own tables per row, so one pass
    covers every victim on any fleet).
    """
    from repro.kernels.fragscore import fragscore as _k

    tables = spec_tables(spec)
    groups = spec.model_groups()
    keys = _effective_keys(pspec)
    l = len(keys)
    p_ = int(tables.profile_rows.shape[1])
    arange_n = jnp.arange(int(tables.V.shape[-1]), dtype=jnp.int32)
    # (K, P, N, A) one-hot feasibility gathers — static per spec
    rowsel_all = (
        tables.profile_rows[:, :, None, :] == arange_n[None, None, :, None]
    ).astype(jnp.float32)

    def migrate_fn(base, free, f, base2, free2, f2, rg, rp, kc):
        victims = (
            base2, free2, f2, rg.astype(jnp.float32),
            tables.V[kc], tables.maskwin[kc, rp], tables.profile_mem[kc, rp],
            tables.profile_rows[kc, rp].astype(jnp.float32),
            tables.profile_valid[kc, rp], tables.profile_anchors[kc, rp],
        )
        cands, out1 = [], None
        for k, (_, rows) in enumerate(groups):
            ridx = jnp.asarray(rows)
            o0, o1 = _k.migrate_refine(
                base[ridx],
                free[ridx],
                f[ridx],
                jnp.asarray(rows, dtype=jnp.float32),
                tables.V[k],
                tables.maskwin[k],
                tables.maskpos[k],
                tables.profile_mem[k],
                rowsel_all[k],
                tables.profile_valid[k],
                tables.profile_anchors[k],
                victims if k == 0 else None,
                keys=keys,
                metric=metric,
                interpret=interpret,
            )
            if o1 is not None:
                out1 = o1
            t0 = o0.shape[0]                   # (T0, P, 2·(L+3)) → (P, 2·T0, L+3)
            cands.append(
                jnp.transpose(o0.reshape(t0, p_, 2, l + 3), (1, 0, 2, 3))
                .reshape(p_, 2 * t0, l + 3)
            )
        merged = _merge_top2(jnp.concatenate(cands, axis=1), l)
        ap = out1[:, l].astype(jnp.int32)
        okp = out1[:, l + 1] > 0
        return merged + (ap, okp, out1[:, :l])

    return migrate_fn


# ---------------------------------------------------------------------------
# PolicySpec lowering: lexicographic keys -> masked refinement argmin
# ---------------------------------------------------------------------------


def _key_tensor(base_key, feasible, free, mem_g, delta, anchors_g, cursor, midx):
    """One scoring key as an (M, A)-broadcastable float32 tensor.

    All key values are integer-valued (ΔF included — see
    :func:`_delta_from_base`), hence exact in float32: the refinement's
    equality comparisons are exact and the lowering matches the host
    interpreter bit-for-bit.
    """
    m, a = feasible.shape
    if base_key == "frag-delta":
        return delta  # (M, A)
    if base_key == "free-slices":
        return (free.astype(jnp.float32) - mem_g)[:, None]  # (M, 1)
    if base_key == "gpu":
        return jnp.arange(m, dtype=jnp.float32)[:, None]
    if base_key == "anchor":
        # real anchor VALUES (``profile_anchors[midx, pid]``), not padded
        # column indexes: on mixed fleets the index<->value mapping differs
        # per model, and the host interpreter compares values — padded
        # (-1) columns are masked infeasible so they never win
        return anchors_g.astype(jnp.float32)  # (M, A)
    if base_key == "rr-distance":
        prio = jnp.mod(jnp.arange(m, dtype=jnp.int32) - cursor, m)
        return prio.astype(jnp.float32)[:, None]
    if base_key == "model-group":
        return midx.astype(jnp.float32)[:, None]
    if base_key in ("tenant", "priority", "wait-age"):
        # request-scoped keys are constant over one request's candidates —
        # a zero tensor never changes the refinement.  Their semantics are
        # cross-request (the wait ring's queue order, policy.queue_order).
        return jnp.zeros((1, 1), jnp.float32)
    raise ValueError(f"unknown scoring key {base_key!r}")  # unreachable


def _lower_select(spec, feasible, free, mem_g, delta, anchors_g, cursor, midx):
    """Compile a spec's key list against the (M, A) feasibility tensor.

    Each key narrows the candidate mask to its minimizers (``-`` prefix
    negates); the first surviving flat index supplies the implicit
    ascending ``(gpu, anchor)`` tie-break — the same total order the host
    interpreter's lexsort produces.  Returns ``(gpu, aidx, ok)``.
    """
    mask = feasible
    for key in spec.keys:
        val = _key_tensor(
            key_base(key), feasible, free, mem_g, delta, anchors_g, cursor, midx
        )
        if key.startswith("-"):
            val = -val
        masked = jnp.where(mask, val, _BIG)
        mask = mask & (masked == masked.min())
    flat = mask.reshape(-1)
    k = jnp.argmax(flat)
    a = feasible.shape[1]
    return k // a, k % a, flat[k]


def _feasibility(base: jax.Array, rows: jax.Array, valid: jax.Array) -> jax.Array:
    """(M, A) bool — anchors whose window has zero occupied slices.

    ``rows (M, A)`` / ``valid (M, A)`` are the per-GPU gathers
    ``profile_rows[midx, pid]`` / ``profile_valid[midx, pid]``.
    """
    overlap = jnp.take_along_axis(base, rows, axis=1)  # (M, A)
    return (overlap == 0) & valid


def _select(spec, base, free, f, metric, tables, midx, vg, pid, cursor,
            delta_fn=None, select_fn=None, gpu_ok=None):
    """Shared decision path: returns (gpu, aidx, ok) for one request.

    ``delta_fn`` (from :func:`make_delta_fn`) routes the ΔF table through
    the fused Pallas kernel; ``select_fn`` (from :func:`make_select_fn`)
    goes further and runs the whole stage — ΔF *and* the masked
    lexicographic argmin — in fused per-model launches; ``None`` uses the
    pure-jnp lowering.  ``gpu_ok`` is an optional (M,) bool availability
    mask (faulted protocols: down GPUs are infeasible regardless of
    occupancy); ``None`` compiles the mask out entirely.
    """
    if select_fn is not None:
        return select_fn(base, free, f, pid)
    rows = tables.profile_rows[midx, pid]  # (M, A)
    valid = tables.profile_valid[midx, pid]  # (M, A)
    mem_g = tables.profile_mem[midx, pid]  # (M,)
    anchors_g = tables.profile_anchors[midx, pid]  # (M, A), -1 where padded
    feasible = _feasibility(base, rows, valid)
    if gpu_ok is not None:
        feasible = feasible & gpu_ok[:, None]
    if spec.requires_delta_f:  # ΔF table only for specs whose keys use it
        if delta_fn is not None:
            delta = delta_fn(base, free, f, pid)
        else:
            delta = _delta_from_base(
                base, free, metric, vg,
                tables.maskwin[midx, pid], tables.maskpos[midx, pid], mem_g, f,
            )
    else:
        delta = None
    return _lower_select(spec, feasible, free, mem_g, delta, anchors_g, cursor, midx)


# ---------------------------------------------------------------------------
# Row-wise / grid-wise refinement variants (the migrate stage's selections)
# ---------------------------------------------------------------------------


def _key_rows(base_key, free, mem_g, delta, anchors_g, cursor, gidx, kidx, num_gpus):
    """One scoring key as a (C, A)-broadcastable tensor for *per-row*
    selection: row ``c`` is an independent single-GPU candidate whose GPU
    index is ``gidx[c]`` and model index ``kidx[c]``."""
    if base_key == "frag-delta":
        return delta  # (C, A)
    if base_key == "free-slices":
        return (free.astype(jnp.float32) - mem_g)[:, None]  # (C, 1)
    if base_key == "gpu":
        return gidx.astype(jnp.float32)[:, None]
    if base_key == "anchor":
        return anchors_g.astype(jnp.float32)  # (C, A)
    if base_key == "rr-distance":
        prio = jnp.mod(gidx.astype(jnp.int32) - cursor, num_gpus)
        return prio.astype(jnp.float32)[:, None]
    if base_key == "model-group":
        return kidx.astype(jnp.float32)[:, None]
    if base_key in ("tenant", "priority", "wait-age"):
        return jnp.zeros((1, 1), jnp.float32)  # request-scoped: constant per request
    raise ValueError(f"unknown scoring key {base_key!r}")  # unreachable


def _refine_rows(spec, feasible, free, mem_g, delta, anchors_g, cursor, gidx,
                 kidx, num_gpus, return_keys=False):
    """Per-row spec selection: one independent argmin along the anchor axis
    of every row of ``feasible (C, A)``.  Returns ``(aidx (C,), ok (C,))``.

    Equivalent to the host interpreter's full select when each row's
    feasible set is confined to its own GPU (GPU-keyed scores are constant
    per row, so only anchor-varying keys act; the implicit ascending-anchor
    tie-break is the first surviving column).

    With ``return_keys`` additionally returns the winner's key values
    ``(C, L)`` (direction prefix applied) — the row's representative in a
    cross-row lexicographic comparison: the grid-wide lex-min equals the
    lex-min over per-row winners compared by ``(keys…, gpu)``, which is
    what the factored migrate search exploits.
    """
    mask = feasible
    vals = []
    for key in spec.keys:
        val = _key_rows(
            key_base(key), free, mem_g, delta, anchors_g, cursor, gidx, kidx,
            num_gpus,
        )
        if key.startswith("-"):
            val = -val
        if return_keys:
            vals.append(jnp.broadcast_to(val, feasible.shape))
        masked = jnp.where(mask, val, _BIG)
        mask = mask & (masked == masked.min(axis=-1, keepdims=True))
    aidx = jnp.argmax(mask, axis=-1)
    ok = mask.any(axis=-1)
    if not return_keys:
        return aidx, ok
    keys = jnp.stack(
        [jnp.take_along_axis(v, aidx[:, None], axis=1)[:, 0] for v in vals],
        axis=-1,
    )  # (C, L)
    return aidx, ok, keys


def _key_grid(base_key, free, mem_g, delta, anchors_g, cursor, midx):
    """One scoring key as a (C, M, A)-broadcastable tensor for *batched
    whole-cluster* selection (one independent (gpu, anchor) argmin per
    leading candidate row): ``free/mem_g (C, M)``, ``delta/anchors_g
    (C, M, A)``."""
    m = free.shape[-1]
    if base_key == "frag-delta":
        return delta
    if base_key == "free-slices":
        return (free.astype(jnp.float32) - mem_g)[..., None]  # (C, M, 1)
    if base_key == "gpu":
        return jnp.arange(m, dtype=jnp.float32)[None, :, None]
    if base_key == "anchor":
        return anchors_g.astype(jnp.float32)
    if base_key == "rr-distance":  # pragma: no cover — defrag+rr is rejected
        prio = jnp.mod(jnp.arange(m, dtype=jnp.int32) - cursor, m)
        return prio.astype(jnp.float32)[None, :, None]
    if base_key == "model-group":
        return midx.astype(jnp.float32)[None, :, None]
    if base_key in ("tenant", "priority", "wait-age"):
        return jnp.zeros((1, 1, 1), jnp.float32)  # request-scoped: constant per request
    raise ValueError(f"unknown scoring key {base_key!r}")  # unreachable


def _refine_grid(spec, feasible, free, mem_g, delta, anchors_g, cursor, midx):
    """Batched whole-cluster spec selection: an independent ``(gpu, anchor)``
    argmin over the trailing (M, A) axes of every leading row of
    ``feasible (C, M, A)``.  Returns ``(gpu (C,), aidx (C,), ok (C,))`` —
    the same total order :func:`_lower_select` produces, per row.
    """
    mask = feasible
    for key in spec.keys:
        val = _key_grid(
            key_base(key), free, mem_g, delta, anchors_g, cursor, midx
        )
        if key.startswith("-"):
            val = -val
        masked = jnp.where(mask, val, _BIG)
        mask = mask & (masked == masked.min(axis=(-2, -1), keepdims=True))
    a = feasible.shape[-1]
    flat = mask.reshape(mask.shape[:-2] + (-1,))
    k = jnp.argmax(flat, axis=-1)
    return k // a, k % a, flat.any(axis=-1)


# ---------------------------------------------------------------------------
# Migrate stage: the batched single-migration defrag search
# ---------------------------------------------------------------------------


class MigrationResult(NamedTuple):
    """Chosen migration of one event (all entries masked by ``mig``)."""

    mig: jax.Array            # () bool — a migration was committed
    gpu: jax.Array            # () int32 — request GPU (= victim's old GPU)
    aidx: jax.Array           # () int32 — request anchor index
    vic_row: jax.Array        # () int32 — victim's ring row
    vic_col: jax.Array        # () int32 — victim's ring column
    vic_gpu: jax.Array        # () int32 — victim's old GPU
    vic_anchor: jax.Array     # () int32 — victim's old anchor value
    vic_pid: jax.Array        # () int32 — victim's demand class
    new_gpu: jax.Array        # () int32 — victim's new GPU
    new_aidx: jax.Array       # () int32 — victim's new anchor index
    new_anchor: jax.Array     # () int32 — victim's new anchor value
    old_mask: jax.Array       # (S,) int32 — victim's old window bitmask
    old_mwin: jax.Array       # (N,) float32 — window counts the old mask held
    new_mask: jax.Array       # (S,) int32 — victim's new window bitmask
    new_mwin: jax.Array       # (N,) float32 — window counts the new mask adds


def _migrate_search_dense(
    spec: PolicySpec,
    metric: str,
    tables: SpecTables,
    midx: jax.Array,
    vg: jax.Array,
    base: jax.Array,
    free: jax.Array,
    f: jax.Array,
    ring_gpu: jax.Array,
    ring_mask: jax.Array,
    ring_pid: jax.Array,
    ring_aidx: jax.Array,
    pid_c: jax.Array,
    cursor: jax.Array,
    want: jax.Array,
) -> MigrationResult:
    """Reference dense form of the single-migration search.

    Materializes the full victim × cluster ``(C, M, A)`` re-placement grid
    (``C`` = every ring slot, dead ones included) and lex-refines it per
    victim — the semantics :func:`_migrate_search` factors into
    ``O(P·M·A + C_live·A)`` work.  Kept as the oracle for the
    factored-vs-dense equivalence test; not used on the engine hot path.
    """
    num_gpus = midx.shape[0]
    rows, cols = ring_gpu.shape
    c = rows * cols
    rg = ring_gpu.reshape(c)                       # (C,) victim gpu
    rm = ring_mask.reshape(c, ring_mask.shape[-1])  # (C, S) victim window
    rp = ring_pid.reshape(c)                       # (C,) victim class
    ra = ring_aidx.reshape(c)                      # (C,) victim anchor index
    present = rm.sum(axis=1) > 0                   # live entries only
    kc = midx[rg]                                  # (C,) victim model index
    vgc = vg[rg]                                   # (C, N) window sizes

    # -- evacuate the victim from its own GPU -------------------------------
    mwin_vic = tables.maskwin[kc, rp, ra]          # (C, N)
    mem_vic = rm.sum(axis=1)                       # (C,) int32
    base_v = base[rg] - mwin_vic                   # (C, N)
    free_v = free[rg] + mem_vic                    # (C,)
    f_v = _frag_from_base(base_v, free_v, metric, vgc)  # (C,)

    # -- re-select the request on the freed GPU -----------------------------
    rows_req = tables.profile_rows[kc, pid_c]      # (C, A)
    valid_req = tables.profile_valid[kc, pid_c]    # (C, A)
    mem_req = tables.profile_mem[kc, pid_c]        # (C,) float32
    anchors_req = tables.profile_anchors[kc, pid_c]  # (C, A)
    overlap_req = jnp.take_along_axis(base_v, rows_req, axis=1)
    feas_req = (overlap_req == 0) & valid_req
    if spec.requires_delta_f:
        delta_req = _delta_from_base(
            base_v, free_v, metric, vgc,
            tables.maskwin[kc, pid_c], tables.maskpos[kc, pid_c],
            mem_req, f_v,
        )
    else:
        delta_req = None
    aidx_req, ok_req = _refine_rows(
        spec, feas_req, free_v, mem_req, delta_req, anchors_req, cursor,
        rg, kc, num_gpus,
    )

    # -- place the request, then re-place the victim anywhere ---------------
    take = lambda t, i: jnp.take_along_axis(  # noqa: E731 — (C, A, ...) @ (C,)
        t, i[:, None, None] if t.ndim == 3 else i[:, None], axis=1
    )[:, 0]
    mask_req = take(tables.profile_masks[kc, pid_c], aidx_req)   # (C, S)
    mwin_req = take(tables.maskwin[kc, pid_c], aidx_req)         # (C, N)
    base2 = base_v + mwin_req                                    # (C, N)
    free2 = free_v - mask_req.sum(axis=1)                        # (C,)
    f2 = _frag_from_base(base2, free2, metric, vgc)              # (C,)

    # whole-cluster tables for the victim's class, with the victim's own
    # GPU row patched to the post-evacuation/post-request state
    rows_all = jnp.transpose(tables.profile_rows[midx], (1, 0, 2))      # (P, M, A)
    valid_all = jnp.transpose(tables.profile_valid[midx], (1, 0, 2))    # (P, M, A)
    anchors_all = jnp.transpose(tables.profile_anchors[midx], (1, 0, 2))
    mem_all = jnp.transpose(tables.profile_mem[midx], (1, 0))           # (P, M)
    overlap_all = jnp.take_along_axis(base[None], rows_all, axis=2)     # (P, M, A)
    feas_all = (overlap_all == 0) & valid_all

    rows_vic = tables.profile_rows[kc, rp]         # (C, A)
    valid_vic = tables.profile_valid[kc, rp]       # (C, A)
    overlap_patch = jnp.take_along_axis(base2, rows_vic, axis=1)
    feas_patch = (overlap_patch == 0) & valid_vic  # (C, A)
    onehot = jnp.arange(num_gpus)[None, :] == rg[:, None]  # (C, M)
    feas_grid = jnp.where(onehot[:, :, None], feas_patch[:, None, :], feas_all[rp])
    free_grid = jnp.where(onehot, free2[:, None], free[None, :])        # (C, M)
    mem_grid = mem_all[rp]                                              # (C, M)
    anchors_grid = anchors_all[rp]                                      # (C, M, A)
    if spec.requires_delta_f:
        mw_all = jnp.transpose(tables.maskwin[midx], (1, 0, 2, 3))      # (P, M, A, N)
        mp_all = jnp.transpose(tables.maskpos[midx], (1, 0, 2, 3))
        delta_all = jnp.stack(  # ΔF per class on the untouched cluster
            [
                _delta_from_base(
                    base, free, metric, vg, mw_all[p], mp_all[p],
                    mem_all[p], f,
                )
                for p in range(mig.NUM_PROFILES)
            ]
        )  # (P, M, A)
        delta_patch = _delta_from_base(
            base2, free2, metric, vgc,
            tables.maskwin[kc, rp], tables.maskpos[kc, rp],
            tables.profile_mem[kc, rp], f2,
        )  # (C, A)
        delta_grid = jnp.where(
            onehot[:, :, None], delta_patch[:, None, :], delta_all[rp]
        )
    else:
        delta_grid = None
    new_gpu, new_aidx, ok_vic = _refine_grid(
        spec, feas_grid, free_grid, mem_grid, delta_grid, anchors_grid,
        cursor, midx,
    )

    # -- score: total cluster fragmentation after both moves ----------------
    kv = midx[new_gpu]                                           # (C,)
    idx3 = (kv, rp, new_aidx)
    mask_new = tables.profile_masks[idx3]                        # (C, S)
    mwin_new = tables.maskwin[idx3]                              # (C, N)
    same = new_gpu == rg
    base_gv = jnp.where(same[:, None], base2, base[new_gpu])     # (C, N)
    free_gv = jnp.where(same, free2, free[new_gpu])              # (C,)
    f_gv_before = _frag_from_base(base_gv, free_gv, metric, vg[new_gpu])
    f_gv_after = _frag_from_base(
        base_gv + mwin_new, free_gv - mask_new.sum(axis=1), metric, vg[new_gpu]
    )
    total = f.sum() - f[rg] + f2 + f_gv_after - f_gv_before      # (C,)

    # -- canonical choice: lex-min (total F, victim gpu, victim anchor) -----
    vic_anchor = tables.profile_anchors[kc, rp, ra]              # (C,)
    cmask = present & ok_req & ok_vic & want
    for val in (total, rg.astype(jnp.float32), vic_anchor.astype(jnp.float32)):
        masked = jnp.where(cmask, val, _BIG)
        cmask = cmask & (masked == masked.min())
    j = jnp.argmax(cmask)
    return MigrationResult(
        mig=cmask[j],
        gpu=rg[j],
        aidx=aidx_req[j].astype(jnp.int32),
        vic_row=(j // cols).astype(jnp.int32),
        vic_col=(j % cols).astype(jnp.int32),
        vic_gpu=rg[j],
        vic_anchor=vic_anchor[j],
        vic_pid=rp[j],
        new_gpu=new_gpu[j].astype(jnp.int32),
        new_aidx=new_aidx[j].astype(jnp.int32),
        new_anchor=tables.profile_anchors[kv[j], rp[j], new_aidx[j]],
        old_mask=rm[j],
        old_mwin=mwin_vic[j],
        new_mask=mask_new[j],
        new_mwin=mwin_new[j],
    )


def _lex_top2(keys: jax.Array, ok: jax.Array):
    """Two lexicographically smallest valid columns per leading row.

    ``keys (B, M, L)`` are ordered key vectors (direction already applied),
    ``ok (B, M)`` their validity; remaining ties break by ascending column
    index — duplicate best keys therefore resolve to the two lowest tied
    columns, in order.  The runner-up excludes the winner's column only
    when a winner exists (``ok1``-guarded): an all-infeasible row keeps
    the full (vacuously empty) mask instead of arbitrarily excluding
    column 0, so ``g2`` carries the same ``argmax``-of-empty-mask value
    (0) as ``g1`` rather than depending on the winner's placeholder.  A
    single-valid-column row yields ``ok2 = False``.  Returns
    ``(g1, ok1, g2, ok2)``, each ``(B,)``.
    """
    def best(mask):
        for l in range(keys.shape[-1]):
            masked = jnp.where(mask, keys[..., l], _BIG)
            mask = mask & (masked == masked.min(axis=-1, keepdims=True))
        return jnp.argmax(mask, axis=-1), mask.any(axis=-1)

    g1, ok1 = best(ok)
    m = keys.shape[1]
    excl = ~ok1[:, None] | (jnp.arange(m)[None, :] != g1[:, None])
    g2, ok2 = best(ok & excl)
    return g1, ok1, g2, ok2


def _migrate_search(
    spec: PolicySpec,
    metric: str,
    tables: SpecTables,
    midx: jax.Array,
    vg: jax.Array,
    base: jax.Array,
    free: jax.Array,
    f: jax.Array,
    ring_gpu: jax.Array,
    ring_mask: jax.Array,
    ring_pid: jax.Array,
    ring_aidx: jax.Array,
    pid_c: jax.Array,
    cursor: jax.Array,
    want: jax.Array,
    delta_fn=None,
    migrate_fn=None,
) -> MigrationResult:
    """Factored masked single-migration search over live ring entries.

    For every candidate victim (a running workload): evacuate it, re-select
    the request on the victim's GPU (the only GPU where feasibility can
    have appeared — the arrival was just rejected everywhere), re-place the
    victim anywhere via the spec's keys, and score the candidate by the
    total cluster fragmentation after both moves.  The winner minimizes
    ``(total F, victim gpu, victim anchor)`` — the host search's canonical
    order.  ``want`` gates the whole stage (scalar bool).

    Unlike :func:`_migrate_search_dense` (the reference oracle), the victim
    re-placement never materializes a ``(C, M, A)`` grid.  Evacuating a
    victim perturbs exactly one GPU row, so the re-placement candidates
    split into the *patched* row (the victim's own GPU after evacuation +
    request placement) and ``M - 1`` *untouched* rows shared by every
    victim of the same demand class:

    * once per event, a per-class ``(P, M, A)`` row refinement over the
      untouched cluster reduces each GPU row to its winning anchor + key
      vector, and :func:`_lex_top2` keeps the best and runner-up row per
      class (the runner-up covers victims whose own GPU is the best row) —
      ``O(P·M·A)``, the per-class table today's ``delta_all`` already paid
      for and then re-broadcast;
    * per victim, only its patched row is refined (``O(C_live·A)``) and
      lex-compared against the class's surviving untouched row (the grid
      lex-min equals the min over row winners compared by ``(keys…,
      gpu)``, anchors having been resolved within each row).

    Dead ring slots are compacted away first: the number of *live* entries
    is bounded by the cluster's total slice count (every running workload
    occupies at least one slice), a static budget ``C_live = min(C, M·S)``
    that a stable argsort of the ``present`` mask fills with live entries
    in ring order.  Decisions are bit-for-bit those of the dense search:
    every key value is integer-valued, hence exact in float32, and the
    winner is unique (two live workloads can never share a (gpu, anchor)).
    """
    num_gpus = midx.shape[0]
    rows, cols = ring_gpu.shape
    c_total = rows * cols
    s = ring_mask.shape[-1]
    rg = ring_gpu.reshape(c_total)                 # (C,) victim gpu
    rm = ring_mask.reshape(c_total, s)             # (C, S) victim window
    rp = ring_pid.reshape(c_total)                 # (C,) victim class
    ra = ring_aidx.reshape(c_total)                # (C,) victim anchor index
    present = rm.sum(axis=1) > 0                   # live entries only

    # -- live-candidate compaction: dead ring slots cost nothing ------------
    c_live = min(c_total, num_gpus * s)
    if c_live < c_total:
        live = jnp.argsort(~present)[:c_live]      # stable: live first, ring order
        rg, rm, rp, ra = rg[live], rm[live], rp[live], ra[live]
        present = present[live]
    else:
        live = jnp.arange(c_total, dtype=jnp.int32)
    kc = midx[rg]                                  # (C,) victim model index
    vgc = vg[rg]                                   # (C, N) window sizes

    # -- evacuate the victim from its own GPU -------------------------------
    mwin_vic = tables.maskwin[kc, rp, ra]          # (C, N)
    mem_vic = rm.sum(axis=1)                       # (C,) int32
    base_v = base[rg] - mwin_vic                   # (C, N)
    free_v = free[rg] + mem_vic                    # (C,)
    f_v = _frag_from_base(base_v, free_v, metric, vgc)  # (C,)

    # -- re-select the request on the freed GPU -----------------------------
    rows_req = tables.profile_rows[kc, pid_c]      # (C, A)
    valid_req = tables.profile_valid[kc, pid_c]    # (C, A)
    mem_req = tables.profile_mem[kc, pid_c]        # (C,) float32
    anchors_req = tables.profile_anchors[kc, pid_c]  # (C, A)
    overlap_req = jnp.take_along_axis(base_v, rows_req, axis=1)
    feas_req = (overlap_req == 0) & valid_req
    if spec.requires_delta_f:
        delta_req = _delta_from_base(
            base_v, free_v, metric, vgc,
            tables.maskwin[kc, pid_c], tables.maskpos[kc, pid_c],
            mem_req, f_v,
        )
    else:
        delta_req = None
    aidx_req, ok_req = _refine_rows(
        spec, feas_req, free_v, mem_req, delta_req, anchors_req, cursor,
        rg, kc, num_gpus,
    )

    # -- place the request on the freed GPU ---------------------------------
    take = lambda t, i: jnp.take_along_axis(  # noqa: E731 — (C, A, ...) @ (C,)
        t, i[:, None, None] if t.ndim == 3 else i[:, None], axis=1
    )[:, 0]
    mask_req = take(tables.profile_masks[kc, pid_c], aidx_req)   # (C, S)
    mwin_req = take(tables.maskwin[kc, pid_c], aidx_req)         # (C, N)
    base2 = base_v + mwin_req                                    # (C, N)
    free2 = free_v - mask_req.sum(axis=1)                        # (C,)
    f2 = _frag_from_base(base2, free2, metric, vgc)              # (C,)

    # -- per-class row winners on the untouched cluster (once per event) ----
    # + per-victim patched-row refinement.  The fused ``migrate_fn`` (from
    # :func:`make_migrate_fn`) runs both in per-model Pallas launches —
    # the per-victim pass riding as grid pass 1 of the first — and returns
    # only the reduced rows; the jnp path below materializes the
    # ``(P, M, A)`` tables and reduces them with :func:`_refine_rows` +
    # :func:`_lex_top2`.
    p_ = mig.NUM_PROFILES
    a_ = tables.profile_rows.shape[-1]
    if migrate_fn is not None:
        (g1, ok1, aw1, kw1, g2, ok2, aw2, kw2, ap, okp, kp) = migrate_fn(
            base, free, f, base2, free2, f2, rg, rp, kc
        )
    else:
        rows_all = jnp.transpose(tables.profile_rows[midx], (1, 0, 2))      # (P, M, A)
        valid_all = jnp.transpose(tables.profile_valid[midx], (1, 0, 2))
        anchors_all = jnp.transpose(tables.profile_anchors[midx], (1, 0, 2))
        mem_all = jnp.transpose(tables.profile_mem[midx], (1, 0))           # (P, M)
        overlap_all = jnp.take_along_axis(base[None], rows_all, axis=2)     # (P, M, A)
        feas_all = (overlap_all == 0) & valid_all
        if spec.requires_delta_f:
            if delta_fn is not None:  # fused Pallas ΔF, one launch per class
                delta_all = jnp.stack([delta_fn(base, free, f, p) for p in range(p_)])
            else:
                mw_all = jnp.transpose(tables.maskwin[midx], (1, 0, 2, 3))  # (P, M, A, N)
                mp_all = jnp.transpose(tables.maskpos[midx], (1, 0, 2, 3))
                delta_all = _delta_from_base_all(
                    base, free, metric, vg, mw_all, mp_all, mem_all, f
                )  # (P, M, A)
        else:
            delta_all = None
        aw, okw, kw = _refine_rows(
            spec,
            feas_all.reshape(p_ * num_gpus, a_),
            jnp.tile(free, p_),
            mem_all.reshape(p_ * num_gpus),
            None if delta_all is None else delta_all.reshape(p_ * num_gpus, a_),
            anchors_all.reshape(p_ * num_gpus, a_),
            cursor,
            jnp.tile(jnp.arange(num_gpus, dtype=jnp.int32), p_),
            jnp.tile(midx, p_),
            num_gpus,
            return_keys=True,
        )
        l_ = kw.shape[-1]
        aw = aw.reshape(p_, num_gpus)
        okw = okw.reshape(p_, num_gpus)
        kw = kw.reshape(p_, num_gpus, l_)
        g1, ok1, g2, ok2 = _lex_top2(kw, okw)      # best + runner-up per class
        pa = jnp.arange(p_)
        kw1, aw1 = kw[pa, g1], aw[pa, g1]          # (P, L), (P,)
        kw2, aw2 = kw[pa, g2], aw[pa, g2]

        # -- per victim: refine its patched row -----------------------------
        rows_vic = tables.profile_rows[kc, rp]     # (C, A)
        valid_vic = tables.profile_valid[kc, rp]   # (C, A)
        mem_vic_c = tables.profile_mem[kc, rp]     # (C,) float32
        anchors_vic = tables.profile_anchors[kc, rp]  # (C, A)
        overlap_patch = jnp.take_along_axis(base2, rows_vic, axis=1)
        feas_patch = (overlap_patch == 0) & valid_vic  # (C, A)
        if spec.requires_delta_f:
            delta_patch = _delta_from_base(
                base2, free2, metric, vgc,
                tables.maskwin[kc, rp], tables.maskpos[kc, rp],
                mem_vic_c, f2,
            )  # (C, A)
        else:
            delta_patch = None
        ap, okp, kp = _refine_rows(
            spec, feas_patch, free2, mem_vic_c, delta_patch, anchors_vic,
            cursor, rg, kc, num_gpus, return_keys=True,
        )

    # -- per victim: best untouched row (excluding its own GPU) -------------
    l_ = kw1.shape[-1]
    use2 = g1[rp] == rg                            # own GPU was the best row
    gu = jnp.where(use2, g2[rp], g1[rp])
    oku = jnp.where(use2, ok2[rp], ok1[rp])
    au = jnp.where(use2, aw2[rp], aw1[rp])
    ku = jnp.where(use2[:, None], kw2[rp], kw1[rp])  # (C, L)

    # -- lex-merge the two row winners: (keys…, gpu) ------------------------
    ku_e = jnp.where(oku[:, None], ku, _BIG)
    kp_e = jnp.where(okp[:, None], kp, _BIG)
    lt = jnp.zeros(ku.shape[0], bool)
    eq = jnp.ones(ku.shape[0], bool)
    for l in range(l_):
        lt = lt | (eq & (ku_e[:, l] < kp_e[:, l]))
        eq = eq & (ku_e[:, l] == kp_e[:, l])
    pick_u = oku & (lt | (eq & (gu < rg)))
    new_gpu = jnp.where(pick_u, gu, rg)
    new_aidx = jnp.where(pick_u, au, ap)
    ok_vic = oku | okp

    # -- score: total cluster fragmentation after both moves ----------------
    kv = midx[new_gpu]                                           # (C,)
    idx3 = (kv, rp, new_aidx)
    mask_new = tables.profile_masks[idx3]                        # (C, S)
    mwin_new = tables.maskwin[idx3]                              # (C, N)
    same = new_gpu == rg
    base_gv = jnp.where(same[:, None], base2, base[new_gpu])     # (C, N)
    free_gv = jnp.where(same, free2, free[new_gpu])              # (C,)
    f_gv_before = _frag_from_base(base_gv, free_gv, metric, vg[new_gpu])
    f_gv_after = _frag_from_base(
        base_gv + mwin_new, free_gv - mask_new.sum(axis=1), metric, vg[new_gpu]
    )
    total = f.sum() - f[rg] + f2 + f_gv_after - f_gv_before      # (C,)

    # -- canonical choice: lex-min (total F, victim gpu, victim anchor) -----
    vic_anchor = tables.profile_anchors[kc, rp, ra]              # (C,)
    cmask = present & ok_req & ok_vic & want
    for val in (total, rg.astype(jnp.float32), vic_anchor.astype(jnp.float32)):
        masked = jnp.where(cmask, val, _BIG)
        cmask = cmask & (masked == masked.min())
    j = jnp.argmax(cmask)
    orig = live[j]                                 # winner's original ring slot
    return MigrationResult(
        mig=cmask[j],
        gpu=rg[j],
        aidx=aidx_req[j].astype(jnp.int32),
        vic_row=(orig // cols).astype(jnp.int32),
        vic_col=(orig % cols).astype(jnp.int32),
        vic_gpu=rg[j],
        vic_anchor=vic_anchor[j],
        vic_pid=rp[j],
        new_gpu=new_gpu[j].astype(jnp.int32),
        new_aidx=new_aidx[j].astype(jnp.int32),
        new_anchor=tables.profile_anchors[kv[j], rp[j], new_aidx[j]],
        old_mask=rm[j],
        old_mwin=mwin_vic[j],
        new_mask=mask_new[j],
        new_mwin=mwin_new[j],
    )


# ---------------------------------------------------------------------------
# Single-decision entry point
# ---------------------------------------------------------------------------


class PolicyDecision(NamedTuple):
    """One placement decision, migration included (``-1`` where n/a)."""

    gpu: jax.Array
    anchor: jax.Array
    ok: jax.Array
    mig: jax.Array
    vic_gpu: jax.Array
    vic_anchor: jax.Array
    new_gpu: jax.Array
    new_anchor: jax.Array


def policy_select_full(
    occ: jax.Array,
    profile_id: jax.Array,
    policy: PolicyLike,
    metric: str = "blocked",
    spec: Optional[mig.ClusterSpec] = None,
    cursor: int = 0,
    workloads: Optional[Sequence[Tuple[int, int, int]]] = None,
) -> PolicyDecision:
    """One placement decision on a raw occupancy, defrag search included.

    ``workloads`` lists the running workloads as ``(gpu, profile_id,
    anchor)`` triples — the allocation table a defrag spec's migration
    search needs (victims).  It is optional (and ignored) for non-defrag
    specs; a defrag spec with no workloads simply has no migration
    candidates.  Matches the host compilation
    (:class:`repro.core.schedulers.MFIDefrag` with an unbounded candidate
    budget) exactly, migration choice included.
    """
    pspec = resolve(policy, engine="batched")
    spec = spec if spec is not None else _default_spec(int(occ.shape[0]))
    tables = spec_tables(spec)
    midx = jnp.asarray(spec.model_index)
    occf = occ.astype(jnp.float32)
    base = jnp.einsum("ms,mns->mn", occf, tables.W[midx])  # (M, N)
    free = tables.slices[midx] - occ.sum(axis=1).astype(jnp.int32)
    vg = tables.V[midx]
    f = _frag_from_base(base, free, metric, vg)
    cur = jnp.int32(cursor)
    gpu, aidx, ok = _select(
        pspec, base, free, f, metric, tables, midx, vg, profile_id, cur
    )
    neg1 = jnp.int32(-1)
    mig_out = (jnp.asarray(False), neg1, neg1, neg1, neg1)
    if pspec.defrag:
        wl = list(workloads) if workloads else []
        cols = max(1, len(wl))
        ring_gpu = np.zeros((1, cols), np.int32)
        ring_mask = np.zeros((1, cols, int(tables.W.shape[2])), np.int32)
        ring_pid = np.zeros((1, cols), np.int32)
        ring_aidx = np.zeros((1, cols), np.int32)
        for i, (g, p, anchor) in enumerate(wl):
            model = spec.model_of(int(g))
            j = model.profiles[int(p)].anchors.index(int(anchor))
            m = model.profiles[int(p)].mem
            ring_gpu[0, i] = g
            ring_mask[0, i, anchor : anchor + m] = 1
            ring_pid[0, i] = p
            ring_aidx[0, i] = j
        res = _migrate_search(
            pspec, metric, tables, midx, vg, base, free, f,
            jnp.asarray(ring_gpu), jnp.asarray(ring_mask),
            jnp.asarray(ring_pid), jnp.asarray(ring_aidx),
            profile_id, cur, want=~ok,
        )
        gpu = jnp.where(res.mig, res.gpu, gpu)
        aidx = jnp.where(res.mig, res.aidx, aidx)
        ok = ok | res.mig
        mig_out = (
            res.mig,
            jnp.where(res.mig, res.vic_gpu, neg1),
            jnp.where(res.mig, res.vic_anchor, neg1),
            jnp.where(res.mig, res.new_gpu, neg1),
            jnp.where(res.mig, res.new_anchor, neg1),
        )
    anchor = jnp.where(ok, tables.profile_anchors[midx[gpu], profile_id, aidx], -1)
    return PolicyDecision(
        gpu=jnp.where(ok, gpu, -1).astype(jnp.int32),
        anchor=anchor.astype(jnp.int32),
        ok=ok,
        mig=mig_out[0],
        vic_gpu=mig_out[1],
        vic_anchor=mig_out[2],
        new_gpu=mig_out[3],
        new_anchor=mig_out[4],
    )


def policy_select(
    occ: jax.Array,
    profile_id: jax.Array,
    policy: PolicyLike,
    metric: str = "blocked",
    spec: Optional[mig.ClusterSpec] = None,
    cursor: int = 0,
    workloads: Optional[Sequence[Tuple[int, int, int]]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One placement decision on a raw occupancy: ``(gpu, anchor, accepted)``.

    Lowers ``policy`` (a registered name or an ad-hoc
    :class:`~repro.core.policy.PolicySpec`) exactly like the scan step (via
    the derived ``base``/``free`` state) and matches the corresponding host
    ``Scheduler.select`` — including rejects — for every batched-capable
    registered policy.  ``spec`` defaults to a homogeneous A100-80GB fleet
    of ``occ.shape[0]`` GPUs; ``cursor`` is the rotation start of stateful
    policies (``SpecScheduler._next``); ``workloads`` supplies the running
    allocations a defrag spec's migration search considers (see
    :func:`policy_select_full`, which also reports the chosen migration).
    """
    d = policy_select_full(
        occ, profile_id, policy, metric=metric, spec=spec, cursor=cursor,
        workloads=workloads,
    )
    return d.gpu, d.anchor, d.ok


# ---------------------------------------------------------------------------
# Scan state and the staged event step
# ---------------------------------------------------------------------------


class ReplicaState(NamedTuple):
    occ: jax.Array        # (M, S) int32 — None when occupancy isn't tracked
    base: jax.Array       # (M, N) float32 — occ @ W[midx]ᵀ, kept incrementally
    free: jax.Array       # (M,) int32
    f: jax.Array          # (M,) float32 — per-GPU F score, kept incrementally
    rr: jax.Array         # () int32 — RoundRobin cursor
    ring_gpu: jax.Array   # (K+2, E) int32 — expiry ring, keyed end_slot % K
    ring_mask: jax.Array  # (K+2, E, S) int32
    ring_pid: jax.Array   # (K+2, E) int32 — defrag specs only, else None
    ring_aidx: jax.Array  # (K+2, E) int32 — defrag specs only, else None
    # wait ring (queued protocols only, else None): parked rejected
    # arrivals, -1 pid marks a free slot.  Each entry keeps its original
    # host-assigned expiry-ring coordinates and absolute end slot — a
    # wait-admit commits with them unchanged (admission is only legal while
    # ``end > t``, so the row is still < one ring revolution ahead and the
    # column stays collision-free).
    wait_pid: jax.Array = None   # (Q,) int32 — demand class, -1 = free slot
    wait_arr: jax.Array = None   # (Q,) int32 — arrival slot
    wait_end: jax.Array = None   # (Q,) int32 — absolute lease deadline
    wait_row: jax.Array = None   # (Q,) int32 — original expiry-ring row
    wait_col: jax.Array = None   # (Q,) int32 — original expiry-ring column
    wait_prio: jax.Array = None  # (Q,) int32 — priority class
    wait_ten: jax.Array = None   # (Q,) int32 — tenant id
    wait_eidx: jax.Array = None  # (Q,) int32 — original event index
    ev: jax.Array = None         # () int32 — running event index (queued only)
    # faulted protocols only (else None): GPU availability, the extra ring
    # planes that make every live entry fully re-queueable on eviction, and
    # the wait ring's retry/backoff bookkeeping.  Appended after ``ev`` so
    # non-faulted pytrees (checkpoints included) are structurally unchanged.
    up: jax.Array = None         # (M,) bool — GPU accepting placements
    ring_end: jax.Array = None   # (K+2, E) int32 — entry's absolute lease deadline
    ring_eidx: jax.Array = None  # (K+2, E) int32 — entry's original event index
    ring_prio: jax.Array = None  # (K+2, E) int32 — entry's priority class
    ring_ten: jax.Array = None   # (K+2, E) int32 — entry's tenant id
    wait_try: jax.Array = None   # (Q,) int32 — re-queue attempts so far
    wait_rdy: jax.Array = None   # (Q,) int32 — earliest admission slot (backoff)


class EventStream(NamedTuple):
    """Host-precomputed per-event scan inputs, each ``(E_max, R)``."""

    pid: np.ndarray        # profile id, -1 for heartbeat/padding lanes
    exp_row: np.ndarray    # ring row (end_slot % K; trash row for padding)
    exp_col: np.ndarray    # ring column (host-assigned, collision-free)
    drain_row: np.ndarray  # ring row to drain when new_slot
    new_slot: np.ndarray   # first event of its slot (drain + maybe sample)
    sample: np.ndarray     # sample metrics of the just-finished slot
    measuring: np.ndarray  # arrival inside the measurement window
    # queued protocols only (None otherwise; shipped to device):
    slot: np.ndarray = None    # int32 — event slot (the wait stage's clock)
    end: np.ndarray = None     # int32 — absolute end slot of the arrival
    prio: np.ndarray = None    # int32 — priority class of the arrival
    tenant: np.ndarray = None  # int32 — tenant id of the arrival
    wlive: np.ndarray = None   # bool — real event (not padding/sentinel)
    # faulted protocols only (None otherwise; shipped to device).  Lanes are
    # (E_max, R, M) and set on the *first* event of each slot only, so the
    # fault stage applies each slot's fail/recover set exactly once.
    fail: np.ndarray = None     # bool — GPU m fails at this slot
    recover: np.ndarray = None  # bool — GPU m recovers at this slot


class EventMeta(NamedTuple):
    """Host-only per-event annotations (never shipped to device), ``(E_max, R)``.

    Used by :mod:`repro.sim.replay` to reconstruct and validate occupancy
    trajectories from a decision trace.
    """

    slot: np.ndarray  # arrival/heartbeat slot (total_slots for padding)
    end: np.ndarray   # absolute end slot of the arrival (0 for non-arrivals)


class EventTrace(NamedTuple):
    """Per-event scan outputs, each ``(E_max, R)``; counters and metric sums
    are reduced host-side against the host-known flags of the stream.

    Fields past ``aidx`` are compiled in per configuration and ``None``
    otherwise: the slot-boundary metrics for protocols with
    ``boundary_metrics`` (steady), the ``post_*`` metrics for protocols
    with ``post_metrics`` (cumulative), the ``mig_*`` fields for defrag
    specs (the victim's old placement and its new one).
    """

    ok: jax.Array        # arrival accepted
    gpu: jax.Array       # chosen GPU (undefined when not accepted)
    aidx: jax.Array      # chosen anchor index (undefined when not accepted)
    free_sum: jax.Array = None  # Σ free slices at slot boundary (pre-drain)
    active: jax.Array = None    # active-GPU count at slot boundary (pre-drain)
    frag: jax.Array = None      # cluster-mean F at slot boundary (pre-drain)
    post_free: jax.Array = None    # Σ free slices after the commit
    post_active: jax.Array = None  # active-GPU count after the commit
    post_frag: jax.Array = None    # cluster-mean F after the commit
    mig: jax.Array = None          # a migration was committed at this event
    mig_from_gpu: jax.Array = None     # victim's old GPU (-1 when no mig)
    mig_from_anchor: jax.Array = None  # victim's old anchor value
    mig_to_gpu: jax.Array = None       # victim's new GPU
    mig_to_anchor: jax.Array = None    # victim's new anchor value
    # queued protocols only: the wait-ring stage's outputs at this event
    parked: jax.Array = None       # rejected arrival entered the wait ring
    wadm_eidx: jax.Array = None    # original event index of the wait-admit (-1 none)
    wadm_gpu: jax.Array = None     # wait-admit's chosen GPU (-1 none)
    wadm_aidx: jax.Array = None    # wait-admit's chosen anchor index (-1 none)
    # faulted protocols only: the fault stage's eviction accounting
    evicted: jax.Array = None      # int32 — live entries evicted by failures
    evict_lost: jax.Array = None   # int32 — evictions dropped (ring full / no budget)
    evict_esum: jax.Array = None   # int32 — Σ original event indexes of evictions


def _init_state(
    tables: SpecTables,
    midx: jax.Array,
    ring_rows: int,
    ring_cols: int,
    track_occ: bool,
    track_alloc: bool,
    wait_slots: int = 0,
    faulted: bool = False,
) -> ReplicaState:
    num_gpus = midx.shape[0]
    s = tables.W.shape[2]
    n = tables.W.shape[1]
    q = wait_slots
    zq = jnp.zeros((q,), jnp.int32) if q else None
    # faulted protocols track every live entry's full identity on the ring
    # (demand class via the defrag planes + the deadline/priority/tenant/
    # event-index planes below) so an eviction can re-queue it losslessly
    track_alloc = track_alloc or faulted
    zr = (lambda: jnp.zeros((ring_rows, ring_cols), jnp.int32)) if faulted else (lambda: None)
    return ReplicaState(
        occ=jnp.zeros((num_gpus, s), jnp.int32) if track_occ else None,
        base=jnp.zeros((num_gpus, n), jnp.float32),
        free=tables.slices[midx].astype(jnp.int32),
        f=jnp.zeros((num_gpus,), jnp.float32),
        rr=jnp.int32(0),
        ring_gpu=jnp.zeros((ring_rows, ring_cols), jnp.int32),
        ring_mask=jnp.zeros((ring_rows, ring_cols, s), jnp.int32),
        ring_pid=jnp.zeros((ring_rows, ring_cols), jnp.int32) if track_alloc else None,
        ring_aidx=jnp.zeros((ring_rows, ring_cols), jnp.int32) if track_alloc else None,
        wait_pid=jnp.full((q,), -1, jnp.int32) if q else None,
        wait_arr=zq,
        wait_end=zq,
        wait_row=zq,
        wait_col=zq,
        wait_prio=zq,
        wait_ten=zq,
        wait_eidx=zq,
        ev=jnp.int32(0) if q else None,
        up=jnp.ones((num_gpus,), bool) if faulted else None,
        ring_end=zr(),
        ring_eidx=zr(),
        ring_prio=zr(),
        ring_ten=zr(),
        wait_try=zq if faulted else None,
        wait_rdy=zq if faulted else None,
    )


@dataclasses.dataclass(frozen=True)
class EngineCore:
    """The staged scan body: one event step, composed from stages.

    Static configuration (``spec``, ``protocol``, ``metric``) selects which
    stages are compiled in; the array members (stacked tables, model-index
    gather, per-GPU window sizes) are closed over as constants.  Stage
    order within one event is the semantic order of the simulators:
    *measure* the just-finished slot (steady), *expire* this slot's ring
    row, decode the *arrival*, *select*, *migrate* (defrag specs, on
    reject), *commit*, and *measure* the post-commit state (cumulative).
    """

    spec: PolicySpec
    protocol: Protocol
    metric: str
    tables: SpecTables
    midx: jax.Array
    vg: jax.Array
    frag_fn: Optional[object] = None
    delta_fn: Optional[object] = None
    select_fn: Optional[object] = None
    migrate_fn: Optional[object] = None
    wait_patience: int = 0  # queued protocols: max slots a request may wait

    # -- stages --------------------------------------------------------------
    def _stage_boundary_measure(self, st: ReplicaState):
        """Slot-boundary metrics (state == end of slot t-1); reduced
        host-side against the ``sample`` flags of the stream."""
        frag = st.f.mean()
        free_sum = st.free.sum()
        active = (st.free < self.tables.slices[self.midx]).sum()
        return frag, free_sum, active

    def _stage_expire(self, st: ReplicaState, drain_row, new_slot):
        """Drain this slot's expiry-ring row (first event of the slot only)."""
        ns = new_slot.astype(jnp.int32)
        rel_gpu = st.ring_gpu[drain_row]  # (E,)
        rel_mask = st.ring_mask[drain_row] * ns  # (E, S)
        occ = None if st.occ is None else st.occ.at[rel_gpu].add(-rel_mask)
        rel_win = jnp.einsum(
            "es,ens->en", rel_mask.astype(jnp.float32), self.tables.W[self.midx[rel_gpu]]
        )  # (E, N) — window counts each release frees, per its GPU's model
        base = st.base.at[rel_gpu].add(-rel_win)
        free = st.free.at[rel_gpu].add(rel_mask.sum(axis=1))
        # rescore exactly the touched rows — through the Pallas kernel when it
        # is routed in (occ is materialized then), else from the window counts
        f = st.f.at[rel_gpu].set(
            self.frag_fn(occ[rel_gpu])
            if self.frag_fn is not None
            else _frag_from_base(
                base[rel_gpu], free[rel_gpu], self.metric, self.vg[rel_gpu]
            )
        )
        ring_mask = st.ring_mask.at[drain_row].set(st.ring_mask[drain_row] * (1 - ns))
        return st._replace(
            occ=occ, base=base, free=free, f=f, ring_mask=ring_mask
        )

    def _btable(self):
        """Static backoff lookup: ``btable[k]`` is the wait before becoming
        eligible again after re-queue attempt ``k`` (1-based; exponential
        ``fault_backoff * 2**(k-1)``, clamped at the retry budget)."""
        b, r = self.protocol.fault_backoff, self.protocol.fault_retries
        return jnp.asarray(
            [b * 2 ** max(0, k - 1) for k in range(r + 2)], jnp.int32
        )

    def _stage_fault(self, st: ReplicaState, fail_v, rec_v, t):
        """Faulted protocols: apply this slot's GPU fail/recover lanes.

        Runs after the expire drain (a lease ending the very slot its GPU
        dies still completes) and before the wait stage (evictions are
        eligible for re-admission only after their backoff).  A failing GPU
        is cleared wholesale — every live allocation is a ring entry, so
        zeroing its occupancy/base/free/f equals subtracting each eviction
        one by one (a down GPU reads empty and inactive in every metric,
        ``F = 0`` exactly like the initial state) — and masked out of
        feasibility via ``up`` until its recover lane.  Evicted entries are
        re-queued into the wait ring in flat ``(row, col)`` ring order,
        filling free slots in ascending index order; whatever exceeds the
        free capacity (or everything, when the retry budget is zero) is a
        final loss, counted in the trace.  Returns
        ``(st, evicted, evict_lost, evict_esum)``.
        """
        up = (st.up | rec_v) & ~fail_v  # presampling alternates fail/recover
        rows, cols = st.ring_gpu.shape
        live = st.ring_mask.sum(axis=-1) > 0          # (K+2, E)
        evict = fail_v[st.ring_gpu] & live            # stale slots: live=False
        fi = fail_v.astype(jnp.int32)
        occ = None if st.occ is None else st.occ * (1 - fi)[:, None]
        base = jnp.where(fail_v[:, None], 0.0, st.base)
        free = jnp.where(
            fail_v, self.tables.slices[self.midx].astype(jnp.int32), st.free
        )
        f = jnp.where(fail_v, 0.0, st.f)
        ring_mask = st.ring_mask * (1 - evict.astype(jnp.int32))[:, :, None]
        st = st._replace(
            up=up, occ=occ, base=base, free=free, f=f, ring_mask=ring_mask
        )

        ev_flat = evict.reshape(-1)                   # flat (row, col) order
        n_ev = ev_flat.sum().astype(jnp.int32)
        esum = (st.ring_eidx.reshape(-1) * ev_flat.astype(jnp.int32)).sum()
        if self.protocol.fault_retries < 1:
            return st, n_ev, n_ev, esum  # no retry budget: immediate losses

        q = st.wait_pid.shape[0]
        c = ev_flat.shape[0]
        rank = jnp.cumsum(ev_flat.astype(jnp.int32)) - 1
        freeslot = st.wait_pid < 0
        nfree = freeslot.sum()
        slot_order = jnp.argsort(~freeslot)  # stable: free slots, ascending
        can = ev_flat & (rank < nfree)
        # rank-based scatter: eviction #k lands in the k-th free wait slot;
        # overflow targets index q and is dropped (a final loss)
        tgt = jnp.where(can, slot_order[jnp.clip(rank, 0, q - 1)], q)
        idx = jnp.arange(c, dtype=jnp.int32)
        tcol = jnp.broadcast_to(t, (c,)).astype(jnp.int32)

        def put(arr, v):
            return arr.at[tgt].set(v, mode="drop")

        st = st._replace(
            wait_pid=put(st.wait_pid, st.ring_pid.reshape(-1)),
            wait_arr=put(st.wait_arr, tcol),
            wait_end=put(st.wait_end, st.ring_end.reshape(-1)),
            wait_row=put(st.wait_row, idx // cols),
            wait_col=put(st.wait_col, idx % cols),
            wait_prio=put(st.wait_prio, st.ring_prio.reshape(-1)),
            wait_ten=put(st.wait_ten, st.ring_ten.reshape(-1)),
            wait_eidx=put(st.wait_eidx, st.ring_eidx.reshape(-1)),
            wait_try=put(st.wait_try, jnp.ones((c,), jnp.int32)),
            wait_rdy=put(st.wait_rdy, tcol + self._btable()[1]),
        )
        lost = n_ev - can.sum().astype(jnp.int32)
        return st, n_ev, lost, esum

    def _stage_select(self, st: ReplicaState, pid_c, valid):
        """Place (or reject) the arrival; ``pid == -1`` lanes are no-ops."""
        gpu, aidx, ok = _select(
            self.spec, st.base, st.free, st.f, self.metric, self.tables,
            self.midx, self.vg, pid_c, st.rr, delta_fn=self.delta_fn,
            select_fn=self.select_fn, gpu_ok=st.up,
        )
        return gpu, aidx, ok & valid

    def _stage_migrate(self, st: ReplicaState, pid_c, valid, gpu, aidx, ok):
        """Defrag search on reject; commits the victim's move in place."""
        res = _migrate_search(
            self.spec, self.metric, self.tables, self.midx, self.vg,
            st.base, st.free, st.f,
            st.ring_gpu, st.ring_mask, st.ring_pid, st.ring_aidx,
            pid_c, st.rr, want=valid & ~ok, delta_fn=self.delta_fn,
            migrate_fn=self.migrate_fn,
        )
        mi = res.mig.astype(jnp.int32)
        mf = res.mig.astype(jnp.float32)
        base = st.base.at[res.vic_gpu].add(-res.old_mwin * mf)
        base = base.at[res.new_gpu].add(res.new_mwin * mf)
        free = st.free.at[res.vic_gpu].add(res.old_mask.sum() * mi)
        free = free.at[res.new_gpu].add(-res.new_mask.sum() * mi)
        occ = st.occ
        if occ is not None:
            occ = occ.at[res.vic_gpu].add(-res.old_mask * mi)
            occ = occ.at[res.new_gpu].add(res.new_mask * mi)
        rc = (res.vic_row, res.vic_col)
        ring_mask = st.ring_mask.at[rc].add((res.new_mask - res.old_mask) * mi)
        ring_gpu = st.ring_gpu.at[rc].set(
            jnp.where(res.mig, res.new_gpu, st.ring_gpu[rc])
        )
        ring_aidx = st.ring_aidx.at[rc].set(
            jnp.where(res.mig, res.new_aidx, st.ring_aidx[rc])
        )
        st = st._replace(
            occ=occ, base=base, free=free,
            ring_gpu=ring_gpu, ring_mask=ring_mask, ring_aidx=ring_aidx,
        )
        gpu = jnp.where(res.mig, res.gpu, gpu)
        aidx = jnp.where(res.mig, res.aidx, aidx)
        ok = ok | res.mig
        return st, gpu, aidx, ok, res

    def _stage_commit(
        self, st: ReplicaState, pid_c, gpu, aidx, ok, exp_row, exp_col,
        mig_res: Optional[MigrationResult], meta=None,
    ):
        """Commit the accepted placement: occupancy/window/free updates, the
        expiry-ring insert, the rescore of touched rows, the cursor.

        ``meta`` (faulted protocols: ``(end, prio, ten, eidx)``) writes the
        entry's identity into the extra ring planes so a later eviction can
        re-queue it losslessly; ``None`` compiles those writes out."""
        tables, midx, vg = self.tables, self.midx, self.vg
        oki = ok.astype(jnp.int32)
        gpu_c = jnp.where(ok, gpu, 0).astype(jnp.int32)
        kg = midx[gpu_c]  # chosen GPU's model index
        mask = tables.profile_masks[kg, pid_c, aidx] * oki  # (S,)
        mwin = tables.maskwin[kg, pid_c, aidx] * oki.astype(jnp.float32)  # (N,)
        occ = None if st.occ is None else st.occ.at[gpu_c].add(mask)
        base = st.base.at[gpu_c].add(mwin)
        free = st.free.at[gpu_c].add(-mask.sum())
        f = st.f.at[gpu_c].set(
            self.frag_fn(occ[gpu_c][None])[0]
            if self.frag_fn is not None
            else _frag_from_base(
                base[gpu_c][None], free[gpu_c][None], self.metric, vg[gpu_c][None]
            )[0]
        )
        if mig_res is not None:
            # rescore the victim's landing GPU too (its old GPU is gpu_c)
            g2 = jnp.where(mig_res.mig, mig_res.new_gpu, gpu_c)
            f = f.at[g2].set(
                self.frag_fn(occ[g2][None])[0]
                if self.frag_fn is not None
                else _frag_from_base(
                    base[g2][None], free[g2][None], self.metric, vg[g2][None]
                )[0]
            )
        rr = st.rr
        if self.spec.stateful_cursor:  # advance the cursor past the chosen GPU
            rr = jnp.where(ok, (gpu_c + 1) % midx.shape[0], rr).astype(jnp.int32)
        ring_gpu = st.ring_gpu.at[exp_row, exp_col].set(
            jnp.where(ok, gpu_c, st.ring_gpu[exp_row, exp_col])
        )
        ring_mask = st.ring_mask.at[exp_row, exp_col].add(mask)
        ring_pid, ring_aidx = st.ring_pid, st.ring_aidx
        if ring_pid is not None:
            ring_pid = ring_pid.at[exp_row, exp_col].set(
                jnp.where(ok, pid_c, ring_pid[exp_row, exp_col])
            )
            ring_aidx = ring_aidx.at[exp_row, exp_col].set(
                jnp.where(ok, aidx.astype(jnp.int32), ring_aidx[exp_row, exp_col])
            )
        ring_end, ring_eidx = st.ring_end, st.ring_eidx
        ring_prio, ring_ten = st.ring_prio, st.ring_ten
        if meta is not None and ring_end is not None:
            end_m, prio_m, ten_m, eidx_m = meta

            def put_meta(plane, v):
                return plane.at[exp_row, exp_col].set(
                    jnp.where(ok, v.astype(jnp.int32), plane[exp_row, exp_col])
                )

            ring_end = put_meta(ring_end, end_m)
            ring_prio = put_meta(ring_prio, prio_m)
            ring_ten = put_meta(ring_ten, ten_m)
            ring_eidx = put_meta(ring_eidx, eidx_m)
        return st._replace(
            occ=occ, base=base, free=free, f=f, rr=rr,
            ring_gpu=ring_gpu, ring_mask=ring_mask,
            ring_pid=ring_pid, ring_aidx=ring_aidx,
            ring_end=ring_end, ring_eidx=ring_eidx,
            ring_prio=ring_prio, ring_ten=ring_ten,
        )

    def _stage_wait(self, st: ReplicaState, t, wlive):
        """Queued protocols: prune the wait ring, then try to admit its head.

        Entries whose lease deadline passed (``end <= t``) or whose wait
        exceeded the patience budget are dropped — final rejects (they
        simply never appear as a wait-admit in the trace).  Among the
        survivors the *head* is the lexicographic minimum of the spec's
        queue order (:func:`repro.core.policy.queue_order`; the original
        event index breaks ties FIFO).  The head re-enters the spec's
        placement selection; on acceptance it commits with its original
        host-assigned ring coordinates (its absolute end slot is
        unchanged, so the expiry row is still less than one ring
        revolution ahead and the column is collision-free).  One admission
        attempt per event — waiting requests drain across the stream's
        events (heartbeats included), always ahead of the concurrent
        arrival.  ``wlive`` gates the stage to real events (padding and
        sentinel lanes have no host-side clock).
        """
        present = st.wait_pid >= 0
        age = t - st.wait_arr
        if self.protocol.faulted:
            # SLA-aware retry: a patience overrun re-arms with exponential
            # backoff while the retry budget and the lease allow it, and
            # becomes a final drop only past the budget.  Entries inside
            # their backoff window (``wait_rdy > t``) are skipped as head.
            overdue = wlive & present & (age > self.wait_patience)
            rearm = (
                overdue
                & (st.wait_try < self.protocol.fault_retries)
                & (st.wait_end > t)
            )
            drop = wlive & present & ((st.wait_end <= t) | (overdue & ~rearm))
            keep = present & ~drop
            try_new = jnp.where(rearm, st.wait_try + 1, st.wait_try)
            btable = self._btable()
            st = st._replace(
                wait_arr=jnp.where(rearm, t, st.wait_arr),
                wait_try=try_new,
                wait_rdy=jnp.where(
                    rearm,
                    t + btable[jnp.clip(try_new, 0, btable.shape[0] - 1)],
                    st.wait_rdy,
                ),
            )
            mask = keep & wlive & (st.wait_rdy <= t)
        else:
            drop = wlive & ((st.wait_end <= t) | (age > self.wait_patience))
            keep = present & ~drop
            mask = keep & wlive
        for key in queue_order(self.spec):
            base_k = key_base(key)
            if base_k == "priority":
                val = st.wait_prio.astype(jnp.float32)
            elif base_k == "wait-age":
                val = age.astype(jnp.float32)
            else:  # tenant
                val = st.wait_ten.astype(jnp.float32)
            if key.startswith("-"):
                val = -val
            masked = jnp.where(mask, val, _BIG)
            mask = mask & (masked == masked.min())
        fifo = jnp.where(mask, st.wait_eidx, jnp.int32(2**31 - 1))
        j = jnp.argmin(fifo)
        head = mask.any()

        pid_w = jnp.maximum(st.wait_pid[j], 0)
        gpu, aidx, sel_ok = _select(
            self.spec, st.base, st.free, st.f, self.metric, self.tables,
            self.midx, self.vg, pid_w, st.rr, delta_fn=self.delta_fn,
            select_fn=self.select_fn, gpu_ok=st.up,
        )
        ok_w = sel_ok & head
        meta_w = (
            (st.wait_end[j], st.wait_prio[j], st.wait_ten[j], st.wait_eidx[j])
            if self.protocol.faulted else None
        )
        st = self._stage_commit(
            st, pid_w, gpu, aidx, ok_w, st.wait_row[j], st.wait_col[j], None,
            meta=meta_w,
        )
        wait_pid = jnp.where(keep, st.wait_pid, jnp.int32(-1))
        wait_pid = wait_pid.at[j].set(jnp.where(ok_w, jnp.int32(-1), wait_pid[j]))
        st = st._replace(wait_pid=wait_pid)
        eidx = jnp.where(ok_w, st.wait_eidx[j], jnp.int32(-1))
        return st, eidx, gpu.astype(jnp.int32), aidx.astype(jnp.int32), ok_w

    def _stage_park(
        self, st: ReplicaState, pid_c, can, t, end, prio, ten, exp_row, exp_col
    ):
        """Insert a rejected arrival into the first free wait-ring slot
        (``can`` already folds in validity, rejection and free capacity)."""
        freeslot = st.wait_pid < 0
        j = jnp.argmax(freeslot)

        def put(arr, v):
            return arr.at[j].set(jnp.where(can, v, arr[j]))

        st = st._replace(
            wait_pid=put(st.wait_pid, pid_c),
            wait_arr=put(st.wait_arr, t),
            wait_end=put(st.wait_end, end),
            wait_row=put(st.wait_row, exp_row),
            wait_col=put(st.wait_col, exp_col),
            wait_prio=put(st.wait_prio, prio),
            wait_ten=put(st.wait_ten, ten),
            wait_eidx=put(st.wait_eidx, st.ev),
        )
        if self.protocol.faulted:  # fresh parks: no retries used, no backoff
            st = st._replace(
                wait_try=put(st.wait_try, jnp.int32(0)),
                wait_rdy=put(st.wait_rdy, t),
            )
        return st

    def _stage_post_measure(self, st: ReplicaState):
        """Post-commit metrics (the cumulative protocol samples every event)."""
        return st.f.mean(), st.free.sum(), (st.free < self.tables.slices[self.midx]).sum()

    # -- the composed step ---------------------------------------------------
    def step(self, st: ReplicaState, x):
        """One event of one replica.  Each stage runs under a named scope,
        ``stage.<name>`` (:data:`repro.obs.STAGES`), which reaches every op
        it emits, so a profile's device time can be split by stage
        (:func:`repro.obs.stage_map`)."""
        if self.protocol.faulted:
            (pid, exp_row, exp_col, drain_row, new_slot,
             t, end, prio, ten, wlive, fail_v, rec_v) = x
        elif self.protocol.queued:
            (pid, exp_row, exp_col, drain_row, new_slot,
             t, end, prio, ten, wlive) = x
        else:
            pid, exp_row, exp_col, drain_row, new_slot = x

        frag = free_sum = active = None
        if self.protocol.boundary_metrics:
            with jax.named_scope("stage.measure"):
                frag, free_sum, active = self._stage_boundary_measure(st)

        with jax.named_scope("stage.expire"):
            st = self._stage_expire(st, drain_row, new_slot)

        evicted = evict_lost = evict_esum = None
        if self.protocol.faulted:  # after expire: same-slot completions win
            with jax.named_scope("stage.fault"):
                st, evicted, evict_lost, evict_esum = self._stage_fault(
                    st, fail_v, rec_v, t
                )

        wadm_eidx = wadm_gpu = wadm_aidx = parked = None
        if self.protocol.queued:  # waiting requests admit ahead of the arrival
            with jax.named_scope("stage.queue"):
                st, wadm_eidx, wadm_gpu, wadm_aidx, ok_w = self._stage_wait(
                    st, t, wlive
                )
                wadm_gpu = jnp.where(ok_w, wadm_gpu, -1)
                wadm_aidx = jnp.where(ok_w, wadm_aidx, -1)

        valid = pid >= 0
        pid_c = jnp.maximum(pid, 0)
        with jax.named_scope("stage.select"):
            gpu, aidx, ok = self._stage_select(st, pid_c, valid)

        mig_res = None
        if self.spec.defrag:
            with jax.named_scope("stage.migrate"):
                st, gpu, aidx, ok, mig_res = self._stage_migrate(
                    st, pid_c, valid, gpu, aidx, ok
                )

        meta = (end, prio, ten, st.ev) if self.protocol.faulted else None
        with jax.named_scope("stage.commit"):
            st = self._stage_commit(
                st, pid_c, gpu, aidx, ok, exp_row, exp_col, mig_res, meta=meta
            )

        if self.protocol.queued:
            with jax.named_scope("stage.queue"):
                parked = valid & ~ok & wlive & (st.wait_pid < 0).any()
                st = self._stage_park(
                    st, pid_c, parked, t, end, prio, ten, exp_row, exp_col
                )
                st = st._replace(ev=st.ev + 1)

        post_frag = post_free = post_active = None
        if self.protocol.post_metrics:
            with jax.named_scope("stage.measure"):
                post_frag, post_free, post_active = self._stage_post_measure(st)

        neg1 = jnp.int32(-1)
        trace = EventTrace(
            ok=ok,
            gpu=jnp.where(ok, gpu, 0).astype(jnp.int32),
            aidx=aidx.astype(jnp.int32),
            free_sum=free_sum,
            active=active,
            frag=frag,
            post_free=post_free,
            post_active=post_active,
            post_frag=post_frag,
            mig=None if mig_res is None else mig_res.mig,
            mig_from_gpu=None if mig_res is None else jnp.where(
                mig_res.mig, mig_res.vic_gpu, neg1
            ),
            mig_from_anchor=None if mig_res is None else jnp.where(
                mig_res.mig, mig_res.vic_anchor, neg1
            ),
            mig_to_gpu=None if mig_res is None else jnp.where(
                mig_res.mig, mig_res.new_gpu, neg1
            ),
            mig_to_anchor=None if mig_res is None else jnp.where(
                mig_res.mig, mig_res.new_anchor, neg1
            ),
            parked=parked,
            wadm_eidx=wadm_eidx,
            wadm_gpu=wadm_gpu,
            wadm_aidx=wadm_aidx,
            evicted=evicted,
            evict_lost=evict_lost,
            evict_esum=evict_esum,
        )
        return st, trace


def _build_core(
    *,
    policy: PolicyLike,
    metric: str,
    num_gpus: int,
    use_kernel: bool,
    kernel_spec: Optional[mig.ClusterSpec] = None,
    protocol: Union[str, Protocol] = "steady",
    wait_slots: int = 0,
    wait_patience: int = 0,
    midx: Optional[jax.Array] = None,
    tables: Optional[SpecTables] = None,
) -> Tuple[EngineCore, SpecTables, jax.Array]:
    """Validate one engine configuration and build its staged core.

    The single construction path shared by the monolithic :func:`_simulate`,
    the chunked :func:`_scan_chunk` and :func:`init_carry` — every entry
    point applies the same policy/protocol validation and compiles the same
    stages, so the chunked and monolithic drivers cannot drift.  Returns
    ``(core, tables, midx)`` with the homogeneous defaults filled in.
    """
    pspec = resolve(policy, engine="batched")
    proto = resolve_protocol(protocol)
    if proto.queued:
        if pspec.defrag:
            raise ValueError(
                f"policy {pspec.name!r}: defrag specs are not supported under "
                "the queued protocol (the migrate stage's victim table does "
                "not cover parked requests)"
            )
        if wait_slots <= 0:
            raise ValueError(
                f"protocol {proto.name!r} needs wait_slots > 0 "
                "(SimConfig.wait_capacity)"
            )
    if tables is None:  # homogeneous A100-80GB default
        cspec = _default_spec(num_gpus)
        tables = spec_tables(cspec)
        midx = jnp.asarray(cspec.model_index)
    frag_fn = delta_fn = select_fn = migrate_fn = None
    if use_kernel:
        # Pallas dispatch rules (`kernel_spec` is the static ClusterSpec):
        # the occupancy-based `fragscore` rescore kernel needs one placement
        # table, so it compiles in on homogeneous specs only (mixed fleets
        # keep the base-derived rescoring); the fused `delta_from_base` ΔF
        # kernel dispatches per model group and serves any fleet, for specs
        # whose keys consume ΔF; specs that additionally declare
        # argmin-fusability (`PolicySpec.fused_argmin`) lower the whole
        # select stage — and, for defrag specs, both migrate refinements —
        # to the fused `select_from_base` / `migrate_refine` kernels (the
        # `(M, A)` score table stays in VMEM).  `kernel_lowering="delta"`
        # keeps only the ΔF kernel.
        kspec = kernel_spec if kernel_spec is not None else _default_spec(num_gpus)
        if kspec.is_homogeneous:
            frag_fn = make_frag_fn(metric, True, kspec.models[0])
        if pspec.requires_delta_f:
            delta_fn = make_delta_fn(kspec, metric)
        # the fused select kernel cannot see the faulted protocol's up-mask,
        # so faulted runs keep the jnp lowering (frag/ΔF kernels still apply)
        if pspec.fused_argmin and not proto.faulted:
            select_fn = make_select_fn(kspec, pspec, metric)
            if pspec.defrag:
                migrate_fn = make_migrate_fn(kspec, pspec, metric)
    vg = tables.V[midx]  # (M, N) per-GPU window sizes, gathered once
    core = EngineCore(
        spec=pspec, protocol=proto, metric=metric, tables=tables,
        midx=midx, vg=vg, frag_fn=frag_fn, delta_fn=delta_fn,
        select_fn=select_fn, migrate_fn=migrate_fn,
        wait_patience=wait_patience,
    )
    return core, tables, midx


def _broadcast_init(
    core: EngineCore, runs: int, ring_rows: int, ring_cols: int, wait_slots: int
) -> ReplicaState:
    """The ``(runs,)``-vmapped initial carry for ``core``'s configuration."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (runs,) + x.shape),
        _init_state(
            core.tables, core.midx, ring_rows, ring_cols,
            track_occ=core.frag_fn is not None, track_alloc=core.spec.defrag,
            wait_slots=wait_slots if core.protocol.queued else 0,
            faulted=core.protocol.faulted,
        ),
    )


def _scan_xs(events: EventStream, proto: Protocol):
    """The scanned input tuple: every device-shipped stream field.

    ``sample``/``measuring`` are host-side reduction flags — never shipped
    to the scan.
    """
    xs = (events.pid, events.exp_row, events.exp_col, events.drain_row, events.new_slot)
    if proto.queued:  # the wait stage's clock + per-arrival queue attributes
        xs = xs + (events.slot, events.end, events.prio, events.tenant, events.wlive)
    if proto.faulted:  # per-slot GPU fail/recover lanes, (E, R, M)
        xs = xs + (events.fail, events.recover)
    return xs


#: the mesh axis the replica dimension is split over (:func:`_replica_sharding`)
REPLICAS = "replicas"


def _per_device(scan, mesh, in_specs):
    """``scan`` mapped over the replica mesh, each device on its own slice.

    XLA cannot partition a Mosaic kernel, so a replica-sharded scan is
    mapped over the mesh explicitly.  Replicas never interact on device,
    so D devices scanning R/D replicas each are bit-identical to one
    device scanning all R.  ``scan`` returns ``(carry, trace)``: the
    carry's replica axis leads, the trace's follows the event axis.
    """
    p = jax.sharding.PartitionSpec
    return jax.shard_map(
        scan, mesh=mesh, in_specs=in_specs,
        out_specs=(p(REPLICAS), p(None, REPLICAS)), check_vma=False,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "metric", "num_gpus", "ring_rows", "ring_cols",
        "use_kernel", "kernel_spec", "protocol", "wait_slots", "wait_patience",
        "mesh",
    ),
)
def _simulate(
    events: EventStream,  # each field (E_max, R) — events are the scanned axis
    *,
    policy: PolicyLike,  # registered name or (hashable, static) PolicySpec
    metric: str,
    num_gpus: int,
    ring_rows: int,
    ring_cols: int,
    use_kernel: bool,
    kernel_spec: Optional[mig.ClusterSpec] = None,
    protocol: Union[str, Protocol] = "steady",
    wait_slots: int = 0,
    wait_patience: int = 0,
    midx: Optional[jax.Array] = None,
    tables: Optional[SpecTables] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[ReplicaState, EventTrace]:
    """The event scan over all replicas; ``mesh`` (the replica mesh of
    :func:`_replica_sharding`) runs it per device (:func:`_per_device`)."""

    def scan(events, midx, tables):
        core, tables, midx = _build_core(
            policy=policy, metric=metric, num_gpus=num_gpus,
            use_kernel=use_kernel, kernel_spec=kernel_spec, protocol=protocol,
            wait_slots=wait_slots, wait_patience=wait_patience,
            midx=midx, tables=tables,
        )
        step = jax.vmap(core.step, in_axes=(0, 0))
        runs = events.pid.shape[1]
        init = _broadcast_init(core, runs, ring_rows, ring_cols, wait_slots)
        return jax.lax.scan(
            lambda st, x: step(st, x), init, _scan_xs(events, core.protocol)
        )

    if mesh is None:
        return scan(events, midx, tables)
    p = jax.sharding.PartitionSpec
    return _per_device(scan, mesh, (p(None, REPLICAS), p(), p()))(
        events, midx, tables
    )


# ---------------------------------------------------------------------------
# Host-side arrival pre-sampling + public entry point
# ---------------------------------------------------------------------------


def _rank_within_groups(keys: np.ndarray) -> np.ndarray:
    """Rank of each element within its equal-key group (first-occurrence order)."""
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    starts = np.r_[0, np.flatnonzero(np.diff(ks)) + 1]
    lengths = np.diff(np.r_[starts, len(ks)])
    ranks_sorted = np.arange(len(ks)) - np.repeat(starts, lengths)
    ranks = np.empty(len(ks), dtype=np.int64)
    ranks[order] = ranks_sorted
    return ranks


def _ring_columns(
    is_arrival: np.ndarray, end: np.ndarray, span: int
) -> Tuple[np.ndarray, int]:
    """Collision-free ring columns: rank among same-(replica, end) arrivals.

    ``span`` must exceed every end slot so the per-replica key blocks never
    overlap.  Returns ``(exp_col, ring_cols)``.
    """
    runs, e_max = is_arrival.shape
    exp_col = np.zeros((runs, e_max), dtype=np.int32)
    flat = np.flatnonzero(is_arrival)  # C-order == per-replica arrival order
    keys = (np.repeat(np.arange(runs), e_max)[flat].astype(np.int64) * span
            + end.ravel()[flat])
    ranks = _rank_within_groups(keys)
    exp_col.ravel()[flat] = ranks
    ring_cols = max(1, int(ranks.max()) + 1 if len(ranks) else 1)
    return exp_col, ring_cols


def presample_fault_slots(
    spec: mig.ClusterSpec,
    fault_model: "mig.FaultModel",
    runs: int,
    total_slots: int,
    rng: np.random.Generator,
) -> Tuple[np.ndarray, np.ndarray]:
    """Draw per-GPU alternating fail/recover slot tables.

    Returns ``(fail, recover)`` as ``(runs, total_slots, M)`` bools.  Each
    GPU alternates ``Exp(mtbf)`` up-phases and ``Exp(mttr)`` down-phases
    (per-model rates via :meth:`FaultModel.rates_for`); phase lengths are
    ceiled to at least one slot, so fail and recover marks strictly
    alternate and never share a slot.  Draw order is fixed (replica-major,
    then GPU, then alternating phases) so a seeded rng reproduces the
    tables exactly.
    """
    m = spec.num_gpus
    rates = [fault_model.rates_for(spec.model_of(g).name) for g in range(m)]
    fail = np.zeros((runs, total_slots, m), dtype=bool)
    recover = np.zeros((runs, total_slots, m), dtype=bool)
    for r in range(runs):
        for g in range(m):
            mtbf, mttr = rates[g]
            t = 0.0
            while True:
                t += max(1.0, np.ceil(rng.exponential(mtbf)))
                if t >= total_slots:
                    break
                fail[r, int(t), g] = True
                t += max(1.0, np.ceil(rng.exponential(mttr)))
                if t >= total_slots:
                    break
                recover[r, int(t), g] = True
    return fail, recover


def presample_arrivals(
    cfg: SimConfig, runs: int, seed=None, queued: bool = False,
    fault_model: "mig.FaultModel" = None,
) -> Tuple[EventStream, EventMeta, int, int]:
    """Build per-replica steady-protocol event streams on host.

    Returns ``(events, meta, ring_rows, ring_cols)``.  One event per
    Poisson arrival plus one heartbeat per empty slot (so consecutive
    events never skip a slot), plus a trailing sentinel that samples the
    final slot; streams are right-padded to the longest replica with no-op
    lanes.

    ``queued`` additionally populates the stream's queued-protocol fields
    (slot clock, absolute end slots, per-arrival tenant/priority draws and
    the live-event mask).  The tenant/priority draws happen strictly
    *after* the shared arrival sampling, so the arrival process — and
    every non-queued field — is byte-identical with ``queued=False``
    (golden steady traces are unaffected).  ``fault_model`` (faulted
    protocols; implies ``queued``) additionally draws per-GPU fail/recover
    lanes — strictly after every other draw, preserving the same
    byte-identity guarantee — and attaches each slot's lane set to the
    first event of that slot.
    """
    rng = np.random.default_rng(cfg.seed if seed is None else seed)
    probs = request_probs(cfg)
    T, warm, meas, rate = steady_params(cfg)
    total_slots = warm + meas
    ring_k = T + 1  # end slots live in (t, t + T] — one ring revolution

    counts = rng.poisson(rate, size=(runs, total_slots))
    ev_per_slot = np.maximum(counts, 1)  # heartbeat for empty slots
    n_events = ev_per_slot.sum(axis=1)  # (R,)
    e_max = int(n_events.max()) + 1  # +1 trailing sentinel

    pid = np.full((runs, e_max), -1, dtype=np.int32)
    slot = np.full((runs, e_max), total_slots, dtype=np.int32)
    new_slot = np.zeros((runs, e_max), dtype=bool)
    end = np.zeros((runs, e_max), dtype=np.int64)  # absolute end slot

    for r in range(runs):
        n = n_events[r]
        slots_r = np.repeat(np.arange(total_slots), ev_per_slot[r])
        within = np.arange(n) - np.repeat(
            np.cumsum(ev_per_slot[r]) - ev_per_slot[r], ev_per_slot[r]
        )
        is_arr = within < counts[r, slots_r]
        na = int(is_arr.sum())
        pid[r, :n][is_arr] = distributions.sample_profile_probs(probs, na, rng)
        slot[r, :n] = slots_r
        new_slot[r, :n] = within == 0
        end[r, :n][is_arr] = slots_r[is_arr] + rng.integers(1, T + 1, size=na)
        new_slot[r, n] = True  # sentinel: drains/samples the final slot

    is_arrival = pid >= 0
    exp_col, ring_cols = _ring_columns(is_arrival, end, total_slots + T + 1)

    exp_row = np.where(is_arrival, end % ring_k, ring_k + 1).astype(np.int32)
    drain_row = (slot % ring_k).astype(np.int32)
    prev = slot - 1
    sample = (
        new_slot & (prev >= warm) & ((prev - warm) % SAMPLE_EVERY == 0)
    )
    measuring = is_arrival & (slot >= warm)

    prio = tenant = wlive = None
    if queued:  # drawn after the shared stream: arrival sampling unchanged
        tenant = np.zeros((runs, e_max), dtype=np.int32)
        prio = np.zeros((runs, e_max), dtype=np.int32)
        for r in range(runs):
            sel = is_arrival[r]
            na = int(sel.sum())
            tenant[r, sel] = rng.integers(0, max(1, cfg.num_tenants), size=na)
            prio[r, sel] = rng.integers(0, max(1, cfg.num_priorities), size=na)
        wlive = slot < total_slots  # padding/sentinel lanes have no clock
        tenant, prio, wlive = tenant.T, prio.T, wlive.T

    fail = recover = None
    if fault_model is not None:  # drawn strictly after every other draw
        spec = cfg.spec()
        fail_s, rec_s = presample_fault_slots(
            spec, fault_model, runs, total_slots, rng
        )
        m = spec.num_gpus
        fail = np.zeros((runs, e_max, m), dtype=bool)
        recover = np.zeros((runs, e_max, m), dtype=bool)
        first = new_slot & (slot < total_slots)  # sentinel/padding carry none
        rr_idx, ee_idx = np.nonzero(first)
        fail[rr_idx, ee_idx] = fail_s[rr_idx, slot[rr_idx, ee_idx]]
        recover[rr_idx, ee_idx] = rec_s[rr_idx, slot[rr_idx, ee_idx]]
        fail = np.ascontiguousarray(fail.transpose(1, 0, 2))
        recover = np.ascontiguousarray(recover.transpose(1, 0, 2))

    events = EventStream(
        pid=pid.T,
        exp_row=exp_row.T,
        exp_col=exp_col.T,
        drain_row=drain_row.T,
        new_slot=new_slot.T,
        sample=sample.T,
        measuring=measuring.T,
        slot=slot.T.astype(np.int32) if queued else None,
        end=end.T.astype(np.int32) if queued else None,
        prio=prio,
        tenant=tenant,
        wlive=wlive,
        fail=fail,
        recover=recover,
    )
    meta = EventMeta(slot=slot.T, end=end.T)
    return events, meta, ring_k + 2, ring_cols


def presample_cumulative(
    cfg: SimConfig, runs: int, seed=None
) -> Tuple[EventStream, EventMeta, int, int]:
    """Build per-replica cumulative-protocol event streams on host.

    One arrival per slot (the paper-literal protocol — no heartbeats, no
    padding), durations ``U[1, T]``.  Replica ``r`` consumes the *same*
    RNG stream as the Python simulator's run ``r`` (seed
    ``cfg.seed + r * 9973``, profiles then durations), so
    :func:`run_batched` and :func:`repro.sim.simulator.run_many` simulate
    identical arrival processes per seed — the cross-engine cumulative
    parity is same-stream, not just statistical.
    """
    base_seed = cfg.seed if seed is None else seed
    spec = cfg.spec()
    cap = spec.total_mem_slices
    probs = request_probs(cfg)
    mean_mem = distributions.mean_mem_from_probs(probs)
    T = int(np.ceil(cap / mean_mem))
    n = int(np.ceil(cfg.max_demand * cap / mean_mem)) + 20
    ring_k = T + 1

    pid = np.zeros((runs, n), dtype=np.int32)
    end = np.zeros((runs, n), dtype=np.int64)
    for r in range(runs):
        rng = np.random.default_rng(base_seed + r * 9973)
        pid[r] = distributions.sample_profile_probs(probs, n, rng)
        end[r] = np.arange(n) + rng.integers(1, T + 1, size=n)

    slot = np.tile(np.arange(n, dtype=np.int32), (runs, 1))
    new_slot = np.ones((runs, n), dtype=bool)
    exp_col, ring_cols = _ring_columns(np.ones_like(pid, bool), end, n + T + 1)
    exp_row = (end % ring_k).astype(np.int32)
    drain_row = (slot % ring_k).astype(np.int32)

    events = EventStream(
        pid=pid.T,
        exp_row=exp_row.T,
        exp_col=exp_col.T,
        drain_row=drain_row.T,
        new_slot=new_slot.T,
        sample=np.zeros((n, runs), dtype=bool),
        measuring=np.ones((n, runs), dtype=bool),
    )
    meta = EventMeta(slot=slot.T, end=end.T)
    return events, meta, ring_k + 2, ring_cols


def _replica_sharding(runs: int, shard: Optional[bool] = None):
    """The replica-axis ``NamedSharding`` for ``(E, R)`` inputs, or ``None``.

    ``shard=None`` (auto) shards when more than one device is visible and
    ``runs`` divides evenly; ``True`` requires it (raises otherwise);
    ``False`` disables.  Factored out of :func:`shard_events` so the
    chunked driver can place every staged chunk on the same mesh.
    """
    if shard is False:
        return None
    devices = jax.devices()
    if len(devices) <= 1:
        if shard:
            raise ValueError(
                "replica sharding requested but only one device is visible"
            )
        return None
    if runs % len(devices) != 0:
        if shard:
            raise ValueError(
                f"runs={runs} does not divide across {len(devices)} devices"
            )
        return None
    mesh = jax.make_mesh(
        (len(devices),), (REPLICAS,), axis_types=(jax.sharding.AxisType.Auto,)
    )
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, REPLICAS)
    )


def shard_events(events, runs: int, shard: Optional[bool] = None):
    """Split the replica axis of a device event stream across devices.

    Replicas are embarrassingly parallel (no cross-replica arithmetic on
    device), so placing the ``(E_max, R)`` inputs on a 1-D ``replicas``
    mesh and scanning with that mesh (``_simulate(..., mesh=...)``) gives
    bitwise-identical results with R/D replicas of work per device.  ``shard=None`` (auto) shards when
    more than one device is visible and ``runs`` divides evenly; ``True``
    requires it (raises otherwise); ``False`` disables.

    Leaves already committed to an equivalent sharding are returned as-is
    (no transfer), so repeated ``run_batched`` calls over the same placed
    stream never re-copy the full event pytree host→device.
    """
    sharding = _replica_sharding(runs, shard)
    if sharding is None:
        return events

    def put(x):
        if (
            isinstance(x, jax.Array)
            and getattr(x, "committed", False)
            and x.sharding.is_equivalent_to(sharding, x.ndim)
        ):
            return x  # already placed — skip the device_put
        return jax.device_put(x, sharding)

    return jax.tree.map(put, events)


# ---------------------------------------------------------------------------
# Chunked streaming driver — double-buffered host→device feed, donated carry
# ---------------------------------------------------------------------------


def init_carry(
    runs: int,
    *,
    policy: PolicyLike,
    metric: str,
    num_gpus: int,
    ring_rows: int,
    ring_cols: int,
    use_kernel: bool = False,
    kernel_spec: Optional[mig.ClusterSpec] = None,
    protocol: Union[str, Protocol] = "steady",
    wait_slots: int = 0,
    wait_patience: int = 0,
    midx: Optional[jax.Array] = None,
    tables: Optional[SpecTables] = None,
) -> ReplicaState:
    """The initial ``(runs,)``-vmapped chunk carry for one configuration.

    This is the *same* initial state :func:`_simulate` builds internally —
    chunking the scan at any boundary is bit-exact because the carry holds
    every cross-event datum (occupancy planes, expiry/wait rings, cursor,
    event counter).  Also the checkpoint *template*: build it from the
    identical static configuration to restore a saved carry via
    :func:`load_stream_checkpoint`.

    Delegates to a jitted builder so repeated chunked runs of one
    configuration pay the table/broadcast construction once at compile
    time; every call returns fresh buffers (safe to donate into the
    first chunk).
    """
    return _init_carry_jit(
        midx, tables, runs=runs, ring_rows=ring_rows, ring_cols=ring_cols,
        policy=policy, metric=metric, num_gpus=num_gpus,
        use_kernel=use_kernel, kernel_spec=kernel_spec, protocol=protocol,
        wait_slots=wait_slots, wait_patience=wait_patience,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "runs", "ring_rows", "ring_cols", "policy", "metric", "num_gpus",
        "use_kernel", "kernel_spec", "protocol", "wait_slots",
        "wait_patience",
    ),
)
def _init_carry_jit(
    midx, tables, *, runs, ring_rows, ring_cols, policy, metric, num_gpus,
    use_kernel, kernel_spec, protocol, wait_slots, wait_patience,
) -> ReplicaState:
    core, _, _ = _build_core(
        policy=policy, metric=metric, num_gpus=num_gpus,
        use_kernel=use_kernel, kernel_spec=kernel_spec, protocol=protocol,
        wait_slots=wait_slots, wait_patience=wait_patience,
        midx=midx, tables=tables,
    )
    return _broadcast_init(core, runs, ring_rows, ring_cols, wait_slots)


@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=(
        "policy", "metric", "num_gpus", "use_kernel", "kernel_spec",
        "protocol", "wait_slots", "wait_patience", "mesh",
    ),
)
def _scan_chunk(
    state: ReplicaState,  # donated: each chunk-step reuses its buffers in place
    events: EventStream,  # one chunk, each field (chunk, R)
    *,
    policy: PolicyLike,
    metric: str,
    num_gpus: int,
    use_kernel: bool,
    kernel_spec: Optional[mig.ClusterSpec] = None,
    protocol: Union[str, Protocol] = "steady",
    wait_slots: int = 0,
    wait_patience: int = 0,
    midx: Optional[jax.Array] = None,
    tables: Optional[SpecTables] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[ReplicaState, EventTrace]:
    """Scan one event chunk from an explicit carry (the chunked step).

    Identical scan body to :func:`_simulate` (same :func:`_build_core`
    path, same vmapped :meth:`EngineCore.step`, same ``mesh`` handling),
    with the carry passed in instead of built internally and its input
    buffers **donated** — XLA writes the updated carry back into the
    chunk's input storage, so the resident state footprint stays one carry
    regardless of chunk count.
    """

    def scan(state, events, midx, tables):
        core, _, _ = _build_core(
            policy=policy, metric=metric, num_gpus=num_gpus,
            use_kernel=use_kernel, kernel_spec=kernel_spec, protocol=protocol,
            wait_slots=wait_slots, wait_patience=wait_patience,
            midx=midx, tables=tables,
        )
        step = jax.vmap(core.step, in_axes=(0, 0))
        return jax.lax.scan(
            lambda st, x: step(st, x), state, _scan_xs(events, core.protocol)
        )

    if mesh is None:
        return scan(state, events, midx, tables)
    p = jax.sharding.PartitionSpec
    return _per_device(scan, mesh, (p(REPLICAS), p(None, REPLICAS), p(), p()))(
        state, events, midx, tables
    )


def save_stream_checkpoint(path, state: ReplicaState, events_done: int,
                           metadata: Optional[dict] = None) -> None:
    """Persist a chunked-scan carry (flat npz via :mod:`repro.checkpoint`).

    ``events_done`` — how many events of the stream the carry has consumed —
    is stored as the checkpoint step; resume by presampling the same
    ``(cfg, runs, seed)`` stream and calling :func:`simulate_chunked` with
    ``carry=state, start=events_done``.
    """
    from repro.checkpoint import ckpt

    host = jax.device_get(state)  # copy out before the next chunk donates it
    ckpt.save_checkpoint(
        path, host, step=int(events_done),
        metadata={"kind": "replica-carry", **(metadata or {})},
    )


def load_stream_checkpoint(path, template: ReplicaState) -> Tuple[ReplicaState, int]:
    """Restore a carry saved by :func:`save_stream_checkpoint`.

    ``template`` must come from :func:`init_carry` with the *identical*
    static configuration (the flat-npz restore validates structure and
    shapes, so a carry from a different policy/protocol/ring geometry
    fails loudly).  Returns ``(state, events_done)``.
    """
    from repro.checkpoint import ckpt

    return ckpt.load_checkpoint(path, template)


def _concat_traces(traces, concat):
    """Concatenate per-chunk :class:`EventTrace` pytrees along the event
    axis; fields compiled out (``None``) stay ``None``."""
    if len(traces) == 1:
        return traces[0]
    return EventTrace(*[
        None if getattr(traces[0], name) is None
        else concat([getattr(t, name) for t in traces], axis=0)
        for name in EventTrace._fields
    ])


def simulate_chunked(
    events: EventStream,  # host-resident stream, each field (E_max, R)
    *,
    chunk_size: int,
    policy: PolicyLike,
    metric: str,
    num_gpus: int,
    ring_rows: int,
    ring_cols: int,
    use_kernel: bool = False,
    kernel_spec: Optional[mig.ClusterSpec] = None,
    protocol: Union[str, Protocol] = "steady",
    wait_slots: int = 0,
    wait_patience: int = 0,
    midx: Optional[jax.Array] = None,
    tables: Optional[SpecTables] = None,
    stream: bool = True,
    carry: Optional[ReplicaState] = None,
    start: int = 0,
    shard: Optional[bool] = None,
    checkpoint_path=None,
    checkpoint_every: int = 0,
) -> Tuple[ReplicaState, EventTrace]:
    """Drive the event scan in chunks with a double-buffered device feed.

    Bit-for-bit equal to :func:`_simulate` on the same stream for *any*
    ``chunk_size`` (the carry holds every cross-event datum, and both paths
    compile the same :meth:`EngineCore.step`), but device memory holds only
    one carry plus two staged chunks instead of the full ``(E_max, R)``
    event tensor and ``(E_max, R)`` trace:

    * the carry lives on device across chunks and is **donated** into each
      :func:`_scan_chunk` call (in-place buffer reuse);
    * chunk ``k+1`` is ``device_put`` while chunk ``k``'s compute is in
      flight (dispatch is asynchronous), so host→device transfer overlaps
      compute;
    * with ``stream=True`` (default) each chunk's decision trace is fetched
      back and concatenated host-side, so full traces never accumulate on
      device; ``stream=False`` keeps them on device (explicit opt-in).

    ``carry``/``start`` resume a run mid-stream (see
    :func:`load_stream_checkpoint`); a passed-in carry is *consumed* (its
    buffers are donated to the first chunk).  ``checkpoint_path`` +
    ``checkpoint_every`` (in chunks) persist the carry periodically through
    :mod:`repro.checkpoint.ckpt`.  ``shard`` places every staged chunk on
    the replica-axis mesh (see :func:`_replica_sharding`).

    The run is one ``simulate_chunked`` span of :mod:`repro.obs` (a child
    of ``run_batched``'s, or a call of its own), holding each chunk's
    ``transfer``, ``dispatch`` and ``fetch`` spans in the order they ran,
    and adds to the call's counters ``h2d_bytes`` (every staged chunk),
    ``h2d_overlapped_bytes`` (those staged while a chunk's scan was in
    flight: all but the first) and ``d2h_bytes`` (the fetched traces).
    """
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    e_max, runs = events.pid.shape
    if not 0 <= start < e_max:
        raise ValueError(f"start={start} outside the event stream [0, {e_max})")
    sharding = _replica_sharding(runs, shard)
    statics = dict(
        policy=policy, metric=metric, num_gpus=num_gpus,
        use_kernel=use_kernel, kernel_spec=kernel_spec, protocol=protocol,
        wait_slots=wait_slots, wait_patience=wait_patience,
        midx=midx, tables=tables,
    )
    state = carry if carry is not None else init_carry(
        runs, ring_rows=ring_rows, ring_cols=ring_cols, **statics
    )
    if state.ring_gpu.shape[-2:] != (ring_rows, ring_cols):
        raise ValueError(
            f"carry ring geometry {state.ring_gpu.shape[-2:]} does not match "
            f"this stream's ({ring_rows}, {ring_cols}) — resumed with a carry "
            "from a different presample?"
        )
    host = jax.tree.map(np.asarray, events)  # host slicing source
    bounds = list(range(start, e_max, chunk_size)) + [e_max]
    n_chunks = len(bounds) - 1
    mesh = None if sharding is None else sharding.mesh

    def put(lo, hi, overlapped):
        ch = jax.tree.map(lambda x: x[lo:hi], host)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(ch))
        obs.count("h2d_bytes", nbytes)
        if overlapped:
            obs.count("h2d_overlapped_bytes", nbytes)
        # one batched transfer for the whole chunk pytree (a single
        # Sharding broadcasts across leaves), not one dispatch per field
        with obs.span("transfer"):
            return (
                jax.device_put(ch, sharding) if sharding is not None
                else jax.device_put(ch)
            )

    def scan(state, buf):
        with obs.span("dispatch"):
            return _scan_chunk(state, buf, mesh=mesh, **statics)

    with obs.span("simulate_chunked"):
        buf = put(bounds[0], bounds[1], False)  # prefetch chunk 0
        state, tr = scan(state, buf)  # async dispatch
        traces = []
        for k in range(n_chunks):
            # chunk k's scan is already in flight; ``state`` is its output carry
            if checkpoint_path and checkpoint_every and (k + 1) % checkpoint_every == 0:
                # copy the post-chunk-k carry out *before* the next dispatch
                # donates its buffers (a deliberate pipeline bubble)
                save_stream_checkpoint(checkpoint_path, state, bounds[k + 1])
            if k + 1 < n_chunks:
                # stage chunk k+1 and dispatch its scan before blocking on
                # chunk k's trace, so the d2h fetch below overlaps compute
                buf = put(bounds[k + 1], bounds[k + 2], True)
                state, tr_next = scan(state, buf)
            if stream:
                with obs.span("fetch"):
                    tr = jax.device_get(tr)  # joins chunk k's compute
                obs.count("d2h_bytes", _nbytes(tr))
            traces.append(tr)
            if k + 1 < n_chunks:
                tr = tr_next
    concat = np.concatenate if stream else jnp.concatenate
    return state, _concat_traces(traces, concat)


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


class BatchedProgram(NamedTuple):
    """One validated batched run: its presampled stream and the static
    configuration shared by :func:`_simulate` and :func:`simulate_chunked`.

    ``kwargs`` are exactly what :func:`run_batched` passes to the scan, so
    a caller that needs the per-event trace (parity checks, compile-only
    inspection) runs the very program the entry point runs, and reduces
    it with :meth:`aggregate` as the entry point does.
    """

    events: EventStream
    meta: EventMeta
    kwargs: dict
    protocol: Protocol
    spec: mig.ClusterSpec
    cfg: SimConfig

    def launch(self, shard: Optional[bool] = None):
        """The monolithic scan's arguments as :func:`run_batched` passes
        them: ``(events, kwargs)``, the stream on the device (split over
        the replica mesh when ``shard`` allows, :func:`shard_events`) and
        the statics with that ``mesh``.  Call ``_simulate(events,
        **kwargs)``."""
        runs = self.events.pid.shape[1]
        sharding = _replica_sharding(runs, shard)
        events = shard_events(jax.tree.map(jnp.asarray, self.events), runs, shard)
        return events, dict(
            self.kwargs, mesh=None if sharding is None else sharding.mesh
        )

    def lower(self, shard: Optional[bool] = None):
        """The monolithic scan :func:`run_batched` launches, lowered."""
        events, kwargs = self.launch(shard)
        return _simulate.lower(events, **kwargs)

    def aggregate(self, trace: EventTrace) -> Dict[str, float]:
        """The protocol's ``run_many``-keyed aggregates of a host trace."""
        events, spec, runs = self.events, self.spec, self.events.pid.shape[1]
        if self.protocol.name == "cumulative":
            return _aggregate_cumulative(events, trace, spec, runs, self.cfg)
        if self.protocol.faulted:
            return _aggregate_faulted(events, trace, spec, runs)
        if self.protocol.queued:
            return _aggregate_queued(events, trace, spec, runs)
        return aggregate(events, trace, spec, runs)


def batched_program(
    policy: PolicyLike,
    cfg: SimConfig,
    runs: int,
    use_kernel: bool | None = None,
) -> BatchedProgram:
    """Validate ``policy`` × ``cfg`` and presample ``runs`` replicas.

    ``use_kernel=None`` picks the Pallas lowering on TPU for every spec
    that allows it (``PolicySpec.kernel_lowering``); see :func:`run_batched`.
    Runs as the ``presample`` span of :mod:`repro.obs` and counts the
    stream's lanes (:func:`_count_lanes`).
    """
    with obs.span("presample"):
        prog = _batched_program(policy, cfg, runs, use_kernel)
    _count_lanes(prog.events, prog.meta)
    return prog


def _count_lanes(events: EventStream, meta: EventMeta) -> None:
    """The open call's lane counters: ``lanes`` (``E_max × R``, what the
    scan steps over), ``arrival_lanes``, ``heartbeat_lanes`` (a slot with
    no arrival) and ``padding_lanes`` (each replica's trailing sentinel and
    the padding to the longest replica, at the slot past the last)."""
    arrivals = events.pid >= 0
    tail = ~arrivals & (meta.slot >= meta.slot.max())
    obs.count("lanes", events.pid.size)
    obs.count("arrival_lanes", arrivals.sum())
    obs.count("padding_lanes", tail.sum())
    obs.count("heartbeat_lanes", events.pid.size - arrivals.sum() - tail.sum())


def _batched_program(
    policy: PolicyLike, cfg: SimConfig, runs: int, use_kernel: bool | None
) -> BatchedProgram:
    """:func:`batched_program`'s validation and presampling."""
    policy = resolve(policy, engine="batched")
    proto = resolve_protocol(cfg.protocol)
    spec = cfg.spec()
    if use_kernel is None:
        use_kernel = bool(
            jax.default_backend() == "tpu" and policy.kernel_lowering
        )
    if use_kernel and not policy.kernel_lowering:
        raise ValueError(
            f"policy {policy.name!r} opts out of Pallas kernel lowering "
            "(PolicySpec.kernel_lowering=False); run with use_kernel=False"
        )
    if proto.faulted:
        if cfg.fault_model is None:
            raise ValueError(
                f"protocol {proto.name!r} needs SimConfig.fault_model "
                "(a repro.core.mig.FaultModel describing MTBF/MTTR)"
            )
        # retry/backoff ride in the (static, hashable) protocol descriptor
        proto = dataclasses.replace(
            proto,
            fault_retries=cfg.fault_model.max_retries,
            fault_backoff=cfg.fault_model.backoff_base,
        )

    if proto.name == "cumulative":
        events, meta, ring_rows, ring_cols = presample_cumulative(cfg, runs)
    else:
        events, meta, ring_rows, ring_cols = presample_arrivals(
            cfg, runs, queued=proto.queued,
            fault_model=cfg.fault_model if proto.faulted else None,
        )
    kwargs = dict(
        policy=policy,
        metric=cfg.metric,
        num_gpus=cfg.num_gpus,
        ring_rows=ring_rows,
        ring_cols=ring_cols,
        use_kernel=use_kernel,
        kernel_spec=spec if use_kernel else None,
        protocol=proto,
        wait_slots=cfg.wait_capacity if proto.queued else 0,
        wait_patience=cfg.wait_patience if proto.queued else 0,
        midx=jnp.asarray(spec.model_index),
        tables=spec_tables(spec),
    )
    return BatchedProgram(events, meta, kwargs, proto, spec, cfg)


def run_batched(
    policy: PolicyLike,
    cfg: SimConfig,
    runs: int = 64,
    use_kernel: bool | None = None,
    shard: Optional[bool] = None,
    chunk_size: Optional[int] = None,
    stream: Optional[bool] = None,
) -> Dict[str, float]:
    """Average ``runs`` replicas in one device program.

    Drop-in for :func:`repro.sim.simulator.run_many` on both protocols
    (same aggregate keys; the cumulative protocol additionally returns the
    demand-grid ``traces``); ``policy`` is any batched-capable registered
    policy name or an ad-hoc :class:`~repro.core.policy.PolicySpec`
    (validated through the registry's single path, like every other entry
    point) — defrag specs included (the migrate stage is compiled into the
    scan).  ``use_kernel`` routes scoring through the Pallas kernels
    (default: only on TPU): the fused ``delta_from_base`` ΔF kernel with
    per-model dispatch on any fleet (for specs whose keys consume ΔF), plus
    the occupancy-based ``fragscore`` rescore kernel on homogeneous specs
    (it bakes in one model's placement table).  A spec may opt out via
    ``PolicySpec.kernel_lowering=False`` (requesting ``use_kernel=True``
    for such a spec raises).  ``shard`` splits the replica axis across
    visible devices (see :func:`shard_events`; default: auto).

    ``chunk_size`` routes the run through the chunked streaming driver
    (:func:`simulate_chunked`): device memory holds one carry plus two
    staged event chunks instead of the full ``(E_max, R)`` tensors —
    bit-identical results for any chunk size.  ``stream`` (chunked only;
    default ``True``) fetches each chunk's trace back as it completes so
    traces never accumulate on device.  ``chunk_size=None`` (default)
    keeps today's single-chunk monolithic scan.

    Each call leaves one ``run_batched`` call in :mod:`repro.obs`'s record:
    its phases as spans (``presample``, ``transfer``, ``dispatch``,
    ``scan``, ``fetch``, ``aggregate``; chunked: one ``simulate_chunked``
    span in place of the middle four) and its counters (the stream's
    lanes, ``h2d_bytes``, ``d2h_bytes``).
    """
    if chunk_size is not None and chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    if chunk_size is None and stream is not None:
        raise ValueError("stream is a knob of the chunked scan; pass chunk_size as well")
    with obs.span("run_batched"):
        prog = batched_program(policy, cfg, runs, use_kernel)
        if chunk_size is not None:
            stream = True if stream is None else stream
            _, trace = simulate_chunked(
                prog.events, chunk_size=chunk_size, stream=stream, shard=shard,
                **prog.kwargs,
            )
            if not stream:
                with obs.span("fetch"):
                    trace = jax.device_get(trace)
                obs.count("d2h_bytes", _nbytes(trace))
        else:
            with obs.span("transfer"):
                events, kwargs = prog.launch(shard)
            obs.count("h2d_bytes", _nbytes(prog.events))
            with obs.span("dispatch"):
                out = _simulate(events, **kwargs)
                # queue the copies back now, as ``device_get`` would: they
                # then start the moment the scan ends, with no host round trip
                for x in jax.tree.leaves(out):
                    if isinstance(x, jax.Array):
                        x.copy_to_host_async()
            with obs.span("scan"):
                jax.block_until_ready(out)
            with obs.span("fetch"):
                _, trace = jax.device_get(out)
            obs.count("d2h_bytes", _nbytes(out))
        with obs.span("aggregate"):
            return prog.aggregate(trace)


def aggregate(
    events: EventStream, trace: EventTrace, spec, runs: int
) -> Dict[str, float]:
    """Reduce per-event steady traces against host-known flags to
    ``run_many`` keys.

    ``spec`` is the ClusterSpec (or an int GPU count, back-compat).
    """
    if isinstance(spec, int):
        spec = _default_spec(spec)
    cap = float(spec.total_mem_slices)
    ok = np.asarray(trace.ok)
    meas = events.measuring
    samp = events.sample

    arrived = np.maximum(meas.sum(axis=0), 1)  # (R,)
    accepted = (ok & meas).sum(axis=0)
    nsamp = np.maximum(samp.sum(axis=0), 1)
    util = ((cap - trace.free_sum) / cap * samp).sum(axis=0) / nsamp
    active = (trace.active * samp).sum(axis=0) / nsamp
    frag = (trace.frag * samp).sum(axis=0) / nsamp
    arrivals_p = np.stack(
        [((events.pid == p) & meas).sum() for p in range(mig.NUM_PROFILES)]
    )
    rejects_p = np.stack(
        [((events.pid == p) & meas & ~ok).sum() for p in range(mig.NUM_PROFILES)]
    )
    return {
        "acceptance_rate": float((accepted / arrived).mean()),
        "allocated_workloads": float(accepted.mean()),
        "active_gpus": float(active.mean()),
        "utilization": float(util.mean()),
        "frag_severity": float(frag.mean()),
        "rejects_by_profile": rejects_p / runs,
        "arrivals_by_profile": arrivals_p / runs,
    }


def _aggregate_queued(
    events: EventStream, trace: EventTrace, spec, runs: int
) -> Dict[str, float]:
    """Reduce queued-protocol traces: acceptance folds in wait-admits, plus
    p50/p99 wait and Jain per-tenant fairness.

    The device trace records each wait-admit's *original* event index
    (``wadm_eidx``), so late acceptances and their waits reconstruct
    host-side: arrival ``e`` was ultimately accepted iff it was accepted
    in place (``ok``) or some later event admitted it from the wait ring;
    its wait is the slot distance between the two events (0 when
    immediate).  Acceptance/fairness attribute to the original arrival's
    measurement-window membership, exactly like the host simulator
    (:func:`repro.sim.simulator._run_steady_queued`).
    """
    if isinstance(spec, int):
        spec = _default_spec(spec)
    cap = float(spec.total_mem_slices)
    ok = np.asarray(trace.ok)
    wadm = np.asarray(trace.wadm_eidx)   # (E, R)
    slot = np.asarray(events.slot)
    tenant = np.asarray(events.tenant)
    meas = events.measuring
    samp = events.sample

    late_ok = np.zeros_like(ok)
    wait = np.zeros(ok.shape, np.float64)
    for r in range(runs):
        adm = np.flatnonzero(wadm[:, r] >= 0)
        orig = wadm[adm, r]
        late_ok[orig, r] = True
        wait[orig, r] = slot[adm, r] - slot[orig, r]
    acc_all = ok | late_ok

    arrived = np.maximum(meas.sum(axis=0), 1)  # (R,)
    accepted = (acc_all & meas).sum(axis=0)
    nsamp = np.maximum(samp.sum(axis=0), 1)
    util = ((cap - trace.free_sum) / cap * samp).sum(axis=0) / nsamp
    active = (trace.active * samp).sum(axis=0) / nsamp
    frag = (trace.frag * samp).sum(axis=0) / nsamp

    p50 = np.zeros(runs)
    p99 = np.zeros(runs)
    fair = np.zeros(runs)
    for r in range(runs):
        w = wait[:, r][acc_all[:, r] & meas[:, r]]
        p50[r] = np.percentile(w, 50) if len(w) else 0.0
        p99[r] = np.percentile(w, 99) if len(w) else 0.0
        tm = meas[:, r]
        rates = [
            (acc_all[:, r] & tm & (tenant[:, r] == tn)).sum()
            / (tm & (tenant[:, r] == tn)).sum()
            for tn in np.unique(tenant[:, r][tm])
        ]
        fair[r] = jain_fairness(rates)

    arrivals_p = np.stack(
        [((events.pid == p) & meas).sum() for p in range(mig.NUM_PROFILES)]
    )
    rejects_p = np.stack(
        [((events.pid == p) & meas & ~acc_all).sum() for p in range(mig.NUM_PROFILES)]
    )
    return {
        "acceptance_rate": float((accepted / arrived).mean()),
        "allocated_workloads": float(accepted.mean()),
        "active_gpus": float(active.mean()),
        "utilization": float(util.mean()),
        "frag_severity": float(frag.mean()),
        "rejects_by_profile": rejects_p / runs,
        "arrivals_by_profile": arrivals_p / runs,
        "wait_p50": float(p50.mean()),
        "wait_p99": float(p99.mean()),
        "fairness": float(fair.mean()),
        "queue_admits": float((late_ok & meas).sum(axis=0).mean()),
    }


def _aggregate_faulted(
    events: EventStream, trace: EventTrace, spec, runs: int
) -> Dict[str, float]:
    """Reduce faulted-protocol traces: the queued keys plus failure stats.

    The extra keys come from a host-side walk of the decision trace against
    the stream's fail lanes, reconstructing each workload's lifecycle
    (admit → maybe evict → maybe re-admit → complete):

    * ``goodput`` — fraction of measured arrivals whose lease *completed*
      (reached its end slot, or was still running at the horizon); an
      admitted-then-evicted-never-re-admitted workload counts against it;
    * ``evictions`` / ``evictions_lost`` — mean per-replica eviction count
      and the subset dropped outright (wait ring full or zero retry budget);
    * ``recovered_fraction`` — evictions later re-admitted / evictions
      (1.0 when nothing was evicted);
    * ``ttr_p50`` / ``ttr_p99`` — per-replica percentiles of the
      time-to-recovery (slots between eviction and re-admission), averaged.
    """
    if isinstance(spec, int):
        spec = _default_spec(spec)
    out = _aggregate_queued(events, trace, spec, runs)

    slot = np.asarray(events.slot)
    end = np.asarray(events.end)
    fail = np.asarray(events.fail)      # (E, R, M)
    wlive = np.asarray(events.wlive)
    new_slot = np.asarray(events.new_slot)
    meas = np.asarray(events.measuring)
    ok = np.asarray(trace.ok)
    gpu_tr = np.asarray(trace.gpu)
    wadm = np.asarray(trace.wadm_eidx)
    wgpu = np.asarray(trace.wadm_gpu)
    e_max = ok.shape[0]

    goodput = np.zeros(runs)
    recovered = np.zeros(runs)
    ttr_p50 = np.zeros(runs)
    ttr_p99 = np.zeros(runs)
    for r in range(runs):
        alive = {}    # original event index -> (gpu, end slot)
        done = set()  # leases that ran to completion
        pending = {}  # eviction awaiting re-admission -> eviction slot
        n_evict = 0
        n_recovered = 0
        ttrs = []
        for e in range(e_max):
            if not wlive[e, r]:
                continue
            t = slot[e, r]
            if new_slot[e, r]:
                # expire before faults — the device order: a lease ending
                # the very slot its GPU dies still completes
                for k in [k for k, (_, kend) in alive.items() if kend <= t]:
                    del alive[k]
                    done.add(k)
                downs = set(np.flatnonzero(fail[e, r]).tolist())
                if downs:
                    for k in [k for k, (g, _) in alive.items() if g in downs]:
                        del alive[k]
                        pending[k] = t
                        n_evict += 1
            a = int(wadm[e, r])
            if a >= 0:
                alive[a] = (int(wgpu[e, r]), int(end[a, r]))
                if a in pending:
                    n_recovered += 1
                    ttrs.append(t - pending.pop(a))
            if ok[e, r]:
                alive[e] = (int(gpu_tr[e, r]), int(end[e, r]))
        done.update(alive)  # still running at the horizon: never disrupted
        m = meas[:, r]
        goodput[r] = sum(1 for k in done if m[k]) / max(1, int(m.sum()))
        recovered[r] = (n_recovered / n_evict) if n_evict else 1.0
        ttr_p50[r] = np.percentile(ttrs, 50) if ttrs else 0.0
        ttr_p99[r] = np.percentile(ttrs, 99) if ttrs else 0.0

    out.update(
        goodput=float(goodput.mean()),
        evictions=float(np.asarray(trace.evicted).sum(axis=0).mean()),
        evictions_lost=float(np.asarray(trace.evict_lost).sum(axis=0).mean()),
        recovered_fraction=float(recovered.mean()),
        ttr_p50=float(ttr_p50.mean()),
        ttr_p99=float(ttr_p99.mean()),
    )
    return out


def _aggregate_cumulative(
    events: EventStream, trace: EventTrace, spec, runs: int, cfg: SimConfig
) -> Dict[str, float]:
    """Reduce per-event cumulative traces to ``run_many`` keys + demand-grid
    traces, replicating the Python simulator's grid-crossing and early-stop
    semantics exactly (both are host-computable from the presampled pids).
    """
    cap = float(spec.total_mem_slices)
    pid = np.asarray(events.pid)           # (E, R)
    ok = np.asarray(trace.ok)
    post_free = np.asarray(trace.post_free)
    post_active = np.asarray(trace.post_active)
    post_frag = np.asarray(trace.post_frag)
    e_max, _ = pid.shape

    frac = np.cumsum(mig.PROFILE_MEM[pid], axis=0) / cap  # (E, R)
    acc_cum = np.cumsum(ok, axis=0)                       # (E, R)
    arr_cum = np.arange(1, e_max + 1)[:, None]            # (E, 1)
    util = (cap - post_free) / cap

    grid = np.asarray(cfg.demand_grid, dtype=np.float64)
    G = len(grid)
    keys = (
        "acceptance_rate", "allocated_workloads", "active_gpus",
        "utilization", "frag_severity",
    )
    per_event = {
        "acceptance_rate": acc_cum / arr_cum,
        "allocated_workloads": acc_cum.astype(np.float64),
        "active_gpus": post_active.astype(np.float64),
        "utilization": util,
        "frag_severity": post_frag.astype(np.float64),
    }
    traces = {k: np.zeros((G, runs)) for k in keys}
    for i in range(G):
        crossed = frac >= grid[i]             # (E, R)
        hit = crossed.any(axis=0)             # (R,)
        idx = np.argmax(crossed, axis=0)      # first crossing event (per replica)
        for k in keys:
            v = per_event[k][idx, np.arange(runs)]
            if i > 0:  # tail-fill: an uncrossed point repeats the last recorded
                v = np.where(hit, v, traces[k][i - 1])
            else:
                v = np.where(hit, v, 0.0)
            traces[k][i] = v

    # early stop: the Python loop breaks once demand reached max_demand AND
    # every grid point was recorded — both depend only on the pid stream
    stop_at = max(float(cfg.max_demand), float(grid[-1]) if G else 0.0)
    stopped = frac >= stop_at
    stop = np.where(stopped.any(axis=0), np.argmax(stopped, axis=0), e_max - 1)
    ridx = np.arange(runs)
    processed = np.arange(e_max)[:, None] <= stop[None, :]  # (E, R)

    arrivals_p = np.stack(
        [((pid == p) & processed).sum() for p in range(mig.NUM_PROFILES)]
    )
    rejects_p = np.stack(
        [((pid == p) & processed & ~ok).sum() for p in range(mig.NUM_PROFILES)]
    )
    return {
        "acceptance_rate": float(per_event["acceptance_rate"][stop, ridx].mean()),
        "allocated_workloads": float(acc_cum[stop, ridx].mean()),
        "active_gpus": float(post_active[stop, ridx].mean()),
        "utilization": float(util[stop, ridx].mean()),
        "frag_severity": float(post_frag[stop, ridx].mean()),
        "rejects_by_profile": rejects_p / runs,
        "arrivals_by_profile": arrivals_p / runs,
        "traces": {k: v.mean(axis=1) for k, v in traces.items()},
        "demand_grid": grid,
    }
