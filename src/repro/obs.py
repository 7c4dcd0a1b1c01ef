"""Spans and counters of the batched engine, and the stage of each device op.

Host side.  Every whole call of :func:`repro.sim.batched.run_batched`
leaves one :class:`Call` in an in-memory record: a tree of host spans
(name, start, end, parent, call id) under the root span ``run_batched``,
and per-call counters.  The spans are::

    run_batched
      presample   validation and presampling (batched_program)
      transfer    the event stream to the device (placed on the replica mesh)
      dispatch    the scan's launch, and its outputs' copies back queued
      scan        the host waiting for the device to finish the scan
      fetch       the rest of the outputs' copies back to the host
      aggregate   the host reduction of the fetched trace

and a chunked call (``chunk_size=``) holds, in place of ``transfer`` to
``fetch``, one ``simulate_chunked`` span whose children are each chunk's
``transfer``, ``dispatch`` and ``fetch``, in the order they ran.  A span
opened while no call is open starts a call of its own (a bare
``batched_program`` or ``simulate_chunked``), so pick calls by the name of
their root span, not by position.  Each span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profile shows it
on the device's clock.  The record keeps the last :data:`MAX_CALLS`
calls; :func:`calls` is its one accessor.  There is nothing to turn on:
one span costs a few microseconds, and a call opens about seven.

Device side.  :meth:`repro.sim.batched.EngineCore.step` runs each stage
under ``jax.named_scope("stage.<name>")``; :func:`stage_map` reads, from
the compiled text of the very executable ``run_batched`` launches, which
stage owns each HLO instruction.  A profile's device ops are named after
those instructions, so the map turns a trace's op times into stage times.
Inside the stages, two sub-scopes mark work that cuts across them
(:data:`SCOPES`): ``rescore``, the rescoring of the GPUs an event touched
(expire, commit, a migration's landing GPU), and ``dispatch``, the
per-model-group gathers, padding, scatters and merge around the select,
delta and migrate kernels.  :func:`scope_map` reads them from the same
compiled text; they are not ``stage.*`` scopes, so the stage map is blind
to them.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax

#: calls the record keeps, oldest dropped first
MAX_CALLS = 48

#: the stages :meth:`EngineCore.step` scopes, in the order it runs them
STAGES = ("measure", "expire", "fault", "queue", "select", "migrate", "commit")
#: what an op outside every stage scope is attributed to: the loop's own
#: carry copies and control, the step's decode and trace assembly
UNSTAGED = "none"
#: the sub-scopes :func:`scope_map` reads (an op outside them: :data:`UNSTAGED`)
SCOPES = ("rescore", "dispatch")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int            # time.perf_counter_ns()
    end_ns: Optional[int]    # None while open
    parent: Optional[int]    # index of the parent in Call.spans; None for the root
    call: int                # Call.id

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Call:
    id: int
    spans: List[Span]             # the root first, then in the order opened
    counters: Dict[str, int]

    @property
    def root(self) -> Span:
        return self.spans[0]

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


_calls: collections.deque = collections.deque(maxlen=MAX_CALLS)
_ids = itertools.count()
_open = threading.local()


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []  # (call, span index), innermost last
    return _open.stack


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Time the block as a child of the innermost open span, or as the root
    of a new call when none is open."""
    stack = _stack()
    if stack:
        call, parent = stack[-1]
    else:
        call, parent = Call(next(_ids), [], {}), None
        _calls.append(call)
    s = Span(name, 0, None, parent, call.id)
    call.spans.append(s)
    stack.append((call, len(call.spans) - 1))
    try:
        with jax.profiler.TraceAnnotation(name):
            s.start_ns = time.perf_counter_ns()
            try:
                yield s
            finally:
                s.end_ns = time.perf_counter_ns()
    finally:
        stack.pop()


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` of the open call (none: dropped)."""
    stack = _stack()
    if stack:
        counters = stack[-1][0].counters
        counters[name] = counters.get(name, 0) + int(n)


def calls() -> List[Call]:
    """The kept calls, oldest first."""
    return list(_calls)


# ---------------------------------------------------------------------------
# Stage of each compiled instruction
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
_STAGE = re.compile(r"\bstage\.(\w+)")
_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(SCOPES) + r")(?![\w.])")


def _stage_of(op_name: str) -> str:
    """The innermost ``stage.*`` scope of an op's ``op_name``, else
    :data:`UNSTAGED`."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else UNSTAGED


def _scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` in an op's ``op_name``, else
    :data:`UNSTAGED`."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNSTAGED


def stages_of_text(hlo: str) -> Dict[str, str]:
    """``{instruction name: stage}`` for every instruction of an HLO
    module's text.

    An instruction's stage is the innermost ``stage.*`` scope of the
    ``op_name`` in its metadata.  A fusion takes the ``op_name`` of its
    fused computation's root (the op whose value it produces).  Where the
    root names no stage (XLA drops the metadata of some rewritten roots,
    and a multi-output fusion's root is a ``tuple``), the fusion takes the
    stage of the last fused instruction that names one, and else its own
    ``op_name``'s.  Instructions with no stage scope, such as the loop's
    carry copies or the ``while`` op itself, map to :data:`UNSTAGED`.
    """
    return _labels_of_text(hlo, _stage_of)


def scopes_of_text(hlo: str) -> Dict[str, str]:
    """``{instruction name: sub-scope}``: the innermost of :data:`SCOPES`
    that an instruction's ``op_name`` names, else :data:`UNSTAGED`; fusions
    as in :func:`stages_of_text`."""
    return _labels_of_text(hlo, _scope_of)


def _labels_of_text(hlo: str, label_of) -> Dict[str, str]:
    """``{instruction name: label_of(op_name)}``, a fusion labelled by its
    root, else by its last fused instruction with a label other than
    :data:`UNSTAGED`, else by its own ``op_name``."""
    names: List[str] = []
    op_name: Dict[str, str] = {}
    body: Dict[str, List[str]] = {}  # computation -> its instructions
    root_of: Dict[str, str] = {}     # computation -> its ROOT instruction
    fused: Dict[str, str] = {}       # fusion instruction -> fused computation
    computation = ""
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(2)
        names.append(name)
        body.setdefault(computation, []).append(name)
        if m.group(1):
            root_of[computation] = name
        meta = _OP_NAME.search(line)
        if meta is not None:
            op_name[name] = meta.group(1)
        f = _FUSION_CALLS.search(line)
        if f is not None:
            fused[name] = f.group(1)
    out: Dict[str, str] = {}
    for name in names:
        comp = fused.get(name)
        inner = [] if comp is None else [root_of.get(comp, "")] + body.get(comp, [])[::-1]
        labels = (label_of(op_name.get(i, "")) for i in inner)
        label = next((lb for lb in labels if lb != UNSTAGED), None)
        out[name] = label or label_of(op_name.get(name, ""))
    return out


def stage_map(prog, shard: Optional[bool] = None) -> Dict[str, str]:
    """``{HLO instruction name: stage}`` of the scan that
    :func:`~repro.sim.batched.run_batched` launches for ``prog`` (a
    :class:`~repro.sim.batched.BatchedProgram`) with ``shard``.

    The executable is built from :meth:`BatchedProgram.launch`, the same
    events, mesh and statics the entry point uses, so after a call it is
    the one that call ran (JAX's caches hand it back without compiling).
    On a TPU a profile's device op is named after its instruction
    (``copy.337``, ``fusion.633``), so the map assigns each op of a trace
    to a stage or to :data:`UNSTAGED`.
    """
    return stages_of_text(prog.lower(shard).compile().as_text())


def scope_map(prog, shard: Optional[bool] = None) -> Dict[str, str]:
    """``{HLO instruction name: sub-scope}`` (:data:`SCOPES`, else
    :data:`UNSTAGED`) of the same executable as :func:`stage_map`, read from
    the same compiled text: each op of a trace falls in the innermost
    ``rescore`` or ``dispatch`` scope around it, or in neither."""
    return scopes_of_text(prog.lower(shard).compile().as_text())
