"""Distributed tracing: span propagation across task and actor calls.

Reference: ray ``python/ray/util/tracing/tracing_helper.py:34,165`` — an
OpenTelemetry context is injected into every task spec at submission and
extracted on the executing worker, so one trace follows a request through
arbitrary task/actor hops.  Native redesign (no opentelemetry dependency,
which this image does not ship): spans are (trace_id, span_id, parent_id,
name, start, end, attrs) tuples carried in a contextvar, injected into
``TaskSpec.trace_ctx``, and recorded through the existing task-event
buffer's profile channel — so traces land in the same control-plane store
the timeline and state API already read, and export as Chrome-trace rows.

Usage:
    with tracing.start_span("preprocess") as span:
        ...                       # user code; nested submits inherit
    spans = tracing.get_trace(span.trace_id)   # driver-side query

Two sinks, two clocks — which to use:

* ``start_span`` / ``record_span``: a request across processes.  Wall
  clock, control-plane store, read by ``cli timeline``, the dashboard and
  ``get_trace``.  May be held across ``await`` (it rides a contextvar).
* ``host_span``: work on ONE thread of the process that holds the chip.
  It is ``jax.profiler.TraceAnnotation``: while a profiler session runs
  (``start_profile`` here, ``jax.profiler.start_trace`` anywhere) the span
  lands in the same ``.xplane.pb`` as the device's operations, on the
  device trace's time base; with no session it costs about a microsecond
  and records nothing.  It must begin and end on one thread and must not
  be held across ``await`` or a generator ``yield`` (annotations are a
  per-thread stack).  Attributes are fixed at entry; what is known only
  at the end goes on a zero-length span written there.

How the two relate: no span is shared, an ANCHOR is.  ``engine.admit`` (a
``host_span``) carries the request's cluster ``trace_id`` and ``unix_ns``,
the wall clock (``time.time_ns()``) at the span's entry.  Every admission a
profiler session saw therefore gives one reading of (wall clock - the device
trace's clock): the median over a session's admissions places any
``start_span`` of those requests on the device trace's time base, and their
spread says how well (about a millisecond a session).

The first sink outlives the cluster: ``ray_tpu.shutdown()`` of the driver
that started the head writes every span row to ``<log dir>/spans.jsonl``
(``write_spans``; the row is in docs/observability.md).

The second sink says which part of the model a device operation belongs to:
``stop_profile()``, after the session has ended, writes
``<path>/programs.jsonl`` beside the trace (``write_programs``): for every
compiled program alive in the process ``{"module", "fingerprint", "ops":
{instruction: op_name}}``, the ``op_name`` holding the ``jax.named_scope``s
(``<family>.<part>``) the instruction was traced under.  Every process that
calls ``stop_profile`` writes its own, under the ``path`` it gave
``start_profile``.  The recipe: ``start_profile(dir)`` -> ``stop_profile()``
-> ``python3 -m benchmarks.lib.scopes report <dir>``.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import logging
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

# (trace_id, span_id) of the currently active span in THIS process/task.
_current: contextvars.ContextVar[Optional[Tuple[str, str]]] = (
    contextvars.ContextVar("rtpu_trace_ctx", default=None)
)


def _rand_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


# ------------------------------------------------- the device-clock sink
_NO_SPAN = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def host_span(name: str, **attrs):
    """A span on the device trace's clock (see the module docstring).

    Never imports jax: in a process that has not loaded it (the proxy, the
    controller, a driver) this is a shared no-op.  A jax that is still
    half-way through its import on another thread counts as not loaded."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
        if _annotation is None:
            return _NO_SPAN
    return _annotation(name, **attrs)


_profile_path: Optional[str] = None  # the running session's, as jax holds one


def start_profile(path: str) -> None:
    """Start a profiler session in THIS process (the one that holds the
    chip): device operations and ``host_span``s into ``path``, one file,
    one time base.  The profiler's Python tracer is off: it doubled a
    host-bound serving step (PERF.md, PR 24)."""
    global _profile_path
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(path, profiler_options=options)
    _profile_path = path


def stop_profile() -> None:
    """End the session, then write ``<path>/programs.jsonl``
    (``write_programs``): after the trace is closed, so the write is in no
    trace, and never at the trace's cost: a table that cannot be written is
    a warning."""
    global _profile_path
    import jax

    jax.profiler.stop_trace()
    path, _profile_path = _profile_path, None
    if path is None:  # a session someone else started
        return
    tables, t0 = os.path.join(path, "programs.jsonl"), time.perf_counter()
    try:
        rows = write_programs(tables)
    except Exception:  # the trace is written; its tables are an extra
        logger.warning("no programs.jsonl under %s", path, exc_info=True)
        return
    logger.info("%s: %d programs, %d bytes, %.3f s after the trace", tables,
                rows, os.path.getsize(tables), time.perf_counter() - t0)


# An instruction line of an optimized module's text and a computation's
# first line; ``calls=`` / ``to_apply=`` name the computation fused into the
# instruction or applied by it an element (a ``call``'s alone runs as itself).
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_HLO_APPLIED = re.compile(r"(?:calls|to_apply)=%([^\s,}]+)")
MIN_PROGRAM_OPS = 8


def program_ops(hlo_text: str) -> Dict[str, str]:
    """{instruction name: ``op_name``} of an optimized HLO module's text,
    for every instruction OUTSIDE fused computations (``""`` for one the
    compiler made and gave no ``op_name``: a name that is here and has no
    scope is told from a name that is nowhere).  An instruction inside a
    fusion never runs by itself: the fusion's own line does, under its
    root's ``op_name``, so a fusion across two ``jax.named_scope``s is
    booked to the scope of its root."""
    by_computation: Dict[str, Dict[str, str]] = {}
    fused, ops = set(), None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            ops = by_computation.setdefault(head.group(1), {})
            continue
        found = _HLO_INSTRUCTION.match(line) if ops is not None else None
        if not found:
            continue
        called = _HLO_APPLIED.search(line)
        if called and " call(" not in line:
            fused.add(called.group(1))
        op_name = _HLO_OP_NAME.search(line)
        ops[found.group(1)] = op_name.group(1) if op_name else ""
    return {name: op_name for computation, ops in by_computation.items()
            if computation not in fused for name, op_name in ops.items()}


def _live_executables() -> list:
    """Every compiled program alive in this process, on the backend that
    holds the chip."""
    import jax

    return jax.devices()[0].client.live_executables()


def write_programs(path: str) -> int:
    """Write the scope table of every compiled program alive in this
    process to ``path``, one JSON object a line: ``{"module": the HLO
    module's name (``jit__lambda``: what the trace's ``XLA Modules`` line
    has before the parenthesis), "fingerprint": the executable's, in hex,
    "ops": program_ops(its optimized text)}``.  The programs are the
    backend's ``live_executables()``: whatever could have run in the trace
    (the engine's decode step, every prefill rung, the samplers, a training
    step someone else compiled), with no registry and nothing kept on any
    path that runs when no session does.  Left out: a program with under
    ``MIN_PROGRAM_OPS`` instructions that have an ``op_name``
    (``jit_convert_element_type`` and its like: nothing a scope could
    split), and one whose text cannot be read (a warning).  Returns the
    number of rows."""
    rows = []
    for executable in _live_executables():
        try:
            module = executable.hlo_modules()[0]
            ops = program_ops(module.to_string())
            fingerprint = executable.fingerprint
            if isinstance(fingerprint, bytes):  # 32 raw bytes on a TPU
                fingerprint = fingerprint.hex()
            row = {"module": module.name, "fingerprint": str(fingerprint),
                   "ops": ops}
        except Exception:  # one program's table, not the others'
            logger.warning("a program's text could not be read",
                           exc_info=True)
            continue
        if sum(1 for op_name in ops.values() if op_name) >= MIN_PROGRAM_OPS:
            rows.append(row)
    with open(path + ".tmp", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    os.replace(path + ".tmp", path)
    return len(rows)


@dataclasses.dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start: float = 0.0
    end: float = 0.0
    attributes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value


def current_context() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) to inject into outgoing task specs."""
    return _current.get()


def set_context(ctx: Optional[Tuple[str, str]]):
    """Install an extracted trace context (executor side)."""
    return _current.set(ctx)


def _record(span: Span) -> None:
    from ray_tpu.core.core_worker import try_global_worker

    w = try_global_worker()
    if w is None or w.task_events is None:
        return
    # Ride the profile-event channel: same buffer, flush loop, and
    # control-plane store as the task timeline (shared shed + drop
    # accounting live in add_profile_row).
    w.task_events.add_profile_row(
        span.name,
        span.start,
        span.end,
        {
            "span": True,
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            **span.attributes,
        },
    )


@contextlib.contextmanager
def start_span(name: str, attributes: Optional[Dict[str, Any]] = None):
    """Open a span; children (including spans opened inside tasks this
    block submits) parent to it."""
    parent = _current.get()
    span = Span(
        trace_id=parent[0] if parent else _rand_id(16),
        span_id=_rand_id(),
        parent_id=parent[1] if parent else None,
        name=name,
        start=time.time(),
        attributes=dict(attributes or {}),
    )
    token = _current.set((span.trace_id, span.span_id))
    try:
        yield span
    finally:
        span.end = time.time()
        _current.reset(token)
        _record(span)


def detached_span(name: str,
                  attributes: Optional[Dict[str, Any]] = None,
                  context: Optional[Tuple[str, str]] = None) -> Span:
    """Open a span WITHOUT installing it as the current context.

    For long-lived scopes that cross ``yield`` boundaries (the streaming
    data scheduler's generator pump): a ``start_span`` block entered
    inside a generator would leak its contextvar into the consumer's
    context between yields.  Scope individual operations to the span
    with ``span_context``; close it with ``finish_span``.  ``context``:
    an explicit (trace_id, parent_span_id), as ``record_span`` takes one,
    for a thread that is not the caller's task (the serve controller's
    reconcile loop)."""
    parent = context if context is not None else _current.get()
    return Span(
        trace_id=parent[0] if parent else _rand_id(16),
        span_id=_rand_id(),
        parent_id=parent[1] if parent else None,
        name=name,
        start=time.time(),
        attributes=dict(attributes or {}),
    )


def finish_span(span: Span) -> None:
    """Close and record a ``detached_span``."""
    if not span.end:
        span.end = time.time()
    _record(span)


@contextlib.contextmanager
def span_context(span: Optional[Span]):
    """Install ``span`` as the current context for the block (submits in
    the block parent to it).  ``None`` is a no-op, so callers can hold an
    optional root without branching."""
    if span is None:
        yield
        return
    token = _current.set((span.trace_id, span.span_id))
    try:
        yield
    finally:
        _current.reset(token)


def record_span(name: str, start: float, end: float,
                attributes: Optional[Dict[str, Any]] = None,
                context: Optional[Tuple[str, str]] = None) -> Optional[Span]:
    """Record an already-measured interval as a completed span.

    ``context``: an explicit (trace_id, parent_span_id) — e.g. one
    extracted from a cross-process message — defaulting to the caller's
    current context.  Returns None (records nothing) when neither
    exists, so instrumentation sites can call this unconditionally."""
    ctx = context if context is not None else _current.get()
    if ctx is None:
        return None
    span = Span(
        trace_id=ctx[0],
        span_id=_rand_id(),
        parent_id=ctx[1],
        name=name,
        start=start,
        end=end,
        attributes=dict(attributes or {}),
    )
    _record(span)
    return span


@contextlib.contextmanager
def task_execution_span(spec) -> Any:
    """Executor-side: extract the submitted trace context (if any) and wrap
    the task body in a span (the tracing_helper wrap of task execution)."""
    ctx = getattr(spec, "trace_ctx", None)
    if ctx is None:
        yield None
        return
    token = set_context(tuple(ctx))
    try:
        with start_span(
            f"task:{spec.name}", {"task_id": spec.task_id.hex()}
        ) as span:
            yield span
    finally:
        _current.reset(token)


class Trace(list):
    """``get_trace`` result: a plain list of span rows (backwards
    compatible) carrying truncation metadata — when the task-event
    profile channel shed spans anywhere in the cluster, the trace may
    have holes and must not be read as complete."""

    truncated: bool = False
    dropped_spans: int = 0


def get_trace(trace_id: str, timeout: float = 30.0,
              min_spans: int = 0) -> Trace:
    """Fetch all recorded spans of a trace from the control plane.

    Remote workers flush their span buffers on a short period; with
    ``min_spans`` the query polls until that many spans arrived (or
    ``timeout`` elapses) instead of racing the flush.  The returned
    ``Trace`` is marked ``truncated`` when span rows were shed from any
    worker's task-event buffer (or the control-plane store cap) since
    the cluster started — the trace may be missing spans."""
    from ray_tpu.core.core_worker import global_worker

    w = global_worker()
    deadline = time.monotonic() + timeout
    while True:
        # Push local spans out before asking.
        w._run_sync(w.task_events.flush())
        reply = w._run_sync(
            w.cp.call("list_task_events", {}, timeout=timeout)
        )
        spans = Trace()
        for ev in reply.get("profile_events", ()):
            extra = ev.get("extra") or {}
            if extra.get("span") and extra.get("trace_id") == trace_id:
                spans.append(ev)
        spans.dropped_spans = int(reply.get("num_span_drops", 0))
        spans.truncated = spans.dropped_spans > 0
        if len(spans) >= min_spans or time.monotonic() > deadline:
            return spans
        time.sleep(0.2)


def write_spans(path: str, session_id: str = "",
                timeout: float = 10.0) -> int:
    """Write every span the control plane's store holds to ``path``, one
    JSON object a line: first ``{"session", "dropped_spans", "spans"}``
    (``dropped_spans`` as ``get_trace`` reads it: above 0 the file has
    holes), then a row a span (``name``, ``start``, ``end``, ``trace_id``,
    ``span_id``, ``parent_id``, the recording process's ``worker_id`` and
    ``node_id``, ``attributes``).  This process's buffer is flushed first
    and every node agent pulls its workers' once more, so what the cluster
    recorded up to this call is in the file.  Returns the number of spans."""
    from ray_tpu.core.core_worker import global_worker

    w = global_worker()
    w._run_sync(w.task_events.flush())
    w._run_sync(w.cp.call("collect_task_events", {}, timeout=timeout))
    reply = w._run_sync(
        w.cp.call("list_task_events", {"limit": 1}, timeout=timeout))
    rows = []
    for ev in reply.get("profile_events", ()):
        extra = dict(ev.get("extra") or {})
        if not extra.pop("span", False):
            continue
        rows.append({
            "name": ev["name"], "start": ev["start"], "end": ev["end"],
            "trace_id": extra.pop("trace_id", None),
            "span_id": extra.pop("span_id", None),
            "parent_id": extra.pop("parent_id", None),
            "worker_id": ev.get("worker_id"), "node_id": ev.get("node_id"),
            "attributes": extra,
        })
    head = {"session": session_id,
            "dropped_spans": int(reply.get("num_span_drops", 0)),
            "spans": len(rows)}
    with open(path + ".tmp", "w") as f:
        for row in (head, *rows):
            f.write(json.dumps(row, default=str) + "\n")
    os.replace(path + ".tmp", path)
    return len(rows)
