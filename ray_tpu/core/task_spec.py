"""Task/actor specifications and object references.

Equivalent of the reference's ``TaskSpecification`` (Ray
``src/ray/common/task/task_spec.h``) and ``ObjectRef``.  Specs are plain
picklable structs; function bodies are NOT embedded — they are exported once
per job to the control-plane KV store keyed by a content hash (the
function-manager pattern, Ray ``python/ray/_private/function_manager.py``)
and fetched+cached by workers.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .ids import ActorID, JobID, ObjectID, PlacementGroupID, TaskID
from .scheduler import SchedulingStrategy


def function_key(pickled_fn: bytes) -> str:
    return "fn:" + hashlib.sha256(pickled_fn).hexdigest()[:32]


@dataclass
class TaskSpec:
    task_id: TaskID
    job_id: JobID
    function_id: str  # KV key of the exported function
    name: str  # human-readable, for errors/state API
    # Serialized positional/keyword args.  ObjectRefs inside are replaced by
    # _RefMarker sentinels during serialization (see core_worker).  Either a
    # flat bytes encoding or a serialization.SerializedPayload whose header
    # and buffers ride the push frame out of band (framing v2 fast path).
    args_payload: Any
    num_returns: int = 1
    # Streaming-generator task: yields push to the owner as produced and
    # num_returns is 0 (the executor streams ONLY when the owner opted in
    # and registered a stream — a generator return without this flag is an
    # ordinary value).
    streaming: bool = False
    resources: Dict[str, float] = field(default_factory=dict)
    strategy: Optional[SchedulingStrategy] = None
    max_retries: int = 0
    retry_exceptions: bool = False
    owner_address: str = ""  # core-worker RPC address of the owner
    # Actor fields
    actor_id: Optional[ActorID] = None  # set for actor tasks
    actor_creation: bool = False
    sequence_number: int = -1  # per-(caller, actor) ordering
    # Placement group
    placement_group_id: Optional[PlacementGroupID] = None
    bundle_index: int = -1
    # Runtime env (round-1: env vars only)
    env_vars: Dict[str, str] = field(default_factory=dict)
    # Distributed tracing: (trace_id, span_id) of the submitting span
    # (reference: tracing_helper.py injects the OTel context here).
    trace_ctx: Optional[Tuple[str, str]] = None
    # Actor method to dispatch (actor tasks; falls back to ``name``).
    method_name: str = ""
    # Per-worker push pipelining cap for this task's lease pool (0 = the
    # max_tasks_in_flight_per_worker knob).  Coarse-grained tasks (data
    # block transforms) set 1: a straggler pipelined ahead of them on a
    # shared worker would serialize execution at the worker — exactly the
    # head-of-line blocking the streaming scheduler exists to avoid.
    pipeline_depth: int = 0

    # Wire-pickled once per task push: tuple state instead of the default
    # dataclass ``__dict__`` (which re-pickles every field-name string per
    # frame) — measurably cheaper on the per-call hot path and smaller on
    # the wire.  Owner-local bookkeeping attrs (``_held_refs``,
    # ``_queue_charge``, ``_lineage_outstanding``, ...) deliberately do
    # not travel; the executor re-derives what it needs (``_attempt``,
    # ``_recv_ts``) from the push payload.  Evolution rule: only APPEND
    # fields here (zip() tolerates a shorter peer tuple on neither side —
    # same-version processes only, enforced by the RPC handshake).
    def __getstate__(self):
        return (
            self.task_id, self.job_id, self.function_id, self.name,
            self.args_payload, self.num_returns, self.streaming,
            self.resources, self.strategy, self.max_retries,
            self.retry_exceptions, self.owner_address, self.actor_id,
            self.actor_creation, self.sequence_number,
            self.placement_group_id, self.bundle_index, self.env_vars,
            self.trace_ctx, self.method_name, self.pipeline_depth,
        )

    def __setstate__(self, state):
        (
            self.task_id, self.job_id, self.function_id, self.name,
            self.args_payload, self.num_returns, self.streaming,
            self.resources, self.strategy, self.max_retries,
            self.retry_exceptions, self.owner_address, self.actor_id,
            self.actor_creation, self.sequence_number,
            self.placement_group_id, self.bundle_index, self.env_vars,
            self.trace_ctx, self.method_name, self.pipeline_depth,
        ) = state

    @property
    def scheduling_class(self) -> Tuple:
        """Tasks with equal scheduling class can share leased workers."""
        return (
            tuple(sorted(self.resources.items())),
            self.placement_group_id,
            tuple(sorted(self.env_vars.items())),
            self.pipeline_depth,
        )

    def return_ids(self) -> List[ObjectID]:
        return [ObjectID.for_task_return(self.task_id, i) for i in range(self.num_returns)]


@dataclass
class ActorSpec:
    actor_id: ActorID
    job_id: JobID
    class_id: str  # KV key of exported class
    name: Optional[str]  # named actor (None = anonymous)
    namespace: str
    ctor_args_payload: Any  # bytes or serialization.SerializedPayload
    resources: Dict[str, float]
    max_restarts: int
    max_task_retries: int
    max_concurrency: int
    strategy: Optional[SchedulingStrategy] = None
    placement_group_id: Optional[PlacementGroupID] = None
    bundle_index: int = -1
    env_vars: Dict[str, str] = field(default_factory=dict)
    detached: bool = False
    owner_address: str = ""
    # "" = plain object plane; "device" keeps jax.Array returns resident in
    # HBM and hands out DeviceRefs (the reference's tensor_transport="nccl"
    # RDT analog; ray ``experimental/gpu_object_manager``).
    tensor_transport: str = ""
    # Per-actor override of the owning job's priority (None = inherit);
    # orders the control plane's pending-actor drain when freed capacity
    # is contended (docs/scheduling.md).
    priority: Optional[int] = None
    # (trace_id, span_id) of the creator's active span: the constructor
    # runs under it, as a task's body runs under ``TaskSpec.trace_ctx``.
    trace_ctx: Optional[Tuple[str, str]] = None


class ObjectRef:
    """Distributed future.  Owner-based: carries the address of the worker
    that owns the object's metadata and value (ownership model from the
    reference's NSDI'21 design — Ray ``src/ray/core_worker/reference_counter.h``).

    Picklable; when deserialized inside a worker, the local core worker
    registers a borrow so the owner keeps the object alive.
    """

    __slots__ = ("id", "owner_address", "_worker", "__weakref__")

    def __init__(self, object_id: ObjectID, owner_address: str, _worker=None):
        self.id = object_id
        self.owner_address = owner_address
        self._worker = _worker
        if _worker is not None:
            _worker.on_ref_created(self)

    def hex(self) -> str:
        return self.id.hex()

    def __repr__(self):
        return f"ObjectRef({self.id.hex()[:16]}, owner={self.owner_address})"

    def __hash__(self):
        return hash(self.id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.id == self.id

    def __del__(self):
        worker = self._worker
        if worker is not None:
            try:
                worker.on_ref_deleted(self.id, self.owner_address)
            except Exception:  # raylint: waive[RTL003] decref from __del__ races interpreter teardown
                pass

    def __reduce__(self):
        # Deserializing side re-binds to its local core worker (borrow).
        # If WE own the object, serialization means the ref is escaping to
        # another process: take a grace-period escape hold so the object
        # survives the window between our last local ref dying and the
        # receiver's incref arriving (reference: borrower registration in
        # reply metadata, reference_counter.cc).
        w = self._worker
        if w is not None:
            if self.owner_address == w.address:
                w.on_ref_escaped(self.id)
            else:
                # A borrower re-lending the ref: remember it so this
                # process's eventual decref is grace-delayed (the
                # sub-borrower's incref must reach the owner first).
                w.on_ref_relent(self.id)
        return (_rehydrate_ref, (self.id, self.owner_address))

    # Allow `await ref` inside async actors / driver coroutines.
    def __await__(self):
        from .core_worker import global_worker

        w = global_worker()
        return w.get_async(self).__await__()


def _rehydrate_ref(object_id: ObjectID, owner_address: str) -> ObjectRef:
    from .core_worker import try_global_worker

    w = try_global_worker()
    return ObjectRef(object_id, owner_address, _worker=w)


class _RefMarker:
    """Placeholder for an ObjectRef inside serialized task args; the executor
    resolves markers to values (or back to refs for nested refs) before
    invoking user code."""

    __slots__ = ("object_id", "owner_address", "nested")

    def __init__(self, object_id: ObjectID, owner_address: str, nested: bool = False):
        self.object_id = object_id
        self.owner_address = owner_address
        self.nested = nested
