"""Top-level public API: init/shutdown/get/put/wait/remote/kill.

Equivalent of ray ``python/ray/_private/worker.py`` public functions
(``ray.init:1406``, ``ray.get:2819``, ``ray.put:3002``, ``ray.wait:3073``,
``ray.kill:3253``, ``ray.get_actor:3218``).
"""

from __future__ import annotations

import atexit
import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Union

from .core import node as node_mod
from .core.api_frontend import ActorClass, ActorHandle, RemoteFunction, remote  # noqa: F401
from .core.config import GlobalConfig
from .core.core_worker import (
    CoreWorker,
    ObjectRefGenerator,
    global_worker,
    set_global_worker,
    try_global_worker,
)
from .core.exceptions import *  # noqa: F401,F403
from .core.ids import JobID, NodeID
from .core.placement import (  # noqa: F401
    PlacementGroup,
    SlicePlacementGroup,
    placement_group,
    placement_group_strategy,
    remove_placement_group,
)
from .core.task_spec import ObjectRef  # noqa: F401

logger = logging.getLogger(__name__)

_local_node: Optional[node_mod.Node] = None
_config_overrides_before: Optional[Dict[str, Any]] = None


def is_initialized() -> bool:
    return try_global_worker() is not None


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    labels: Optional[Dict[str, str]] = None,
    job_priority: Optional[int] = None,
    job_quota: Optional[Dict[str, float]] = None,
    _system_config: Optional[Dict[str, Any]] = None,
) -> "ClientContext":
    """Start a local cluster (head) or connect to an existing one.

    ``address``: None → start head locally; "auto" → discover local head;
    "host:port" → connect to that control plane (starts a local node agent
    for this machine if none is known).

    ``job_priority``/``job_quota``: multi-tenant arbitration inputs for
    this driver's job — higher priority may checkpoint-then-evict
    lower-priority placement groups when chips are contended; quota caps
    the job's durable reservations per resource (over-quota requests
    queue instead of failing).  See ``docs/scheduling.md``.

    .. note:: ``init()`` calls ``gc.collect()`` + ``gc.freeze()`` (a ~3x
       win on sequential call throughput — see the comment at the call
       site).  The freeze covers EVERY object alive at that moment,
       including application objects created before ``init()``: any
       cyclic garbage among them becomes uncollectable until
       ``shutdown()`` un-freezes it (plain refcounted objects are
       unaffected).  Long-lived drivers should therefore ``init()``
       early, before building large temporary object graphs.
    """
    global _local_node, _config_overrides_before
    if is_initialized():
        return ClientContext(global_worker())
    if _system_config:
        # _system_config is cluster-scoped (reference semantics): snapshot
        # the prior overrides so shutdown() restores them — a test process
        # init/shutdown cycle must not leak config into the next cluster.
        _config_overrides_before = dict(GlobalConfig._overrides)
        GlobalConfig.override(**_system_config)

    if address in (None, "local"):
        node = node_mod.Node(
            head=True, resources=resources, labels=labels, num_cpus=num_cpus,
            die_with_parent=True,
        )
        node.start()
        _local_node = node
        cp_address = node.cp_address
        agent_address = node.agent_address
        session_id = node.session_id
    else:
        if address == "auto":
            info = node_mod.read_head_info()
            if info is None:
                raise ConnectionError("no local head found (address='auto')")
            cp_address = info["cp_address"]
            session_id = info["session_id"]
        else:
            cp_address = address
            info = node_mod.read_head_info()
            session_id = info["session_id"] if info else "remote"
        ha_dir = info.get("ha_dir") if info else None
        node = node_mod.Node(
            head=False,
            cp_address=cp_address,
            resources=resources,
            labels=labels,
            session_id=session_id,
            num_cpus=num_cpus,
            ha_dir=ha_dir,
            # A connecting driver's local agent must die with the driver:
            # client processes exiting uncleanly were orphaning 0-CPU
            # agents on shared clusters.
            die_with_parent=True,
        )
        node.start()
        _local_node = node
        agent_address = node.agent_address

    worker = CoreWorker(
        CoreWorker.DRIVER,
        cp_address,
        agent_address,
        session_id,
        NodeID.from_random(),
        job_id=JobID.from_random(),
        job_priority=job_priority,
        job_quota=job_quota,
    )
    worker.start_threaded()
    set_global_worker(worker)
    atexit.register(shutdown)
    # Exclude the just-built permanent heap (imported modules, framework
    # state) from future GC traversals: the per-call garbage of a hot
    # submit/get loop triggers collections whose cost is dominated by
    # walking these long-lived objects — freezing them measured ~3x on
    # sequential actor-call throughput on a 1-core box.  (The classic
    # post-fork/post-init gc.freeze pattern; the reference leaves GC
    # untuned but its per-call path is C++, not collectable objects.)
    import gc

    gc.collect()
    gc.freeze()
    return ClientContext(worker)


def shutdown():
    global _local_node, _config_overrides_before
    worker = try_global_worker()
    if worker is not None:
        if _local_node is not None and worker.task_events is not None:
            # The driver that started the head leaves the session's trace
            # beside its logs: the store dies with the control plane.
            try:
                from .util import tracing

                tracing.write_spans(
                    os.path.join(_local_node.log_dir, "spans.jsonl"),
                    _local_node.session_id,
                )
            except Exception as e:  # noqa: BLE001 - shutdown goes on
                logger.warning("spans.jsonl was not written: %s", e)
        worker.shutdown()
        set_global_worker(None)
        # Undo init()'s gc.freeze: without this, every init/shutdown
        # cycle would strand the dead session's object graph (CoreWorker,
        # tasks, tracebacks — cycle-rich) in the permanent generation,
        # growing memory monotonically in long-lived drivers (pytest,
        # notebooks).  Unfreeze returns it to gen2 for normal collection;
        # the next init re-freezes whatever is genuinely permanent.
        import gc

        gc.unfreeze()
    if _local_node is not None:
        _local_node.stop()
        _local_node = None
    if _config_overrides_before is not None:
        # Restoring _overrides alone is not enough: override() also wrote
        # the values into the knob CACHE (__dict__), which would leak the
        # dead cluster's _system_config into the next init in this
        # process (observed: chaos knobs poisoning the next test).
        restored = _config_overrides_before
        _config_overrides_before = None
        GlobalConfig._overrides = {}
        GlobalConfig.reload()
        if restored:
            GlobalConfig.override(**restored)


class ClientContext:
    def __init__(self, worker: CoreWorker):
        self.worker = worker

    @property
    def address_info(self) -> dict:
        return {
            "cp_address": self.worker.cp_address,
            "agent_address": self.worker.agent_address,
            "session_id": self.worker.session_id,
        }

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        shutdown()


def get(
    refs: Union[ObjectRef, Sequence[ObjectRef]],
    *,
    timeout: Optional[float] = None,
):
    return global_worker().get(refs, timeout=timeout)


def put(value: Any) -> ObjectRef:
    return global_worker().put(value)


def wait(
    refs: List[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    return global_worker().wait(refs, num_returns=num_returns, timeout=timeout)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    global_worker().kill_actor(actor._actor_id, no_restart=no_restart)


def cancel(refs: Union[ObjectRef, Sequence[ObjectRef]]):
    """Best-effort cancel of the task(s) producing the given ref(s).

    A task still queued (owner-side lease queue or executor-side pipeline
    wait) is skipped and its return refs resolve to ``TaskCancelledError``;
    a task already executing runs to completion and resolves normally; a
    ref from ``put`` or an actor call is ignored.  Returns immediately —
    observe the outcome by getting the refs.
    """
    if isinstance(refs, ObjectRef):
        refs = [refs]
    global_worker().cancel_tasks(list(refs))


def get_actor(name: str, namespace: str = "") -> ActorHandle:
    info = global_worker().get_actor_by_name(name, namespace)
    if info is None or info["state"] == "DEAD":
        raise ValueError(f"actor {name!r} not found in namespace {namespace!r}")
    return ActorHandle(info["actor_id"])


def cluster_resources() -> Dict[str, float]:
    worker = global_worker()
    view = worker._run_sync(worker.cp.call("get_cluster_view"))
    total: Dict[str, float] = {}
    for info in view["nodes"].values():
        for k, v in info["snapshot"]["total"].items():
            total[k] = total.get(k, 0) + v
    return total


def available_resources() -> Dict[str, float]:
    worker = global_worker()
    view = worker._run_sync(worker.cp.call("get_cluster_view"))
    total: Dict[str, float] = {}
    for info in view["nodes"].values():
        for k, v in info["snapshot"]["available"].items():
            total[k] = total.get(k, 0) + v
    return total


def nodes() -> List[dict]:
    worker = global_worker()
    view = worker._run_sync(worker.cp.call("get_cluster_view"))
    return [
        {"node_id": nid.hex(), **info} for nid, info in view["nodes"].items()
    ]


def state_summary() -> dict:
    """Cluster state snapshot (ray.util.state analog)."""
    worker = global_worker()
    return worker._run_sync(worker.cp.call("get_state"))


def timeline_stats() -> dict:
    worker = global_worker()
    return worker._run_sync(worker.agent.call("debug_state"))


def timeline(filename: Optional[str] = None) -> List[dict]:
    """Dump the task timeline as Chrome-trace events (``ray timeline``
    analog; reference ``python/ray/_private/state.py:441,527``).  Load the
    written JSON in chrome://tracing or Perfetto."""
    from .util.state.api import StateApiClient, chrome_trace_events

    events = chrome_trace_events(
        StateApiClient().list_task_events(limit=100000)
    )
    if filename:
        import json as _json

        with open(filename, "w") as f:
            _json.dump(events, f)
    return events


def profile(event_name: str, extra: Optional[dict] = None):
    """Context manager recording a user profile span into the timeline
    (``ray.timeline`` profile-event analog)."""
    return global_worker().task_events.profile(event_name, extra)
