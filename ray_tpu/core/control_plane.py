"""Cluster control plane — the GCS equivalent.

One process per cluster (Ray ``src/ray/gcs/gcs_server.h``).  Owns:
  - node table + health checking (GcsNodeManager / GcsHealthCheckManager)
  - cluster-wide KV store (InternalKV) — function exports, named actors, user KV
  - actor directory + scheduling + restart FT (GcsActorManager/Scheduler)
  - placement groups with two-phase Prepare/Commit across node agents
    (GcsPlacementGroupManager/Scheduler)
  - job table
  - pubsub of node/actor state changes (long-poll-free: server-push over the
    subscriber's existing connection, Ray ``src/ray/pubsub/``)
  - the authoritative eventually-consistent resource view (ray_syncer analog:
    agents push snapshots on every heartbeat).

Storage is pluggable (``store_client.py``, the reference's
``gcs/store_client/`` hierarchy): in-memory, or an embedded sqlite journal
under the session directory for restart fault tolerance.  With the durable
store, the KV, actor, placement-group and job tables survive a
control-plane crash: the restarted process reloads them, node agents
re-register on their next heartbeat ("reregister" reply), drivers likewise,
and pending actors/PGs resume scheduling — the
``test_gcs_fault_tolerance.py`` story without the external Redis.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import logging
import pickle
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set

from .admission import JobArbiter
from .config import GlobalConfig
from .ids import ActorID, JobID, NodeID, PlacementGroupID
from .resources import ResourceSet
from .rpc import (
    ClientPool,
    NotLeaderError,
    RpcServer,
    ServerConnection,
    resolve_service_lanes,
)
from .scheduler import ClusterScheduler, InfeasibleError
from .event_export import (
    ACTOR_DEFINITION,
    ACTOR_LIFECYCLE,
    JOB_LIFECYCLE,
    NODE_LIFECYCLE,
    PG_LIFECYCLE,
    EventRecorder,
)
from .store_client import FencedWriteError, make_store_client
from .task_events import TaskEventStore
from .task_spec import ActorSpec

logger = logging.getLogger(__name__)

# Actor lifecycle states (reference: rpc::ActorTableData::ActorState).
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"


class NodeEntry:
    def __init__(self, node_id: NodeID, agent_address: str, snapshot: dict):
        self.node_id = node_id
        self.agent_address = agent_address
        self.snapshot = snapshot
        self.last_heartbeat = time.monotonic()
        # Health sweeps in a row that found the heartbeat late but the
        # agent answering a ping (see _health_check_loop).
        self.late_sweeps = 0
        self.alive = True
        # Drain state machine (autoscaler scale-down): a draining node is
        # unschedulable but still heartbeats; the autoscaler terminates it
        # once drain_status reports it empty.
        self.draining = False
        self.drain_cause = ""
        self.drain_started = 0.0


class ActorEntry:
    def __init__(self, spec: ActorSpec):
        self.spec = spec
        self.state = PENDING_CREATION
        self.address: Optional[str] = None  # worker RPC address
        self.node_id: Optional[NodeID] = None
        self.num_restarts = 0
        self.incarnation = 0
        self.death_cause: Optional[str] = None

    def public_info(self) -> dict:
        return {
            "actor_id": self.spec.actor_id,
            "state": self.state,
            "address": self.address,
            "incarnation": self.incarnation,
            "name": self.spec.name,
            "death_cause": self.death_cause,
            "max_task_retries": self.spec.max_task_retries,
        }


class PlacementGroupEntry:
    def __init__(self, pg_id, bundles: List[dict], strategy: str, name: str,
                 job_id: Optional[JobID] = None,
                 priority: Optional[int] = None, created_seq: int = 0):
        self.pg_id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.name = name
        self.state = "PENDING"  # PENDING | CREATED | REMOVED
        self.bundle_nodes: Optional[List[NodeID]] = None
        # Arbitration: owning job, effective priority (resolved once at
        # creation), and a monotonic creation sequence — victim selection
        # is (priority asc, created_seq desc): lowest priority, newest
        # first, so the cheapest work (least sunk progress) dies first.
        self.job_id = job_id
        self.priority = (
            priority if priority is not None
            else GlobalConfig.sched_default_priority
        )
        self.created_seq = created_seq
        self.preemptions = 0

    def public_info(self) -> dict:
        return {
            "pg_id": self.pg_id,
            "state": self.state,
            "bundles": self.bundles,
            "strategy": self.strategy,
            "bundle_nodes": [n.hex() if n else None for n in (self.bundle_nodes or [])],
            "job_id": self.job_id.hex() if self.job_id else None,
            "priority": self.priority,
            "preemptions": self.preemptions,
        }


class ControlPlane:
    # Read-only SINGLE-KEY lookups the multi-lane RPC server may serve
    # directly on a lane thread: individual dict get/contains are
    # GIL-atomic and every mutation happens on the primary loop (see
    # rpc.RpcServer).  job_heartbeat's single timestamp store is likewise
    # atomic.  Handlers that ITERATE shared dicts (list_actors, kv_keys,
    # get_cluster_view, ...) are deliberately NOT here — iteration racing
    # a primary-loop insert raises "dict changed size during iteration" —
    # and everything stateful (node/actor/PG machines, KV writes, pubsub)
    # forwards to the primary loop.
    LANE_SAFE_METHODS = frozenset({
        "kv_get",
        "kv_exists",
        "get_actor_info",
        "get_named_actor",
        "get_placement_group",
        "job_heartbeat",
        "ping",
    })

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 session_id: str = "", store_path: Optional[str] = None,
                 store=None, ha_dir: Optional[str] = None, lease=None):
        self.session_id = session_id
        # HA mode (core/cp_ha.py): a pre-warmed journaled store and the
        # leader lease we serve under arrive from run_ha_candidate();
        # store_path keeps the plain single-CP sqlite path working.
        self.ha_dir = ha_dir
        self.lease = lease
        self._fenced = False
        self.server = RpcServer(self, host, port, lanes=resolve_service_lanes())
        self.scheduler = ClusterScheduler()
        self.arbiter = JobArbiter()
        self._pg_seq = 0
        # Actors being checkpoint-then-evicted: their worker-death reports
        # must not consume max_restarts (eviction is scheduler policy, not
        # a failure of the actor).
        self._evicting_actors: Set[ActorID] = set()
        self.nodes: Dict[NodeID, NodeEntry] = {}
        self.agent_clients = ClientPool()
        self._kv: Dict[str, Dict[str, bytes]] = {}  # namespace -> key -> value
        self.actors: Dict[ActorID, ActorEntry] = {}
        self.named_actors: Dict[tuple, ActorID] = {}  # (namespace, name) -> id
        self.placement_groups: Dict[PlacementGroupID, PlacementGroupEntry] = {}
        self.jobs: Dict[JobID, dict] = {}
        # job_heartbeat is lane-safe (runs on lane threads, PR 6); this
        # lock covers its liveness-stamp write against primary-loop
        # readers/expirers of the same job dict.
        self._heartbeat_lock = threading.Lock()
        # pubsub: channel -> set of subscriber connections
        self._subs: Dict[str, Set[ServerConnection]] = {}
        self._pending_actors: List[ActorID] = []
        self._schedule_tasks: set = set()
        self._pending_pgs: List[PlacementGroupID] = []
        # Placement-group group-commit queue (see the placement-group
        # section): (kind, entry, future) ops drained by one sweep task.
        self._pg_ops: deque = deque()
        self._pg_drain_task: Optional[asyncio.Task] = None
        self.pg_batch_stats = {
            "batches": 0,          # drain sweeps executed
            "batched_creates": 0,  # creates that shared a sweep with others
            "batched_removes": 0,  # removes that shared a sweep with others
            "fused_commits": 0,    # single-node groups committed in one RPC
            "rollbacks": 0,        # whole-group rollbacks on partial failure
        }
        self._bg_tasks: List[asyncio.Task] = []
        self.task_event_store = TaskEventStore()
        self._obs_seen: Dict[str, int] = {}  # worker -> last obs batch id
        # Aggregation beats: obs_report arrivals.  The remediation
        # controller's beat thread reads this (debug_control_plane) to
        # evaluate once per beat instead of polling blind.
        self.obs_beats = 0
        self._requested_resources: List[dict] = []
        self._recent_unplaceable: List[tuple] = []  # (ts, key, resources)
        # Over-quota task-lease demand: unlike queued actors/PGs it lives in
        # no PENDING table (the submitter backs off and retries), so it is
        # remembered here briefly for the autoscaler's load state.
        self._recent_queued_tasks: List[tuple] = []  # (ts, key, resources)
        self.store = store if store is not None else make_store_client(store_path)
        export_path = None
        if store_path:
            export_path = os.path.join(
                os.path.dirname(store_path), "events.jsonl"
            )
        elif ha_dir:
            export_path = os.path.join(ha_dir, "events.jsonl")
        self.events = EventRecorder(export_path)
        self._recovered = self._recover()
        # Grace window after a recovery: ALIVE actors whose node never
        # re-registers are declared dead only after agents have had a full
        # health-check timeout to reconnect.
        self._recovery_deadline = (
            time.monotonic() + GlobalConfig.health_check_timeout_s
            if self._recovered
            else None
        )

    # ----------------------------------------------------------- persistence
    _KV_SEP = "\x00"

    def _store_put(self, table: str, key: str, value: bytes) -> None:
        try:
            self.store.put(table, key, value)
        except FencedWriteError as e:
            self._on_fenced(e)

    def _store_delete(self, table: str, key: str) -> None:
        try:
            self.store.delete(table, key)
        except FencedWriteError as e:
            self._on_fenced(e)

    def _on_fenced(self, exc: FencedWriteError) -> None:
        """A newer leader exists: stop mutating, redirect the in-flight
        caller (NotLeaderError is retried by every client against the
        published endpoint), and exit shortly — after the error reply
        has had a beat to flush."""
        from .cp_ha import read_endpoint

        hint = None
        if self.ha_dir:
            info = read_endpoint(self.ha_dir)
            hint = info.get("address") if info else None
        if not self._fenced:
            self._fenced = True
            logger.error("fenced by a newer leader (%s); exiting: %s",
                         hint, exc)
            try:
                asyncio.get_running_loop().call_later(
                    0.2, os._exit, 3
                )
            except RuntimeError:
                os._exit(3)
        raise NotLeaderError(hint) from exc

    def _persist_kv(self, namespace: str, key: str, value,
                    delete: bool = False) -> None:
        if not self.store.durable:
            return
        # KV values are arbitrary picklable objects (the job SDK stores
        # dicts), not only bytes — pickle for the blob store.
        skey = namespace + self._KV_SEP + key
        if delete:
            self._store_delete("kv", skey)
        else:
            self._store_put("kv", skey, pickle.dumps(value))

    def _persist_actor(self, entry: ActorEntry) -> None:
        if not self.store.durable:
            return
        self._store_put(
            "actors",
            entry.spec.actor_id.hex(),
            pickle.dumps(
                {
                    "spec": entry.spec,
                    "state": entry.state,
                    "address": entry.address,
                    "node_id": entry.node_id,
                    "num_restarts": entry.num_restarts,
                    "incarnation": entry.incarnation,
                    "death_cause": entry.death_cause,
                }
            ),
        )

    def _persist_pg(self, entry: PlacementGroupEntry) -> None:
        if not self.store.durable:
            return
        self._store_put(
            "pgs",
            entry.pg_id.hex(),
            pickle.dumps(
                {
                    "pg_id": entry.pg_id,
                    "bundles": entry.bundles,
                    "strategy": entry.strategy,
                    "name": entry.name,
                    "state": entry.state,
                    "bundle_nodes": entry.bundle_nodes,
                    "job_id": entry.job_id,
                    "priority": entry.priority,
                    "created_seq": entry.created_seq,
                    "preemptions": entry.preemptions,
                }
            ),
        )

    def _persist_job(self, job_id: JobID) -> None:
        if not self.store.durable:
            return
        job = self.jobs[job_id]
        self._store_put(
            "jobs",
            job_id.hex(),
            pickle.dumps(
                {k: v for k, v in job.items()
                 if k not in ("last_heartbeat", "late_sweeps",
                              "grace_until")}
            ),
        )

    def _persist_obs_seen(self, wid: str, bid: int) -> None:
        # The obs-report dedupe watermark must survive failover: the
        # agents' pull staging redelivers at-least-once, and a standby
        # that forgot the acked ids would double-count the redelivered
        # batches' task events (the PR-16 regression test).
        if not self.store.durable:
            return
        self._store_put("obs_seen", wid, pickle.dumps(bid))

    def _recover(self) -> bool:
        """Rebuild in-memory state from the durable store (no-op for the
        in-memory backend).  Returns True if anything was loaded."""
        loaded = False
        for skey, value in self.store.scan("kv"):
            ns, key = skey.split(self._KV_SEP, 1)
            self._kv.setdefault(ns, {})[key] = pickle.loads(value)
            loaded = True
        for _key, blob in self.store.scan("actors"):
            d = pickle.loads(blob)
            entry = ActorEntry(d["spec"])
            entry.state = d["state"]
            entry.address = d["address"]
            entry.node_id = d["node_id"]
            entry.num_restarts = d["num_restarts"]
            entry.incarnation = d["incarnation"]
            entry.death_cause = d["death_cause"]
            self.actors[entry.spec.actor_id] = entry
            if entry.spec.name is not None and entry.state != DEAD:
                self.named_actors[(entry.spec.namespace, entry.spec.name)] = (
                    entry.spec.actor_id
                )
            if entry.state in (PENDING_CREATION, RESTARTING):
                self._pending_actors.append(entry.spec.actor_id)
            loaded = True
        for _key, blob in self.store.scan("pgs"):
            d = pickle.loads(blob)
            # .get() defaults: blobs persisted before the arbitration
            # fields existed must still load.
            entry = PlacementGroupEntry(
                d["pg_id"], d["bundles"], d["strategy"], d["name"],
                job_id=d.get("job_id"), priority=d.get("priority"),
                created_seq=d.get("created_seq", 0),
            )
            entry.state = d["state"]
            entry.bundle_nodes = d["bundle_nodes"]
            entry.preemptions = d.get("preemptions", 0)
            self._pg_seq = max(self._pg_seq, entry.created_seq + 1)
            self.placement_groups[entry.pg_id] = entry
            if entry.state == "PENDING":
                self._pending_pgs.append(entry.pg_id)
            loaded = True
        now = time.monotonic()
        for key, blob in self.store.scan("jobs"):
            job = pickle.loads(blob)
            job["last_heartbeat"] = now  # grace: drivers re-heartbeat soon
            self.jobs[JobID.from_hex(key)] = job
            loaded = True
        for wid, blob in self.store.scan("obs_seen"):
            self._obs_seen[wid] = pickle.loads(blob)
        self._recharge_arbiter()
        if loaded:
            logger.info(
                "recovered state: %d actors, %d pgs, %d jobs, %d kv ns",
                len(self.actors), len(self.placement_groups), len(self.jobs),
                len(self._kv),
            )
        return loaded

    def _recharge_arbiter(self) -> None:
        """Rebuild quota accounting from recovered state.  Charges are
        keyed and idempotent, so replaying them over whatever the arbiter
        already holds can never double-count — the invariant the
        CP-restart × preemption tests pin."""
        for job_id, job in self.jobs.items():
            self.arbiter.register_job(
                job_id.hex(), job.get("priority"), job.get("quota")
            )
        for actor_id, entry in self.actors.items():
            # PG-bound actors draw from their bundle (charged under the
            # PG key); charging them too would double-count.
            if entry.spec.placement_group_id is not None:
                continue
            if entry.state in (ALIVE, RESTARTING):
                job = entry.spec.job_id
                self.arbiter.charge(
                    ("actor", actor_id.hex()),
                    job.hex() if job else None,
                    ResourceSet(entry.spec.resources),
                )
        for pg_id, entry in self.placement_groups.items():
            # A victim checkpointed-and-evicted before the crash is
            # PENDING here: it recovers un-charged and re-admits on the
            # next sweep, exactly like any queued group.
            if entry.state == "CREATED":
                total = ResourceSet(entry.bundles[0])
                for b in entry.bundles[1:]:
                    total = total + ResourceSet(b)
                self.arbiter.charge(
                    ("pg", pg_id.hex()),
                    entry.job_id.hex() if entry.job_id else None,
                    total,
                )

    # ------------------------------------------------------------- lifecycle
    async def start(self) -> str:
        addr = await self.server.start()
        loop = asyncio.get_running_loop()
        self._bg_tasks.append(loop.create_task(self._health_check_loop()))
        logger.info("control plane listening on %s", addr)
        return addr

    async def stop(self):
        for t in self._bg_tasks:
            t.cancel()
        if self._pg_drain_task is not None and not self._pg_drain_task.done():
            self._pg_drain_task.cancel()
        await self.server.stop()
        await self.agent_clients.close_all()
        self.store.close()
        self.events.close()

    # ---------------------------------------------------------------- pubsub
    def _publish(self, channel: str, message: dict):
        dead = []
        for conn in self._subs.get(channel, ()):  # copy not needed; no await
            task = asyncio.get_running_loop().create_task(
                conn.push("pub", {"channel": channel, "message": message})
            )
            task.add_done_callback(lambda t: t.exception())  # swallow
        _ = dead

    def handle_subscribe(self, payload, conn: ServerConnection):
        for channel in payload["channels"]:
            self._subs.setdefault(channel, set()).add(conn)
        conn.metadata.setdefault("channels", set()).update(payload["channels"])
        return True

    def handle_unsubscribe(self, payload, conn: ServerConnection):
        for channel in payload["channels"]:
            self._subs.get(channel, set()).discard(conn)
        return True

    def on_connection_closed(self, conn: ServerConnection):
        for channel in conn.metadata.get("channels", ()):
            self._subs.get(channel, set()).discard(conn)
        # Job liveness is heartbeat-based (see _health_check_loop), NOT
        # connection-based: a transient TCP reset must not kill the job's
        # actors — the driver's RetryableRpcClient reconnects transparently.

    async def _cleanup_job(self, job_id: JobID):
        """Kill the job's non-detached actors."""
        for actor_id, entry in list(self.actors.items()):
            if entry.spec.job_id == job_id and not entry.spec.detached:
                await self._kill_actor_entry(entry, "job finished")

    # ----------------------------------------------------------------- nodes
    def handle_register_node(self, payload, conn):
        node_id = payload["node_id"]
        entry = NodeEntry(node_id, payload["agent_address"], payload["snapshot"])
        prev = self.nodes.get(node_id)
        if prev is not None and prev.draining:
            # An agent restart must not re-open a node the autoscaler is
            # retiring: the drain decision outlives the registration.
            entry.draining = True
            entry.drain_cause = prev.drain_cause
            entry.drain_started = prev.drain_started
        self.nodes[node_id] = entry
        self.scheduler.update_node(node_id, payload["snapshot"])
        if entry.draining:
            self.scheduler.set_draining(node_id, True)
        logger.info(
            "node %s registered (%s) resources=%s",
            node_id.hex()[:8],
            payload["agent_address"],
            payload["snapshot"]["total"],
        )
        self._publish("nodes", {"event": "added", "node_id": node_id})
        self.events.record(
            NODE_LIFECYCLE, node_id.hex(), "ALIVE",
            agent_address=payload["agent_address"],
            resources=payload["snapshot"].get("total", {}),
        )
        self._kick_pending()
        # Reconcile the agent's held bundles against the PG table: a
        # group removed or evicted while this node (or this control
        # plane) was away must release its reservation — otherwise a
        # remove that raced the re-registration window leaks the
        # agent-side resources forever.
        stale = []
        for pg_id in payload.get("held_pgs", ()):
            pg = self.placement_groups.get(pg_id)
            if pg is None or pg.state != "CREATED":
                stale.append(pg_id)
        return {"ok": True, "session_id": self.session_id, "drop_pgs": stale}

    def handle_heartbeat(self, payload, conn):
        node_id = payload["node_id"]
        entry = self.nodes.get(node_id)
        if entry is None:
            return {"ok": False, "reregister": True}
        entry.last_heartbeat = time.monotonic()
        entry.late_sweeps = 0
        entry.snapshot = payload["snapshot"]
        self.scheduler.update_node(node_id, payload["snapshot"])
        self._kick_pending()
        return {"ok": True}

    def handle_get_cluster_view(self, payload, conn):
        return {
            "nodes": {
                nid: {
                    "agent_address": e.agent_address,
                    "snapshot": e.snapshot,
                    "alive": e.alive,
                }
                for nid, e in self.nodes.items()
                if e.alive
            }
        }

    def _publish_own_metrics(self):
        """The control plane has no CoreWorker to push its registry
        through — it IS the KV server: record lane/PG-batch telemetry
        and drop the snapshot straight into the metrics namespace (not
        via handle_kv_put: metric payloads need no sqlite persistence)."""
        try:
            from ray_tpu.util import flight_recorder
            from ray_tpu.util import metrics as _m

            flight_recorder.record_rpc_lanes(self.server, role="control_plane")
            flight_recorder.record_pg_batches(self.pg_batch_stats)
            flight_recorder.record_cp_ha(self._cp_ha_info())
            payload = _m.payload_snapshot()
            if payload is not None:
                self._kv.setdefault(_m._REGISTRY_NS, {})["controlplane"] = (
                    payload
                )
        except Exception as e:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("control-plane metrics publish failed: %s", e)

    async def _answers(self, address, timeout: float) -> bool:
        """Does the process at ``address`` (an agent, a driver) answer a
        ping?  No address, refused, reset or timed out: no."""
        if not address:
            return False
        try:
            await self.agent_clients.get(address).call(
                "ping", timeout=timeout, retries=0
            )
            return True
        except Exception:  # noqa: BLE001 — refused, reset or timed out
            return False

    async def _health_check_loop(self):
        period = GlobalConfig.health_check_period_s
        timeout = GlobalConfig.health_check_timeout_s
        while True:
            await asyncio.sleep(period)
            now = time.monotonic()
            self._publish_own_metrics()
            late = [
                (node_id, entry, now - entry.last_heartbeat)
                for node_id, entry in self.nodes.items()
                if entry.alive and now - entry.last_heartbeat > timeout
            ]
            # Late is not dead: ask before burying.  A killed agent refuses
            # the connection at once; one whose machine stood still — or
            # whose heartbeats queue behind this loop's own stall — answers
            # as soon as it runs again (a TPU runtime starting or stopping
            # held agent heartbeat rounds up for 10 s, and this loop for
            # 7 s, on the v5e host).  An agent that answers pings (a lane
            # thread) while its main loop never heartbeats again is buried
            # on the third late sweep.
            answers = await asyncio.gather(
                *(self._answers(e.agent_address, timeout)
                  for _, e, _ in late)
            )
            for (node_id, entry, gap), answered in zip(late, answers):
                logger.warning(
                    "node %s: no heartbeat for %.1fs (limit %.1fs); ping %s",
                    node_id.hex()[:8], gap, timeout,
                    "answered" if answered else "failed",
                )
                entry.late_sweeps += 1
                if answered and entry.late_sweeps < 3:
                    entry.last_heartbeat = time.monotonic()
                else:
                    await self._on_node_dead(node_id)
            if (
                self._recovery_deadline is not None
                and now > self._recovery_deadline
            ):
                # Post-recovery reconciliation: ALIVE actors whose node
                # never re-registered are on lost nodes.
                self._recovery_deadline = None
                for actor_id, a in list(self.actors.items()):
                    if a.state == ALIVE and (
                        a.node_id not in self.nodes
                        or not self.nodes[a.node_id].alive
                    ):
                        await self._on_actor_worker_died(
                            actor_id, "node lost across control-plane restart"
                        )
            # Drivers get the agents' treatment: the same stall that holds
            # an agent's heartbeats up holds a driver's (on the v5e host one
            # serving run in 26 lost its job this way, 20 s after it
            # started, while the replica's TPU runtime came up).  A killed
            # driver refuses the ping at once and is cleaned up as before.
            # Only this loop writes ``late_sweeps`` / ``grace_until``; the
            # heartbeat handler (a lane thread) keeps to its one timestamp.
            late_jobs = []
            for job_id, job in list(self.jobs.items()):
                if job["state"] != "RUNNING":
                    continue
                if now - job.get("last_heartbeat", now) <= timeout:
                    job["late_sweeps"] = 0
                elif now > job.get("grace_until", 0.0):
                    late_jobs.append((job_id, job))
            answers = await asyncio.gather(
                *(self._answers(j.get("driver_address"), timeout)
                  for _, j in late_jobs)
            )
            for (job_id, job), answered in zip(late_jobs, answers):
                job["late_sweeps"] = job.get("late_sweeps", 0) + 1
                logger.warning(
                    "job %s: no driver heartbeat for %.1fs; ping %s",
                    job_id.hex(), now - job["last_heartbeat"],
                    "answered" if answered else "failed",
                )
                if answered and job["late_sweeps"] < 3:
                    job["grace_until"] = time.monotonic() + timeout
                elif job["state"] == "RUNNING":
                    job["state"] = "FINISHED"
                    self.events.record(JOB_LIFECYCLE, job_id.hex(), "FINISHED")
                    self._persist_job(job_id)
                    logger.info("job %s lost its driver; cleaning up",
                                job_id.hex())
                    await self._cleanup_job(job_id)

    async def _on_node_dead(self, node_id: NodeID):
        entry = self.nodes.get(node_id)
        if entry is None or not entry.alive:
            return
        entry.alive = False
        self.scheduler.remove_node(node_id)
        logger.warning("node %s marked dead", node_id.hex()[:8])
        self.events.record(NODE_LIFECYCLE, node_id.hex(), "DEAD")
        self._publish("nodes", {"event": "removed", "node_id": node_id})
        # Fail or restart actors that lived there.
        for actor_id, a in list(self.actors.items()):
            if a.node_id == node_id and a.state == ALIVE:
                await self._on_actor_worker_died(actor_id, "node died")

    # -------------------------------------------------------------------- kv
    def handle_kv_put(self, payload, conn):
        ns = self._kv.setdefault(payload.get("namespace", ""), {})
        overwrite = payload.get("overwrite", True)
        if not overwrite and payload["key"] in ns:
            return False
        ns[payload["key"]] = payload["value"]
        self._persist_kv(
            payload.get("namespace", ""), payload["key"], payload["value"]
        )
        return True

    def handle_kv_get(self, payload, conn):
        return self._kv.get(payload.get("namespace", ""), {}).get(payload["key"])

    def handle_kv_del(self, payload, conn):
        ns = self._kv.get(payload.get("namespace", ""), {})
        existed = ns.pop(payload["key"], None) is not None
        if existed:
            self._persist_kv(
                payload.get("namespace", ""), payload["key"], None, delete=True
            )
        return existed

    def handle_kv_keys(self, payload, conn):
        ns = self._kv.get(payload.get("namespace", ""), {})
        prefix = payload.get("prefix", "")
        return [k for k in ns if k.startswith(prefix)]

    def handle_kv_exists(self, payload, conn):
        return payload["key"] in self._kv.get(payload.get("namespace", ""), {})

    # ------------------------------------------------------------------ jobs
    def handle_register_job(self, payload, conn):
        job_id = payload["job_id"]
        priority = self.arbiter.register_job(
            job_id.hex(), payload.get("priority"), payload.get("quota")
        )
        self.jobs[job_id] = {
            "state": "RUNNING",
            "driver_address": payload.get("driver_address"),
            "start_time": time.time(),
            "last_heartbeat": time.monotonic(),
            "priority": priority,
            "quota": self.arbiter.quota_of(job_id.hex()),
        }
        conn.metadata["job_id"] = job_id
        self.events.record(
            JOB_LIFECYCLE, job_id.hex(), "RUNNING",
            driver_address=payload.get("driver_address"),
        )
        self._persist_job(job_id)
        return {"ok": True, "session_id": self.session_id, "priority": priority}

    def handle_job_heartbeat(self, payload, conn):
        job = self.jobs.get(payload["job_id"])
        if job is None:
            return {"ok": False, "reregister": True}
        with self._heartbeat_lock:
            job["last_heartbeat"] = time.monotonic()
        return {"ok": True}

    def handle_list_jobs(self, payload, conn):
        return {jid: dict(info) for jid, info in self.jobs.items()}

    # ---------------------------------------------------------------- actors
    async def handle_register_actor(self, payload, conn):
        spec: ActorSpec = payload["spec"]
        if spec.name is not None:
            key = (spec.namespace, spec.name)
            if key in self.named_actors:
                existing = self.actors.get(self.named_actors[key])
                if existing is not None and existing.state != DEAD:
                    if payload.get("get_if_exists"):
                        return existing.public_info()
                    raise ValueError(
                        f"actor name {spec.name!r} already taken in "
                        f"namespace {spec.namespace!r}"
                    )
            self.named_actors[key] = spec.actor_id
        entry = ActorEntry(spec)
        self.actors[spec.actor_id] = entry
        self.events.record(
            ACTOR_DEFINITION, spec.actor_id.hex(), "REGISTERED",
            name=spec.name or "", namespace=spec.namespace,
            resources=dict(spec.resources),
            max_restarts=spec.max_restarts,
        )
        self._persist_actor(entry)
        # Schedule in the background: registration replies immediately
        # (the reference's GCS actor registration is likewise async) so a
        # burst of .remote() creations pipelines instead of serializing on
        # worker spawn + __init__.  Callers' method submissions wait on
        # the PENDING_CREATION -> ALIVE state publish.
        self._schedule_actor_bg(entry)
        return entry.public_info()

    def _schedule_actor_bg(self, entry: ActorEntry):
        """Run _try_schedule_actor as a retained task: an escaping
        exception re-queues the actor for the next reconcile pass instead
        of silently stranding it in PENDING_CREATION."""
        task = asyncio.get_running_loop().create_task(
            self._try_schedule_actor(entry)
        )
        self._schedule_tasks.add(task)

        def done(t: asyncio.Task):
            self._schedule_tasks.discard(t)
            if t.cancelled():
                return
            exc = t.exception()
            if exc is not None:
                logger.warning(
                    "actor %s scheduling failed: %s; re-queueing",
                    entry.spec.actor_id, exc,
                )
                if entry.spec.actor_id not in self._pending_actors:
                    self._pending_actors.append(entry.spec.actor_id)

        task.add_done_callback(done)

    async def _try_schedule_actor(self, entry: ActorEntry):
        if entry.state == DEAD:
            return  # killed before scheduling got to it
        spec = entry.spec
        if spec.placement_group_id is not None:
            # PG-bound actor: its resources come from the bundle, which was
            # already carved OUT of the node's main pool — consulting
            # pick_node would wrongly demand the capacity twice (and fail
            # on a saturated node).  Target the bundle's node directly.
            pg = self.placement_groups.get(spec.placement_group_id)
            if pg is None or pg.state == "REMOVED":
                # Terminal: an actor bound to a gone PG can never schedule.
                entry.state = DEAD
                entry.death_cause = (
                    f"placement group {spec.placement_group_id} was removed"
                )
                self._publish_actor(entry)
                return
            if pg.state != "CREATED" or not pg.bundle_nodes:
                if spec.actor_id not in self._pending_actors:
                    self._pending_actors.append(spec.actor_id)
                return
            idx = spec.bundle_index if spec.bundle_index >= 0 else 0
            if idx >= len(pg.bundle_nodes):
                entry.state = DEAD
                entry.death_cause = (
                    f"bundle_index {idx} out of range for placement group "
                    f"with {len(pg.bundle_nodes)} bundles"
                )
                self._publish_actor(entry)
                return
            await self._create_actor_on_node(entry, pg.bundle_nodes[idx])
            return
        request = ResourceSet(spec.resources)
        job_hex = spec.job_id.hex() if spec.job_id else None
        charge_key = ("actor", spec.actor_id.hex())
        if job_hex and not self.arbiter.is_charged(charge_key):
            if not self.arbiter.admit(job_hex, request):
                # Over quota: queue (stay pending), never fail — the
                # next drain re-admits once usage drains below the cap.
                self.arbiter.mark_queued(charge_key, job_hex)
                self._record_sched_event("admission_queued", job=job_hex)
                if spec.actor_id not in self._pending_actors:
                    self._pending_actors.append(spec.actor_id)
                return
        try:
            node_id = self.scheduler.pick_node(
                ResourceSet(spec.resources), spec.strategy
            )
        except InfeasibleError:
            # No current node shape fits — keep pending rather than fail:
            # the autoscaler may add a node that does (its load state
            # includes this actor's demand), and the reference likewise
            # queues infeasible actors indefinitely.
            if spec.actor_id not in self._pending_actors:
                self._pending_actors.append(spec.actor_id)
            return
        if node_id is None:
            if spec.actor_id not in self._pending_actors:
                self._pending_actors.append(spec.actor_id)
            return
        # Charge before dispatch (idempotent by key): a RESTARTING actor
        # keeps its charge across the respawn instead of re-admitting.
        self.arbiter.charge(charge_key, job_hex, request)
        await self._create_actor_on_node(entry, node_id)

    async def _create_actor_on_node(self, entry: ActorEntry, node_id: NodeID):
        spec = entry.spec
        node = self.nodes.get(node_id)
        if node is None or not node.alive:
            if spec.actor_id not in self._pending_actors:
                self._pending_actors.append(spec.actor_id)
            return
        client = self.agent_clients.get(node.agent_address)
        try:
            # The agent's handler may wait for a worker spawn AND an
            # actor_init (each bounded by worker_startup_timeout_s) plus the
            # user __init__ runtime — our deadline must dominate both.
            reply = await client.call(
                "create_actor_worker",
                {"spec": spec, "incarnation": entry.incarnation},
                timeout=GlobalConfig.worker_startup_timeout_s * 2 + 30,
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("actor %s creation on node failed: %s", spec.actor_id, e)
            if spec.actor_id not in self._pending_actors:
                self._pending_actors.append(spec.actor_id)
            return
        if reply.get("init_error"):
            # User constructor raised: permanent failure, never retried.
            entry.state = DEAD
            entry.death_cause = f"actor __init__ failed: {reply['init_error']}"
            self._publish_actor(entry)
            return
        if entry.state == DEAD:
            # Killed while the (async) creation was in flight: the fresh
            # worker must not come up as a zombie holding its lease — kill
            # it and keep the DEAD state (the kill's worker-kill RPC was a
            # no-op because no worker existed yet).
            entry.node_id = node_id
            entry.address = reply["worker_address"]
            await self._kill_actor_worker(entry)
            entry.address = None
            return
        entry.node_id = node_id
        entry.address = reply["worker_address"]
        entry.state = ALIVE
        self._publish_actor(entry)

    def _publish_actor(self, entry: ActorEntry):
        if entry.state == DEAD:
            self.arbiter.release(("actor", entry.spec.actor_id.hex()))
            self.arbiter.unmark_queued(("actor", entry.spec.actor_id.hex()))
        # Every actor state transition publishes — persist + export events
        # at the same spot.
        self.events.record(
            ACTOR_LIFECYCLE, entry.spec.actor_id.hex(), entry.state,
            death_cause=entry.death_cause,
            num_restarts=entry.num_restarts,
        )
        self._persist_actor(entry)
        self._publish("actor:" + entry.spec.actor_id.hex(), entry.public_info())

    def handle_get_actor_info(self, payload, conn):
        entry = self.actors.get(payload["actor_id"])
        if entry is None:
            return None
        return entry.public_info()

    def handle_get_named_actor(self, payload, conn):
        key = (payload.get("namespace", ""), payload["name"])
        actor_id = self.named_actors.get(key)
        if actor_id is None:
            return None
        entry = self.actors[actor_id]
        info = entry.public_info()
        info["spec"] = entry.spec
        return info

    def handle_list_actors(self, payload, conn):
        return [e.public_info() for e in self.actors.values()]

    async def handle_actor_worker_died(self, payload, conn):
        await self._on_actor_worker_died(
            payload["actor_id"], payload.get("cause", "worker died")
        )
        return True

    async def _on_actor_worker_died(self, actor_id: ActorID, cause: str):
        entry = self.actors.get(actor_id)
        if entry is None or entry.state == DEAD:
            return
        if actor_id in self._evicting_actors:
            # Checkpoint-then-evict already moved this actor to
            # RESTARTING; the agent's death report for the eviction kill
            # must not burn a num_restarts credit (eviction is scheduler
            # policy, not an actor failure).
            self._evicting_actors.discard(actor_id)
            return
        restarts_allowed = (
            entry.spec.max_restarts == -1
            or entry.num_restarts < entry.spec.max_restarts
        )
        if restarts_allowed:
            entry.num_restarts += 1
            entry.incarnation += 1
            entry.state = RESTARTING
            entry.address = None
            self._publish_actor(entry)
            await self._try_schedule_actor(entry)
        else:
            entry.state = DEAD
            entry.death_cause = cause
            entry.address = None
            self._publish_actor(entry)

    async def handle_kill_actor(self, payload, conn):
        entry = self.actors.get(payload["actor_id"])
        if entry is None:
            return False
        if payload.get("no_restart", True):
            await self._kill_actor_entry(entry, "ray_tpu.kill")
        else:
            # Kill only the worker process; the death path restarts the
            # actor if restarts remain.
            await self._kill_actor_worker(entry)
            await self._on_actor_worker_died(
                entry.spec.actor_id, "ray_tpu.kill(no_restart=False)"
            )
        return True

    async def _kill_actor_worker(self, entry: ActorEntry):
        if entry.node_id is not None and entry.address is not None:
            node = self.nodes.get(entry.node_id)
            if node is not None and node.alive:
                client = self.agent_clients.get(node.agent_address)
                try:
                    await client.call(
                        "kill_worker", {"worker_address": entry.address}, retries=1
                    )
                except Exception as e:
                    logger.warning("kill_worker RPC to agent failed: %s", e)

    async def _kill_actor_entry(self, entry: ActorEntry, cause: str):
        await self._kill_actor_worker(entry)
        entry.state = DEAD
        entry.death_cause = cause
        entry.address = None
        self._publish_actor(entry)

    # ------------------------------------------------------- placement groups
    #
    # Group commit: create/remove requests enqueue on one ops queue and a
    # single drain task sweeps it.  A lone request drains immediately (no
    # batching timer — serial latency is untouched), while requests
    # arriving during an in-flight sweep coalesce into the next one: ONE
    # bundle-reservation sweep and one batched RPC per node per batch
    # instead of a prepare+commit round-trip pair per group.  Single-node
    # groups fuse prepare+commit into one ``reserve_bundles_batch`` agent
    # RPC (two-phase commit only pays for itself across nodes); multi-node
    # groups keep the classic two-phase protocol with per-node batched
    # prepare/commit/cancel.  Atomicity is per placement group: a group
    # whose bundles can't all be reserved rolls back every node it touched
    # and re-queues as PENDING; other groups in the same sweep are
    # unaffected (independent clients must not fate-share a batch).

    async def handle_create_placement_group(self, payload, conn):
        pg_id = payload["pg_id"]
        job_id = payload.get("job_id")
        entry = PlacementGroupEntry(
            pg_id, payload["bundles"], payload["strategy"],
            payload.get("name", ""),
            job_id=job_id,
            priority=self.arbiter.priority_of(
                job_id.hex() if job_id else None, payload.get("priority")
            ),
            created_seq=self._pg_seq,
        )
        self._pg_seq += 1
        self.placement_groups[pg_id] = entry
        self.events.record(PG_LIFECYCLE, pg_id.hex(), "PENDING")
        self._persist_pg(entry)
        await self._enqueue_pg_op("create", entry)
        # The reply carries the post-sweep state: CREATED in the common
        # case, so the client's ready() needs no follow-up poll.
        return entry.public_info()

    async def handle_remove_placement_group(self, payload, conn):
        entry = self.placement_groups.get(payload["pg_id"])
        if entry is None:
            return False
        # Through the ops queue so a remove can never overtake the create
        # sweep that is still reserving this group's bundles.
        await self._enqueue_pg_op("remove", entry)
        return True

    def _enqueue_pg_op(self, kind: str, entry: PlacementGroupEntry):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pg_ops.append((kind, entry, fut))
        if self._pg_drain_task is None or self._pg_drain_task.done():
            self._pg_drain_task = loop.create_task(self._drain_pg_ops())
        return fut

    async def _drain_pg_ops(self):
        while self._pg_ops:
            batch = []
            cap = max(1, GlobalConfig.pg_commit_batch_max)
            while self._pg_ops and len(batch) < cap:
                batch.append(self._pg_ops.popleft())
            creates = [(e, f) for k, e, f in batch if k == "create"]
            removes = [(e, f) for k, e, f in batch if k == "remove"]
            self.pg_batch_stats["batches"] += 1
            if len(creates) > 1:
                self.pg_batch_stats["batched_creates"] += len(creates)
            if len(removes) > 1:
                self.pg_batch_stats["batched_removes"] += len(removes)
            if creates:
                try:
                    await self._schedule_pg_batch([e for e, _f in creates])
                except Exception:  # noqa: BLE001 — a sweep bug fails its waiters, not the drain loop
                    logger.exception("placement-group commit sweep failed")
                for _e, fut in creates:
                    if not fut.done():
                        fut.set_result(None)
            if removes:
                try:
                    await self._remove_pg_batch([e for e, _f in removes])
                except Exception:  # noqa: BLE001
                    logger.exception("placement-group removal sweep failed")
                for _e, fut in removes:
                    if not fut.done():
                        fut.set_result(None)

    async def _try_schedule_pg(self, entry: PlacementGroupEntry):
        await self._schedule_pg_batch([entry])

    async def _schedule_pg_batch(self, entries: List[PlacementGroupEntry]):
        """One reservation sweep over a batch of pending groups.

        Node picks within a sweep don't see each other's reservations (the
        scheduler view is heartbeat-synced; agents are authoritative), so
        an over-packed pick simply fails its reservation and re-queues —
        the same convergence the serial path had."""
        placeable: List[tuple] = []  # (entry, assignment)
        # Highest priority first (oldest first within a band): when the
        # sweep covers more demand than fits — e.g. right after a
        # preemption freed capacity — the most important group places
        # first instead of whichever happened to enqueue first.
        entries = sorted(entries, key=lambda e: (-e.priority, e.created_seq))
        for entry in entries:
            if entry.state != "PENDING":
                continue
            bundles = [ResourceSet(b) for b in entry.bundles]
            total = bundles[0]
            for b in bundles[1:]:
                total = total + b
            job_hex = entry.job_id.hex() if entry.job_id else None
            charge_key = ("pg", entry.pg_id.hex())
            if job_hex and not self.arbiter.is_charged(charge_key):
                if not self.arbiter.admit(job_hex, total):
                    # Over quota: stay PENDING and retry on later sweeps
                    # (admission queues, never fails).
                    self.arbiter.mark_queued(charge_key, job_hex)
                    self._record_sched_event("admission_queued", job=job_hex)
                    self._pg_requeue(entry)
                    continue
            assignment = self.scheduler.pick_nodes_for_bundles(
                bundles, entry.strategy
            )
            if assignment is None:
                assignment = await self._try_preempt_for(entry, bundles)
            if assignment is None:
                self._pg_requeue(entry)
                continue
            # Charge before the reservation RPCs: co-admitted groups of
            # one job in the same sweep see each other's usage.  A failed
            # reservation re-queues through _pg_requeue, which releases.
            self.arbiter.charge(charge_key, job_hex, total)
            placeable.append((entry, assignment))
        if not placeable:
            return
        single_by_node: Dict[NodeID, List[tuple]] = {}
        multi: List[tuple] = []
        for entry, assignment in placeable:
            if len(set(assignment)) == 1:
                single_by_node.setdefault(assignment[0], []).append(
                    (entry, assignment)
                )
            else:
                multi.append((entry, assignment))
        tasks = [
            self._reserve_single_node(nid, items)
            for nid, items in single_by_node.items()
        ]
        if multi:
            tasks.append(self._two_phase_multi(multi))
        await asyncio.gather(*tasks)

    async def _reserve_single_node(self, nid: NodeID, items: List[tuple]):
        """Fused prepare+commit for groups placed wholly on one node —
        one agent round trip for the whole sub-batch."""
        node = self.nodes.get(nid)
        if node is None or not node.alive:
            for entry, _a in items:
                self._pg_requeue(entry)
            return
        client = self.agent_clients.get(node.agent_address)
        groups = [
            {
                "pg_id": entry.pg_id,
                "bundles": {i: b for i, b in enumerate(entry.bundles)},
            }
            for entry, _a in items
        ]
        try:
            res = await client.call("reserve_bundles_batch", {"groups": groups})
            results = res["results"]
        except Exception as e:  # noqa: BLE001 — agent racing shutdown/death
            logger.warning("reserve_bundles_batch to agent failed: %s", e)
            for entry, _a in items:
                self._pg_requeue(entry)
            return
        for entry, assignment in items:
            if results.get(entry.pg_id):
                self.pg_batch_stats["fused_commits"] += 1
                self._pg_created(entry, assignment)
            else:
                self._pg_requeue(entry)

    async def _two_phase_multi(self, multi: List[tuple]):
        """Classic two-phase commit for groups spanning nodes, with the
        per-node prepare/commit/cancel RPCs batched across groups."""
        # node -> pg_id -> {bundle_index: spec}
        by_node: Dict[NodeID, Dict] = {}
        for entry, assignment in multi:
            for idx, nid in enumerate(assignment):
                by_node.setdefault(nid, {}).setdefault(entry.pg_id, {})[idx] = (
                    entry.bundles[idx]
                )
        prepare_ok: Dict[NodeID, Dict] = {}

        async def prepare(nid):
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                prepare_ok[nid] = {}
                return
            client = self.agent_clients.get(node.agent_address)
            groups = [
                {"pg_id": pg_id, "bundles": bundles}
                for pg_id, bundles in by_node[nid].items()
            ]
            try:
                res = await client.call(
                    "prepare_bundles_batch", {"groups": groups}
                )
                prepare_ok[nid] = res["results"]
            except Exception as e:  # noqa: BLE001
                logger.warning("prepare_bundles_batch to agent failed: %s", e)
                prepare_ok[nid] = {}

        await asyncio.gather(*(prepare(nid) for nid in by_node))
        committed: List[tuple] = []
        cancels: Dict[NodeID, List] = {}
        for entry, assignment in multi:
            nodes = set(assignment)
            if all(prepare_ok.get(nid, {}).get(entry.pg_id) for nid in nodes):
                committed.append((entry, assignment))
            else:
                # Whole-group rollback: every node that DID reserve this
                # group's bundles releases them before the group re-queues.
                self.pg_batch_stats["rollbacks"] += 1
                for nid in nodes:
                    if prepare_ok.get(nid, {}).get(entry.pg_id):
                        cancels.setdefault(nid, []).append(entry.pg_id)
                self._pg_requeue(entry)

        async def cancel(nid, pg_ids):
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                return
            client = self.agent_clients.get(node.agent_address)
            try:
                await client.call("cancel_bundles_batch", {"pg_ids": pg_ids})
            except Exception as e:  # noqa: BLE001
                logger.warning("cancel_bundles_batch to agent failed: %s", e)

        commit_ok: Dict[NodeID, bool] = {}

        async def commit(nid, pg_ids):
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                commit_ok[nid] = False
                return
            client = self.agent_clients.get(node.agent_address)
            try:
                await client.call("commit_bundles_batch", {"pg_ids": pg_ids})
                commit_ok[nid] = True
            except Exception as e:  # noqa: BLE001
                logger.warning("commit_bundles_batch to agent failed: %s", e)
                commit_ok[nid] = False

        commit_by_node: Dict[NodeID, List] = {}
        for entry, assignment in committed:
            for nid in set(assignment):
                commit_by_node.setdefault(nid, []).append(entry.pg_id)
        await asyncio.gather(
            *(cancel(nid, pg_ids) for nid, pg_ids in cancels.items()),
            *(commit(nid, pg_ids) for nid, pg_ids in commit_by_node.items()),
        )
        for entry, assignment in committed:
            nodes = set(assignment)
            if all(commit_ok.get(nid) for nid in nodes):
                self._pg_created(entry, assignment)
            else:
                # A node died (or its commit RPC failed) between prepare
                # and commit: the group must NOT claim CREATED with only
                # part of its bundles live.  Release whatever this group
                # holds on its surviving nodes and re-queue it.
                self.pg_batch_stats["rollbacks"] += 1
                self._release_bundles(entry.pg_id, nodes)
                self._pg_requeue(entry)

    def _release_bundles(self, pg_id: PlacementGroupID, node_ids):
        """Best-effort fire-and-forget release of one group's bundles on
        the given (surviving) nodes — the rollback half of a partial
        commit or a reservation whose group was removed mid-flight."""

        async def release():
            for nid in node_ids:
                node = self.nodes.get(nid)
                if node is None or not node.alive:
                    continue
                client = self.agent_clients.get(node.agent_address)
                try:
                    await client.call(
                        "return_bundles_batch", {"pg_ids": [pg_id]}
                    )
                except Exception as e:  # noqa: BLE001 — node racing death
                    logger.debug("rollback return_bundles failed: %s", e)

        task = asyncio.get_running_loop().create_task(release())
        self._bg_tasks.append(task)
        task.add_done_callback(self._bg_tasks.remove)

    def _pg_created(self, entry: PlacementGroupEntry, assignment):
        if entry.state != "PENDING":
            # A remove raced this group's reservation sweep: the group
            # stays REMOVED — release what the sweep just reserved
            # instead of resurrecting it.
            self._release_bundles(entry.pg_id, set(assignment))
            return
        entry.bundle_nodes = list(assignment)
        entry.state = "CREATED"
        self.events.record(PG_LIFECYCLE, entry.pg_id.hex(), "CREATED")
        self._persist_pg(entry)
        self._publish("pg:" + entry.pg_id.hex(), entry.public_info())
        # Actors parked on this group while it was PENDING (an evicted
        # group's survivors waiting to resume) must not wait out a full
        # heartbeat interval before re-placing.
        self._kick_pending()

    def _pg_requeue(self, entry: PlacementGroupEntry):
        # A re-queued group holds no quota: it re-admits on its next sweep
        # (release is idempotent — a never-charged group is a no-op).
        self.arbiter.release(("pg", entry.pg_id.hex()))
        if entry.state == "PENDING" and entry.pg_id not in self._pending_pgs:
            self._pending_pgs.append(entry.pg_id)

    async def _remove_pg_batch(self, entries: List[PlacementGroupEntry]):
        by_node: Dict[NodeID, List] = {}
        for entry in entries:
            if entry.state == "REMOVED":
                continue
            for nid in set(entry.bundle_nodes or ()):
                node = self.nodes.get(nid)
                if node is None or not node.alive:
                    continue
                by_node.setdefault(nid, []).append(entry.pg_id)

        async def return_node(nid, pg_ids):
            client = self.agent_clients.get(self.nodes[nid].agent_address)
            try:
                await client.call("return_bundles_batch", {"pg_ids": pg_ids})
            except Exception as e:  # noqa: BLE001
                logger.debug("return_bundles_batch to agent failed: %s", e)

        await asyncio.gather(
            *(return_node(nid, pg_ids) for nid, pg_ids in by_node.items())
        )
        for entry in entries:
            if entry.state == "REMOVED":
                continue
            entry.state = "REMOVED"
            self.arbiter.release(("pg", entry.pg_id.hex()))
            self.arbiter.unmark_queued(("pg", entry.pg_id.hex()))
            self.events.record(PG_LIFECYCLE, entry.pg_id.hex(), "REMOVED")
            self._persist_pg(entry)
            if entry.pg_id in self._pending_pgs:
                self._pending_pgs.remove(entry.pg_id)
            self._publish("pg:" + entry.pg_id.hex(), entry.public_info())
        # Freed bundles may unblock evicted (PENDING) groups and their
        # parked actors; don't make them wait out a heartbeat.  The
        # retry sweep may still see a stale (heartbeat-synced) view and
        # re-queue — the next heartbeat's kick then lands it.
        self._kick_pending()

    # ------------------------------------------------------------- preemption
    #
    # Checkpoint-then-evict: when a higher-priority group cannot place,
    # pick victim groups (lowest priority first, newest first within a
    # priority — least sunk progress dies first), simulate feasibility
    # with the victims' resources added back to the scheduler view, and
    # only if the demand would then fit: fan out ``prepare_evict``
    # through the node agents (workloads checkpoint via their existing
    # restart machinery), kill the victim's actors WITHOUT consuming
    # max_restarts, reclaim the bundles, and re-queue the victim as
    # PENDING — it resumes automatically when capacity frees.  Every
    # eviction spends the demanding job's token-bucket preemption budget,
    # so a crash-looping high-priority job drains its burst, quarantines,
    # and provably cannot evict the world.

    def _record_sched_event(self, kind: str, **tags) -> None:
        try:
            from ray_tpu.util import flight_recorder

            flight_recorder.record_sched_event(kind, **tags)
        except Exception as e:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("sched event record failed: %s", e)

    def _select_victims(
        self,
        priority: int,
        bundles: List[ResourceSet],
        strategy: str,
    ) -> Optional[tuple]:
        """Pure simulation, no side effects: the smallest prefix of the
        victim ordering whose eviction would make ``bundles`` placeable.
        Returns (victims, assignment) or None when no set suffices.
        Victims must be STRICTLY lower priority — same-job victims are
        allowed (priority is per-group: a driver's latency burst evicting
        its own batch-training group is the single-driver sharing story),
        and the strict inequality is what prevents eviction cycles."""
        cands = [
            e
            for e in self.placement_groups.values()
            if e.state == "CREATED"
            and e.bundle_nodes
            and e.priority < priority
        ]
        cands.sort(key=lambda e: (e.priority, -e.created_seq))
        extra: Dict[NodeID, ResourceSet] = {}
        chosen: List[PlacementGroupEntry] = []
        for victim in cands:
            for idx, nid in enumerate(victim.bundle_nodes):
                r = ResourceSet(victim.bundles[idx])
                extra[nid] = extra[nid] + r if nid in extra else r
            chosen.append(victim)
            assignment = self.scheduler.pick_nodes_for_bundles(
                bundles, strategy, extra_available=extra
            )
            if assignment is not None:
                return chosen, assignment
        return None

    async def _try_preempt_for(
        self, entry: PlacementGroupEntry, bundles: List[ResourceSet]
    ) -> Optional[List[NodeID]]:
        """Preemption attempt on behalf of a PENDING group that cannot
        place.  Returns the post-eviction assignment, or None."""
        if not GlobalConfig.sched_preemption_enabled:
            return None
        sel = self._select_victims(entry.priority, bundles, entry.strategy)
        if sel is None:
            return None
        victims, assignment = sel
        job_hex = entry.job_id.hex() if entry.job_id else ""
        ok, reason = self.arbiter.spend_preemption(
            job_hex, len(victims), time.monotonic()
        )
        if not ok:
            self._record_sched_event("preemption_denied", job=job_hex)
            logger.warning(
                "preemption for pg %s denied: %s",
                entry.pg_id.hex()[:8], reason,
            )
            return None
        self._record_sched_event("preemption", job=job_hex,
                                 victims=len(victims))
        cause = (
            f"preempted by pg {entry.pg_id.hex()[:8]} "
            f"(priority {entry.priority} > {victims[0].priority})"
        )
        for victim in victims:
            await self._preempt_pg(victim, cause)
        return assignment

    async def _preempt_pg(self, victim: PlacementGroupEntry,
                          cause: str) -> int:
        """Checkpoint-then-evict one CREATED group.  Returns the number
        of workers that acked the checkpoint fan-out."""
        victim.preemptions += 1
        timeout = GlobalConfig.sched_evict_checkpoint_timeout_s
        nodes = set(victim.bundle_nodes or ())

        async def prep(nid):
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                return 0
            client = self.agent_clients.get(node.agent_address)
            try:
                reply = await client.call(
                    "prepare_evict",
                    {"pg_id": victim.pg_id, "timeout": timeout,
                     "cause": cause},
                    timeout=timeout + 5, retries=1,
                )
                return int(reply.get("acks", 0))
            except Exception as e:  # noqa: BLE001 — evict proceeds anyway
                logger.warning("prepare_evict to agent failed: %s", e)
                return 0

        acks = sum(await asyncio.gather(*(prep(nid) for nid in nodes)))
        # Kill the victim's actors through the eviction guard: they go
        # RESTARTING (incarnation bumped, num_restarts untouched) and
        # re-park as pending until their group re-creates.
        for actor_id, a in list(self.actors.items()):
            if a.spec.placement_group_id == victim.pg_id and a.state == ALIVE:
                self._evicting_actors.add(actor_id)
                a.incarnation += 1
                a.state = RESTARTING
                await self._kill_actor_worker(a)
                a.address = None
                self._publish_actor(a)
                if actor_id not in self._pending_actors:
                    self._pending_actors.append(actor_id)

        async def ret(nid):
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                return
            client = self.agent_clients.get(node.agent_address)
            try:
                await client.call(
                    "return_bundles_batch", {"pg_ids": [victim.pg_id]}
                )
            except Exception as e:  # noqa: BLE001 — node racing death
                logger.warning("preemption bundle return failed: %s", e)

        await asyncio.gather(*(ret(nid) for nid in nodes))
        victim.state = "PENDING"
        victim.bundle_nodes = None
        self.events.record(
            PG_LIFECYCLE, victim.pg_id.hex(), "PREEMPTED", cause=cause
        )
        self._record_sched_event(
            "preemption_victim",
            pg=victim.pg_id.hex(), priority=victim.priority, acks=acks,
        )
        # Crash consistency across tables: the group's PENDING flip and
        # its evicted actors' RESTARTING records land as ONE commit — a
        # crash here can never recover a CREATED group whose actors were
        # already evicted (which would leak phantom bundle charges).
        with self.store.transaction():
            self._persist_pg(victim)
            for _aid, a in list(self.actors.items()):
                if a.spec.placement_group_id == victim.pg_id:
                    self._persist_actor(a)
        self._pg_requeue(victim)  # releases the victim's quota charge
        self._publish("pg:" + victim.pg_id.hex(), victim.public_info())
        logger.info(
            "preempted pg %s (priority %d, %d checkpoint acks): %s",
            victim.pg_id.hex()[:8], victim.priority, acks, cause,
        )
        return acks

    async def handle_request_preemption(self, payload, conn):
        """Explicit preemption on behalf of a high-priority demand that
        is not itself a pending placement group — the remediation
        controller's fair-share actuator (queue pressure on a
        high-priority serve deployment frees training capacity here
        instead of declining at max_replicas)."""
        if not GlobalConfig.sched_preemption_enabled:
            return {"preempted": [], "reason": "preemption disabled"}
        bundles = [ResourceSet(b) for b in payload["bundles"]]
        priority = int(
            payload.get("priority") or GlobalConfig.sched_default_priority
        )
        job_id = payload.get("job_id")
        sel = self._select_victims(
            priority, bundles, payload.get("strategy", "PACK")
        )
        if sel is None:
            return {
                "preempted": [],
                "reason": "no lower-priority victim set frees enough capacity",
            }
        victims, _assignment = sel
        max_victims = payload.get("max_victims")
        if max_victims is not None and len(victims) > int(max_victims):
            return {
                "preempted": [],
                "reason": (
                    f"needs {len(victims)} victims > max_victims {max_victims}"
                ),
            }
        job_hex = job_id.hex() if job_id else "__remediation__"
        ok, reason = self.arbiter.spend_preemption(
            job_hex, len(victims), time.monotonic()
        )
        if not ok:
            self._record_sched_event("preemption_denied", job=job_hex)
            return {"preempted": [], "reason": reason}
        self._record_sched_event("preemption", job=job_hex,
                                 victims=len(victims))
        cause = payload.get("cause") or "remediation request_preemption"
        out = []
        for victim in victims:
            await self._preempt_pg(victim, cause)
            out.append(victim.pg_id.hex())
        self._kick_pending()
        return {"preempted": out, "reason": ""}

    def handle_get_placement_group(self, payload, conn):
        entry = self.placement_groups.get(payload["pg_id"])
        return entry.public_info() if entry else None

    def handle_list_placement_groups(self, payload, conn):
        return [e.public_info() for e in self.placement_groups.values()]

    # ------------------------------------------------------- pending retries
    def _actor_priority(self, actor_id) -> int:
        """Effective drain priority of a pending actor: its spec override
        if set, else the owning job's registered priority."""
        entry = self.actors.get(actor_id)
        if entry is None:
            return GlobalConfig.sched_default_priority
        spec = entry.spec
        job_hex = spec.job_id.hex() if spec.job_id else None
        return self.arbiter.priority_of(
            job_hex, getattr(spec, "priority", None)
        )

    def _kick_pending(self):
        if self._pending_actors or self._pending_pgs:
            asyncio.get_running_loop().create_task(self._drain_pending())

    async def _drain_pending(self):
        pending_actors, self._pending_actors = self._pending_actors, []
        # Highest effective priority first (stable, so FIFO within a
        # priority band): freed capacity after an eviction or node join
        # goes to the most important waiter, not the oldest one.
        pending_actors.sort(key=self._actor_priority, reverse=True)
        for actor_id in pending_actors:
            entry = self.actors.get(actor_id)
            if entry is not None and entry.state in (PENDING_CREATION, RESTARTING):
                await self._try_schedule_actor(entry)
        pending_pgs, self._pending_pgs = self._pending_pgs, []
        retry = [
            entry
            for entry in (self.placement_groups.get(p) for p in pending_pgs)
            if entry is not None and entry.state == "PENDING"
        ]
        if retry:
            # Through the ops queue, not a direct sweep: retries must
            # serialize with concurrent removes exactly like fresh
            # creates (a direct sweep racing a remove could resurrect a
            # REMOVED group with leaked bundles).
            await asyncio.gather(
                *(self._enqueue_pg_op("create", e) for e in retry)
            )

    # -------------------------------------------------------------- lookups
    def handle_pick_node_for_lease(self, payload, conn):
        """Spillback target selection for agents that can't fit a lease.
        Unplaceable demands are remembered briefly so the autoscaler's load
        state sees them (they live in no queue while the submitter backs
        off and retries)."""
        pg_id = payload.get("placement_group_id")
        if pg_id is not None:
            # PG-bound lease: the only valid target is the bundle's node
            # (its resources live in that node's bundle pool).
            entry = self.placement_groups.get(pg_id)
            if entry is None or entry.state == "REMOVED":
                # Fatal (not retry-until-autoscaled): the PG is gone.
                return {
                    "infeasible": True,
                    "fatal": True,
                    "error": f"placement group {pg_id} was removed",
                }
            if entry.state != "CREATED" or not entry.bundle_nodes:
                return {"node_id": None}  # PG pending; submitter retries
            idx = payload.get("bundle_index", -1)
            idx = idx if idx >= 0 else 0
            if idx >= len(entry.bundle_nodes):
                return {
                    "infeasible": True,
                    "fatal": True,
                    "error": (
                        f"bundle_index {idx} out of range for placement "
                        f"group with {len(entry.bundle_nodes)} bundles"
                    ),
                }
            node_id = entry.bundle_nodes[idx]
            node = self.nodes.get(node_id)
            if node is None or not node.alive:
                return {"node_id": None}
            return {"node_id": node_id, "agent_address": node.agent_address}
        job_hex = payload.get("job_id")
        if job_hex and not self.arbiter.admit(
            job_hex, ResourceSet(payload["resources"])
        ):
            # Over-quota task lease: queue (submitter backs off and
            # retries), surfaced as a queued-by-admission count and as
            # autoscaler demand (a quota raise or freed capacity elsewhere
            # may admit it — the cluster should be ABLE to run it).
            self.arbiter.note_queued_event(job_hex)
            self._record_sched_event("admission_queued", job=job_hex)
            self._note_queued_task(
                payload["resources"], owner=payload.get("owner_id")
            )
            return {"node_id": None}
        try:
            node_id = self.scheduler.pick_node(
                ResourceSet(payload["resources"]),
                payload.get("strategy"),
                preferred=payload.get("preferred"),
            )
        except InfeasibleError as e:
            self._note_unplaceable(
                payload["resources"], owner=payload.get("owner_id")
            )
            return {"infeasible": True, "error": str(e)}
        if node_id is None:
            self._note_unplaceable(
                payload["resources"], owner=payload.get("owner_id")
            )
            return {"node_id": None}
        # Satisfied demand must stop driving scale-up: a granted lease
        # retires its own window entries, or the autoscaler would keep
        # seeing a phantom pending task for up to the window length
        # (and launch a replacement the moment the hosting node drains).
        self._clear_demand(payload["resources"], payload.get("owner_id"))
        return {
            "node_id": node_id,
            "agent_address": self.nodes[node_id].agent_address,
        }

    # ------------------------------------------------------------- autoscaler
    #
    # Drain state machine (scale-down): mark unschedulable -> evict
    # residents through the prepare_evict checkpoint protocol -> the
    # autoscaler polls drain_status until the node is empty -> provider
    # terminate -> drain_complete retires the entry.  Drain flags are
    # in-memory only: after a control-plane failover the autoscaler's
    # next status poll sees draining=False and simply re-issues the mark
    # (drain_node is idempotent).

    def _resolve_node_id(self, raw) -> Optional[NodeID]:
        if isinstance(raw, NodeID):
            return raw
        try:
            return NodeID.from_hex(raw)
        except Exception:  # noqa: BLE001 — malformed client input
            return None

    async def handle_drain_node(self, payload, conn):
        """Mark a node unschedulable and evict its residents (autoscaler
        scale-down; reference: ray ``DrainNode`` GCS RPC).  Idempotent;
        ``cancel`` reverses a drain that has not terminated yet."""
        node_id = self._resolve_node_id(payload.get("node_id"))
        entry = self.nodes.get(node_id) if node_id is not None else None
        if entry is None:
            return {"ok": False, "error": "unknown node"}
        if payload.get("cancel"):
            if entry.draining:
                entry.draining = False
                entry.drain_cause = ""
                self.scheduler.set_draining(node_id, False)
                self.events.record(
                    NODE_LIFECYCLE, node_id.hex(), "DRAIN_CANCELLED"
                )
                self._kick_pending()
            return {"ok": True, "draining": False}
        cause = payload.get("cause") or "autoscaler scale-down"
        already = entry.draining
        if not already:
            entry.draining = True
            entry.drain_cause = cause
            entry.drain_started = time.monotonic()
            self.scheduler.set_draining(node_id, True)
            self.events.record(
                NODE_LIFECYCLE, node_id.hex(), "DRAINING", cause=cause
            )
            logger.info("draining node %s: %s", node_id.hex()[:8], cause)
        # Evict resident placement groups through the checkpoint-then-
        # evict protocol.  No preemption-budget spend: drain is cluster
        # policy, not one tenant demanding another's chips.
        evicted = []
        for pg in list(self.placement_groups.values()):
            if (
                pg.state == "CREATED"
                and pg.bundle_nodes
                and node_id in pg.bundle_nodes
            ):
                await self._preempt_pg(pg, f"node drain: {cause}")
                evicted.append(pg.pg_id.hex())
        migrated = 0
        for actor_id, a in list(self.actors.items()):
            if (
                a.node_id == node_id
                and a.state == ALIVE
                and not a.spec.placement_group_id
            ):
                # Same guard as preemption: the kill must not consume
                # max_restarts — the actor re-places on another node.
                self._evicting_actors.add(actor_id)
                a.incarnation += 1
                a.state = RESTARTING
                await self._kill_actor_worker(a)
                a.address = None
                self._persist_actor(a)
                self._publish_actor(a)
                if actor_id not in self._pending_actors:
                    self._pending_actors.append(actor_id)
                migrated += 1
        if evicted or migrated:
            self._record_sched_event(
                "drain_evict", node=node_id.hex()[:8],
                pgs=len(evicted), actors=migrated,
            )
        self._kick_pending()
        return {
            "ok": True,
            "draining": True,
            "already_draining": already,
            "evicted_pgs": evicted,
            "migrated_actors": migrated,
        }

    def handle_drain_status(self, payload, conn):
        """Is this draining node empty yet?  The autoscaler polls this
        until ``drained`` before calling the provider's terminate."""
        node_id = self._resolve_node_id(payload.get("node_id"))
        entry = self.nodes.get(node_id) if node_id is not None else None
        if entry is None:
            # Gone entirely — nothing left to wait for.
            return {"known": False, "draining": False, "drained": True}
        resident_pgs = sum(
            1
            for pg in self.placement_groups.values()
            if pg.state == "CREATED"
            and pg.bundle_nodes
            and node_id in pg.bundle_nodes
        )
        resident_actors = sum(
            1
            for a in self.actors.values()
            if a.node_id == node_id and a.state == ALIVE
        )
        snap = entry.snapshot or {}
        busy = (
            bool(snap.get("pending_demands"))
            or snap.get("available", {}) != snap.get("total", {})
        )
        drained = not entry.alive or (
            resident_pgs == 0 and resident_actors == 0 and not busy
        )
        return {
            "known": True,
            "alive": entry.alive,
            "draining": entry.draining,
            "drained": drained,
            "resident_pgs": resident_pgs,
            "resident_actors": resident_actors,
            "busy": busy,
            "cause": entry.drain_cause,
            "age_s": (
                time.monotonic() - entry.drain_started
                if entry.draining else 0.0
            ),
        }

    async def handle_drain_complete(self, payload, conn):
        """Provider terminate happened: retire the node entry now instead
        of waiting out the health-check timeout."""
        node_id = self._resolve_node_id(payload.get("node_id"))
        entry = self.nodes.get(node_id) if node_id is not None else None
        if entry is None:
            return {"ok": True, "known": False}
        if entry.alive:
            self.events.record(
                NODE_LIFECYCLE, node_id.hex(), "DRAINED",
                cause=entry.drain_cause,
            )
            await self._on_node_dead(node_id)
        return {"ok": True, "known": True}

    def handle_get_load_state(self, payload, conn):
        """Cluster load snapshot for the autoscaler (reference:
        ``GcsAutoscalerStateManager`` state consumed by
        ``autoscaler/v2/autoscaler.py:50``)."""
        pending_actors = []
        for actor_id in self._pending_actors:
            entry = self.actors.get(actor_id)
            if entry is not None and entry.state in (PENDING_CREATION, RESTARTING):
                pending_actors.append(dict(entry.spec.resources))
        pending_pgs = []
        for pg_id in self._pending_pgs:
            entry = self.placement_groups.get(pg_id)
            if entry is not None and entry.state == "PENDING":
                pending_pgs.append(
                    {
                        "strategy": entry.strategy,
                        "bundles": [dict(b) for b in entry.bundles],
                    }
                )
        return {
            "nodes": {
                nid.hex(): {
                    "alive": e.alive,
                    "draining": e.draining,
                    "total": e.snapshot.get("total", {}),
                    "available": e.snapshot.get("available", {}),
                    "labels": e.snapshot.get("labels", {}),
                    "pending_demands": e.snapshot.get("pending_demands", []),
                    "idle_s": e.snapshot.get("idle_s", 0.0),
                }
                for nid, e in self.nodes.items()
            },
            "pending_actors": pending_actors,
            "pending_pgs": pending_pgs,
            "requested_resources": list(self._requested_resources),
            "unplaceable_demands": [
                dict(r)
                for ts, _k, r in self._recent_unplaceable
                if time.monotonic() - ts < 5.0
            ],
            # Over-quota task leases queued by admission (JobArbiter): no
            # PENDING table holds them, so they ride a short recency
            # window like unplaceable demand.
            "queued_task_demands": [
                dict(r)
                for ts, _k, r in self._recent_queued_tasks
                if time.monotonic() - ts < 5.0
            ],
            "queued_by_admission": {
                job: info.get("queued_now", 0)
                for job, info in self.arbiter.snapshot().items()
                if info.get("queued_now")
            },
        }

    @staticmethod
    def _demand_key(resources: dict, owner) -> tuple:
        return (owner, tuple(sorted(resources.items())))

    def _note_queued_task(self, resources: dict, owner=None,
                          window_s: float = 5.0):
        # Keyed by requester identity: a lease pool retrying the same
        # over-quota request every backoff must read as ONE pending task,
        # not one per retry — or the autoscaler overshoots.
        now = time.monotonic()
        key = self._demand_key(resources, owner)
        self._recent_queued_tasks = [
            (ts, k, r) for ts, k, r in self._recent_queued_tasks
            if now - ts < window_s and k != key
        ]
        self._recent_queued_tasks.append((now, key, dict(resources)))

    def _note_unplaceable(self, resources: dict, owner=None,
                          window_s: float = 5.0):
        now = time.monotonic()
        key = self._demand_key(resources, owner)
        self._recent_unplaceable = [
            (ts, k, r) for ts, k, r in self._recent_unplaceable
            if now - ts < window_s and k != key
        ]
        self._recent_unplaceable.append((now, key, dict(resources)))

    def _clear_demand(self, resources: dict, owner):
        """Retire a requester's window entries once its lease is granted."""
        key = self._demand_key(resources, owner)
        self._recent_queued_tasks = [
            e for e in self._recent_queued_tasks if e[1] != key
        ]
        self._recent_unplaceable = [
            e for e in self._recent_unplaceable if e[1] != key
        ]

    def handle_request_resources(self, payload, conn):
        """Explicit autoscaling demand (``ray.autoscaler.sdk.
        request_resources`` analog): a standing list of resource bundles the
        cluster should be able to fit."""
        self._requested_resources = [
            dict(b) for b in payload.get("bundles", [])
        ]
        return True

    # ------------------------------------------------------------ task events
    def handle_task_events(self, payload, conn):
        """Worker task-event flush (GcsTaskManager::HandleAddTaskEventData
        analog)."""
        self.task_event_store.add_batch(
            payload.get("events", ()), payload.get("profile_events", ())
        )
        if payload.get("worker_id"):
            self.task_event_store.report_span_drops(
                payload["worker_id"], payload.get("span_drops", 0)
            )
        return True

    def handle_obs_report(self, payload, conn):
        """Node-agent aggregated observability delivery: one RPC per
        heartbeat carrying every pulled worker's task events, spans,
        span-drop totals, and metrics-registry snapshot.  Metrics land
        under the same per-worker KV key the worker's own flush uses, so
        the two delivery paths overwrite instead of double counting.
        Batches carry per-worker ids (the pull staging's at-least-once
        redelivery): an id seen before is a duplicate of a batch that
        DID land — only its idempotent span-drop total is merged."""
        self.obs_beats += 1
        metrics_ns = self._kv.setdefault("metrics", {})
        for batch in payload.get("batches") or ():
            wid = batch.get("worker_id")
            if wid and batch.get("span_drops"):
                self.task_event_store.report_span_drops(
                    wid, batch["span_drops"]
                )
            bid = batch.get("batch_id")
            if bid is not None and wid and self._obs_seen.get(wid) == bid:
                continue
            self.task_event_store.add_batch(
                batch.get("events") or (), batch.get("profile_events") or ()
            )
            if batch.get("metrics") and batch.get("metrics_key"):
                metrics_ns[batch["metrics_key"]] = batch["metrics"]
            if bid is not None and wid:
                self._obs_seen[wid] = bid
                self._persist_obs_seen(wid, bid)
        return True

    def handle_list_task_events(self, payload, conn):
        return {
            "tasks": self.task_event_store.list_tasks(
                payload.get("filters"), payload.get("limit", 1000)
            ),
            "profile_events": self.task_event_store.profile_events(),
            "num_dropped": self.task_event_store.num_dropped,
            "num_span_drops": self.task_event_store.span_drop_total(),
        }

    async def handle_collect_task_events(self, payload, conn):
        """Every alive agent pulls its workers' task events and spans once
        more, now (``obs_pull_now``): when this returns, the store holds
        what the cluster had recorded when it was called."""

        async def one(address):
            try:
                return await self.agent_clients.get(address).call(
                    "obs_pull_now", {}, timeout=10, retries=1
                )
            except Exception:  # noqa: BLE001 — agent racing shutdown
                return False

        return sum(await asyncio.gather(
            *(
                one(entry.agent_address)
                for entry in list(self.nodes.values())
                if entry.alive
            )
        ))

    async def handle_list_objects(self, payload, conn):
        """Cluster-wide sealed-object listing: concurrent fan-out to every
        alive agent's directory (``ray list objects`` analog) — one wedged
        agent must not serialize the whole sweep."""

        async def one(address):
            try:
                return await self.agent_clients.get(address).call(
                    "list_objects", {}, timeout=10, retries=1
                )
            except Exception:  # noqa: BLE001 — agent racing shutdown
                return []

        replies = await asyncio.gather(
            *(
                one(entry.agent_address)
                for entry in list(self.nodes.values())
                if entry.alive
            )
        )
        return [row for reply in replies for row in reply]

    def handle_list_cluster_events(self, payload, conn):
        """Typed lifecycle events (reference: RayEventRecorder export)."""
        return self.events.list_events(
            payload.get("event_type"), payload.get("entity_id"),
            payload.get("limit", 1000),
        )

    def handle_ping(self, payload, conn):
        return "pong"

    # ------------------------------------------------------------------ HA
    def _cp_ha_info(self) -> dict:
        """Role/lease/journal summary for cli status, /api/cluster, and
        the ``ray_tpu_cp_*`` metrics."""
        info = {
            "role": "leader",
            "ha": bool(self.ha_dir),
            "epoch": self.lease.epoch if self.lease is not None else 0,
        }
        stats_fn = getattr(self.store, "journal_stats", None)
        if stats_fn is not None:
            info["journal"] = stats_fn()
        if self.ha_dir:
            from .cp_ha import read_standby_statuses

            leader_seq = getattr(self.store, "applied_seq", 0)
            standbys = []
            for s in read_standby_statuses(self.ha_dir):
                standbys.append({
                    "holder": s.get("holder"),
                    "address": s.get("address"),
                    "applied_seq": s.get("applied_seq", 0),
                    "lag_records": max(
                        0, leader_seq - s.get("applied_seq", 0)
                    ),
                    "updated_at": s.get("updated_at"),
                })
            info["standbys"] = standbys
        return info

    def handle_cp_role(self, payload, conn):
        return self._cp_ha_info()

    def handle_debug_control_plane(self, payload, conn):
        """Control-plane self-diagnosis: group-commit accounting + per-lane
        RPC dispatch stats (tests and the many-client limits stage)."""
        return {
            "pg_batch_stats": dict(self.pg_batch_stats),
            "rpc_lanes": self.server.lane_stats(),
            "nodes": len(self.nodes),
            "placement_groups": len(self.placement_groups),
            "obs_beats": self.obs_beats,
            "sched": {
                "preemptions_total": self.arbiter.preemptions_total,
                "victims_total": self.arbiter.victims_total,
                "denied_total": self.arbiter.denied_total,
            },
            "cp": self._cp_ha_info(),
        }

    def handle_get_state(self, payload, conn):
        """State-API snapshot (reference: ray.util.state / StateAggregator)."""
        autoscaler = self._kv.get("autoscaler", {}).get("status")
        return {
            "nodes": {
                nid.hex(): {
                    "alive": e.alive,
                    "draining": e.draining,
                    "snapshot": e.snapshot,
                }
                for nid, e in self.nodes.items()
            },
            "actors": [e.public_info() for e in self.actors.values()],
            "placement_groups": [
                e.public_info() for e in self.placement_groups.values()
            ],
            "jobs": {jid.hex(): dict(j) for jid, j in self.jobs.items()},
            "scheduling": self.arbiter.snapshot(),
            "cp": self._cp_ha_info(),
            # Published by the autoscaler each reconcile round (KV
            # namespace "autoscaler"): last decision, per-type counts,
            # draining nodes, pending-demand summary, launch backoff.
            "autoscaler": autoscaler if isinstance(autoscaler, dict) else {},
        }



async def run_ha_candidate(host: str, port: int, session_id: str,
                           ha_dir: str) -> None:
    """One control-plane CANDIDATE: tail the journal as a warm standby
    while contending for the leader lease; on winning, replay the tail,
    bump the fencing epoch (promote), and serve as the leader on the SAME
    port — renewing the lease on the heartbeat cadence and exiting hard
    the moment renewal fails (a standby is about to take over)."""
    from .cp_ha import (
        LeaderLease,
        StandbyControlPlane,
        publish_endpoint,
        read_endpoint,
        clear_standby_status,
        write_standby_status,
    )
    from .store_client import JournaledStoreClient

    holder = f"cp-{os.getpid()}-{port}"
    journal_dir = os.path.join(ha_dir, "journal")
    store = JournaledStoreClient(journal_dir)
    lease = LeaderLease(ha_dir, holder)
    standby = StandbyControlPlane(
        lambda: (read_endpoint(ha_dir) or {}).get("address")
    )
    standby_server = RpcServer(standby, host, port, lanes=1)
    address = await standby_server.start()
    logger.info("cp candidate %s standing by on %s", holder, address)
    poll = max(0.02, GlobalConfig.cp_lease_poll_s)
    while True:
        store.tail()
        write_standby_status(ha_dir, holder, address, store.applied_seq)
        if lease.try_acquire(address):
            break
        await asyncio.sleep(poll)
    # Leader: free the port the standby rejector held, promote the
    # journal under the new epoch, and serve the real control plane.
    await standby_server.stop()
    clear_standby_status(ha_dir, holder)
    store.promote(lease)
    cp = ControlPlane(
        host, port, session_id=session_id,
        store=store, ha_dir=ha_dir, lease=lease,
    )
    await cp.start()
    publish_endpoint(ha_dir, cp.server.address, lease.epoch)
    logger.info(
        "cp candidate %s is LEADER (epoch %d) on %s",
        holder, lease.epoch, cp.server.address,
    )
    renew_period = min(
        GlobalConfig.health_check_period_s, max(0.05, lease.ttl / 3.0)
    )
    while True:
        await asyncio.sleep(renew_period)
        if not lease.renew():
            logger.error(
                "cp %s lost the leader lease; exiting for failover", holder
            )
            os._exit(3)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--session-id", required=True)
    parser.add_argument("--store-path", default=None)
    parser.add_argument("--ha-dir", default=None)
    args = parser.parse_args()
    from .reaper import watch_parent_process

    watch_parent_process()
    logging.basicConfig(
        level=GlobalConfig.log_level,
        format="%(asctime)s %(levelname)s control_plane: %(message)s",
    )

    async def run():
        from .stack_dump import install_signal_dumpers

        install_signal_dumpers(asyncio.get_running_loop())
        if args.ha_dir:
            await run_ha_candidate(
                args.host, args.port, args.session_id, args.ha_dir
            )
            return
        cp = ControlPlane(
            args.host, args.port, args.session_id, store_path=args.store_path
        )
        await cp.start()
        await asyncio.Event().wait()  # serve forever

    asyncio.run(run())


if __name__ == "__main__":
    main()
