"""Per-node agent — the raylet equivalent.

One process per node (Ray ``src/ray/raylet/node_manager.h``).  Owns:
  - the worker pool (spawn/cache/kill worker processes; Ray ``worker_pool.h``)
  - the lease protocol: queue + grant worker leases against local resources,
    spillback to other nodes via the control plane's view
    (Ray ``cluster_lease_manager.h`` / ``local_lease_manager.h``)
  - instance-granular TPU chip accounting → ``TPU_VISIBLE_CHIPS`` isolation
    for leased workers (reference precedent:
    ray ``python/ray/_private/accelerators/tpu.py``)
  - placement-group bundle reservations (2-phase prepare/commit; Ray
    ``node_manager.h:589``)
  - the node object directory for the shm tier + chunked node-to-node object
    pulls (Ray ``object_manager/``)
  - worker lifecycle monitoring; actor-death reporting to the control plane.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from .config import GlobalConfig
from .ids import ActorID, NodeID, ObjectID, PlacementGroupID, WorkerID
from .object_store import NodeObjectDirectory, ShmObjectStore
from .resources import NodeResources, ResourceInstanceSet, ResourceSet
from .rpc import ClientPool, RetryableRpcClient, RpcServer, resolve_service_lanes
from .task_spec import ActorSpec
from ..util.metric_registry import (
    LEASE_GRANT_WAIT_HIST,
    LEASE_QUEUE_DEPTH,
    LEASES_HELD,
)

logger = logging.getLogger(__name__)

_KILL_GRACE_S = 2.0  # SIGTERM -> SIGKILL, see _kill_worker_proc
_WORKER_REAP_S = 90.0  # stop(): wait this long for killed workers to be gone

# TPU_CHIPS_PER_HOST_BOUNDS of a lease that holds part of a host's chips.
_SUB_HOST_CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1"}


def _ignore_usr1():
    """preexec_fn: SIGUSR1 → SIG_IGN before exec.  Ignored dispositions
    survive exec (handlers don't), so a `ray-tpu stack` signal landing
    during the child's import phase — before the loop installs the real
    dump handler — is dropped instead of killing the starting worker."""
    import signal as _signal

    _signal.signal(_signal.SIGUSR1, _signal.SIG_IGN)


def _sched_idle():
    """preexec_fn: run the child under SCHED_IDLE (falls back to nice 19
    where unavailable) so prestart imports only use otherwise-idle CPU."""
    _ignore_usr1()
    try:
        os.sched_setscheduler(0, os.SCHED_IDLE, os.sched_param(0))
    except Exception:  # noqa: BLE001
        try:
            os.nice(19)
        except Exception:  # raylint: waive[RTL003] no further fallback below nice(19)
            pass


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, proc: subprocess.Popen, env_key: tuple):
        self.worker_id = worker_id
        self.proc = proc
        self.env_key = env_key  # pool key: (tpu_chips_tuple, extra_env_items)
        self.address: Optional[str] = None
        self.ready = asyncio.Event()
        self.leased = False
        self.is_actor = False
        self.actor_id: Optional[ActorID] = None
        self.last_idle = time.monotonic()


class Lease:
    def __init__(self, lease_id: int, worker: WorkerHandle, resources: ResourceSet,
                 instances: Dict[str, List[int]], pg_id: Optional[PlacementGroupID],
                 bundle_index: int, is_actor: bool = False):
        self.lease_id = lease_id
        self.worker = worker
        self.resources = resources
        self.instances = instances
        self.pg_id = pg_id
        self.bundle_index = bundle_index
        self.is_actor = is_actor
        self.retriable = not is_actor  # refined from the lease request
        self.start_ts = time.monotonic()


class BundlePool:
    """Resources reserved for one placement-group bundle on this node."""

    def __init__(self, spec: Dict[str, float]):
        self.total = ResourceSet(spec)
        self.available = ResourceSet(spec)
        self.committed = False


class NodeAgent:
    # Read-only probes the multi-lane RPC server may run on a lane thread
    # (see rpc.RpcServer).  The agent's stateful paths — leases, bundle
    # pools, worker lifecycle, pulls — keep their single-loop semantics by
    # forwarding; lanes still isolate per-connection framing/serialization.
    LANE_SAFE_METHODS = frozenset({"ping", "object_info"})

    def __init__(
        self,
        host: str,
        port: int,
        cp_address: str,
        session_id: str,
        resources: Dict[str, float],
        labels: Dict[str, str],
        node_id: Optional[NodeID] = None,
        cp_ha_dir: Optional[str] = None,
    ):
        self.node_id = node_id or NodeID.from_random()
        self.session_id = session_id
        self.cp_address = cp_address
        self.cp_ha_dir = cp_ha_dir
        self.server = RpcServer(self, host, port, lanes=resolve_service_lanes())
        # With HA, every reconnect re-resolves the published leader
        # endpoint — failover re-anchoring IS the plain reconnect path
        # (heartbeat's "reregister" reply then replays node state).
        resolver = None
        if cp_ha_dir:
            from .cp_ha import make_cp_resolver

            resolver = make_cp_resolver(cp_ha_dir, cp_address)
        self.cp_client = RetryableRpcClient(
            cp_address, address_resolver=resolver
        )
        self.agent_clients = ClientPool()  # peers, for remote pulls
        self.worker_clients = ClientPool()  # local workers (actor_init etc.)
        self.resources = NodeResources(resources, labels)
        self.instances = ResourceInstanceSet(resources)
        self.directory = NodeObjectDirectory(
            session_id, GlobalConfig.object_store_memory_bytes
        )
        # The agent is the session arena's creator; every other process
        # (workers, drivers) attaches only — see get_arena's leak note.
        self.shm_store = ShmObjectStore(session_id, create_arena=True)
        self.workers: Dict[WorkerID, WorkerHandle] = {}
        self.idle_pool: Dict[tuple, List[WorkerHandle]] = {}
        # cgroup-v2 isolation of application workers (no-op unless
        # enable_resource_isolation and a writable cgroup mount).
        from .cgroup import WorkerIsolation

        self.isolation = WorkerIsolation(
            session_id,
            memory_limit_bytes=(
                GlobalConfig.worker_cgroup_memory_limit_bytes or None
            ),
        )
        self.leases: Dict[int, Lease] = {}
        self._next_lease_id = 1
        self.bundles: Dict[Tuple[PlacementGroupID, int], BundlePool] = {}
        self._lease_queue: List[tuple] = []  # (payload, future)
        # Stable lease ownership: owner_id -> latest live connection, and
        # pending grace-reap timers for owners whose conn dropped.
        self._owner_conns: Dict[str, Any] = {}
        self._owner_reap_timers: Dict[str, Any] = {}
        self._idle_since = None  # monotonic ts when node went fully idle
        self._pull_futures: Dict[ObjectID, asyncio.Future] = {}
        # Frees observed while a pull of the same oid is in flight: the
        # pull's post-await seal would otherwise re-register a dead oid
        # (same hazard handle_seal_object guards against) and leak its
        # directory accounting + storage forever.
        self._freed_during_pull: set = set()
        self._prestart_task: Optional[asyncio.Task] = None
        self._last_pop = 0.0  # monotonic ts of last default-pool pop
        self._pool_miss_at = 0.0  # monotonic ts of last EMPTY-pool pop
        self._prestart_inflight: set = set()  # spawning prestart handles
        self._prestart_first = True  # initial fill runs hot (see loop)
        self._prestart_hot_until = 0.0  # forced-hot deadline (prestart_pool)
        # Pool key of a plain CPU-only lease (chip isolation applied to an
        # empty chip set) — constant per process; prestarted workers carry
        # exactly this env so they match ordinary task/actor leases.
        env: Dict[str, str] = {}
        self._apply_chip_isolation(env, {})
        self._default_env = env
        self._default_env_key = tuple(sorted(env.items()))
        self._bg: List[asyncio.Task] = []
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # Observability aggregator counters (pull rides the heartbeat —
        # by design there is NO separate periodic loop for it; a test
        # pins that via the _bg task list in debug_state).
        self._obs_rounds = 0
        self._obs_events_forwarded = 0
        self._obs_workers_pulled = 0
        # batch-id acks per worker: sent with the next pull only AFTER a
        # successful obs_report, so workers re-deliver un-forwarded
        # batches instead of losing them (at-least-once).
        self._obs_acks: Dict[str, int] = {}
        self._obs_round_lock = asyncio.Lock()

    # ------------------------------------------------------------- lifecycle
    async def start(self) -> str:
        addr = await self.server.start()
        reply = await self.cp_client.call(
            "register_node",
            {
                "node_id": self.node_id,
                "agent_address": addr,
                "snapshot": self._snapshot(),
                "held_pgs": self._held_pg_ids(),
            },
        )
        assert reply["ok"]
        self._drop_stale_pgs(reply.get("drop_pgs"))
        loop = asyncio.get_running_loop()
        # The agent has no CoreWorker, so its flight-recorder metrics
        # (object directory, lease waits) reach the cluster registry via a
        # custom flush hook; the heartbeat loop forces a push each period.
        self._loop = loop
        from ..util import metrics as _metrics

        _metrics.set_flush_hook(self._push_metrics_payload)
        self._bg.append(loop.create_task(self._heartbeat_loop()))
        self._bg.append(loop.create_task(self._monitor_workers_loop()))
        if GlobalConfig.memory_monitor_period_s > 0:
            self._bg.append(loop.create_task(self._memory_monitor_loop()))
        self._replenish_pool()
        logger.info("node agent %s on %s", self.node_id.hex()[:8], addr)
        return addr

    async def _memory_monitor_loop(self):
        """OOM defense (reference: MemoryMonitor + WorkerKillingPolicy):
        when node memory crosses the threshold, kill the newest retriable
        lease's worker — the submitter's retry machinery resubmits it."""
        from .memory_monitor import MemoryMonitor, system_memory_fraction

        fake_file = GlobalConfig.memory_monitor_fake_usage_file

        def usage_reader() -> float:
            if fake_file:  # chaos/testing hook
                try:
                    with open(fake_file) as f:
                        return float(f.read().strip())
                except (OSError, ValueError):
                    return 0.0
            return system_memory_fraction()

        monitor = MemoryMonitor(
            GlobalConfig.memory_monitor_threshold, usage_reader
        )
        self.memory_monitor = monitor
        period = GlobalConfig.memory_monitor_period_s
        while True:
            await asyncio.sleep(period)
            try:
                victims = [
                    {
                        "lease_id": lid,
                        "start_ts": lease.start_ts,
                        "retriable": lease.retriable and not lease.is_actor,
                        "is_actor": lease.is_actor,
                    }
                    for lid, lease in self.leases.items()
                ]
                picked = monitor.check(victims)
                if picked is not None:
                    lease = self.leases.get(picked[0])
                    if lease is not None:
                        self._kill_worker_proc(lease.worker)
            except Exception as e:  # noqa: BLE001
                logger.warning("memory monitor round failed: %s", e)

    def _push_metrics_payload(self, payload: dict):
        """metrics flush hook: ship this agent process's registry to the
        control-plane KV.  Must be callable from any thread (the directory's
        spill thread records counters) and never raise."""
        async def push():
            try:
                await self.cp_client.call(
                    "kv_put",
                    {"namespace": "metrics",
                     "key": f"agent:{self.node_id.hex()}",
                     "value": payload, "overwrite": True},
                    retries=1,
                )
            except Exception:  # raylint: waive[RTL003] metrics are best-effort
                pass

        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        try:
            if running is self._loop:
                running.create_task(push())
            elif self._loop is not None:
                asyncio.run_coroutine_threadsafe(push(), self._loop)
        except RuntimeError:
            pass  # loop tearing down

    async def stop(self):
        """SIGTERM (``Node.stop``): the node goes silent, then its workers
        are killed and reaped before the agent exits, so a stopped node
        leaves no process behind.  Workers run in sessions of their own; a
        TPU worker can take tens of seconds to let go of its chip, and one
        that outlives the agent is an orphan nobody waits for."""
        from ..util import metrics as _metrics

        _metrics.clear_flush_hook(self._push_metrics_payload)
        if self._prestart_task is not None:
            self._prestart_task.cancel()
        for t in self._bg:
            t.cancel()
        # No new leases; every worker sees EOF on its agent connection and
        # exits by its own watchdog, whatever it does with SIGTERM.
        await self.server.stop()
        workers = list(self.workers.values())
        for w in workers:
            self._kill_worker_proc(w)
        deadline = time.monotonic() + _WORKER_REAP_S
        while any(w.proc.poll() is None for w in workers):
            if time.monotonic() > deadline:
                logger.warning("workers still alive after %ss", _WORKER_REAP_S)
                break
            await asyncio.sleep(0.02)
        self.isolation.cleanup()
        self.directory.cleanup()
        await self.cp_client.close()
        await self.agent_clients.close_all()
        await self.worker_clients.close_all()

    def _snapshot(self) -> dict:
        # Idle tracking + queued lease demands feed the autoscaler's load
        # state (reference: resource-demand fields in the raylet's resource
        # report consumed by GcsAutoscalerStateManager).
        pending = [
            dict(payload.get("resources") or {})
            for payload, fut, _conn in self._lease_queue
            if not fut.done()
        ]
        busy = bool(pending) or (
            self.resources.available.to_dict() != self.resources.total.to_dict()
        )
        if busy:
            self._idle_since = None
        elif self._idle_since is None:
            self._idle_since = time.monotonic()
        return {
            "total": self.resources.total.to_dict(),
            "available": self.resources.available.to_dict(),
            "labels": dict(self.resources.labels),
            "pending_demands": pending,
            "idle_s": (
                time.monotonic() - self._idle_since
                if self._idle_since is not None
                else 0.0
            ),
        }

    async def _heartbeat_loop(self):
        from ..util import flight_recorder as fr
        from ..util import metrics as _metrics

        period = GlobalConfig.health_check_period_s
        while True:
            t_round = time.monotonic()
            try:
                # Flight-recorder gauges ride the heartbeat cadence (off
                # every hot path), then the registry is force-pushed
                # through the agent's flush hook.
                if fr.enabled():
                    self.directory.record_telemetry()
                    fr.gauge(LEASE_QUEUE_DEPTH, len(self._lease_queue))
                    fr.gauge(LEASES_HELD, len(self.leases))
                    fr.record_rpc_lanes(self.server, role="node_agent")
                    _metrics.flush()
            except Exception:  # raylint: waive[RTL003] telemetry must not kill heartbeat
                pass
            try:
                # Observability aggregation rides the SAME cadence: pull
                # every local worker's span/task-event/metric deltas and
                # forward one merged obs_report — no extra periodic RPC.
                if GlobalConfig.enable_obs_aggregator:
                    await self._obs_pull_round()
            except Exception:  # raylint: waive[RTL003] telemetry must not kill heartbeat
                pass
            try:
                reply = await self.cp_client.call(
                    "heartbeat",
                    {"node_id": self.node_id, "snapshot": self._snapshot()},
                    retries=1,
                )
                if reply.get("reregister"):
                    rereg = await self.cp_client.call(
                        "register_node",
                        {
                            "node_id": self.node_id,
                            "agent_address": self.server.address,
                            "snapshot": self._snapshot(),
                            "held_pgs": self._held_pg_ids(),
                        },
                    )
                    self._drop_stale_pgs(rereg.get("drop_pgs"))
            except Exception as e:
                logger.debug("heartbeat send failed: %s", e)
            took = time.monotonic() - t_round
            if took > GlobalConfig.health_check_timeout_s / 3:
                # The control plane declares this node dead after
                # health_check_timeout_s without a heartbeat: leave a trace
                # of which side was slow.
                logger.warning("heartbeat round took %.1fs", took)
            await asyncio.sleep(period)

    async def handle_obs_pull_now(self, payload, conn):
        """A round outside the heartbeat's cadence: whoever reads the
        store next (``tracing.write_spans``) finds what this node's
        workers recorded up to now."""
        await self._obs_pull_round()
        return True

    async def _obs_pull_round(self):
        """One aggregator round: drain each ready local worker's
        observability buffers (obs_pull) and ship the merged batches to
        the control plane as one obs_report.  Per-worker failures are
        isolated — a dying worker must not cost the node its telemetry.
        One round at a time: two would each re-deliver what the other's
        ack has not yet covered."""
        async with self._obs_round_lock:
            await self._obs_pull_round_locked()

    async def _obs_pull_round_locked(self):
        self._obs_rounds += 1
        timeout = max(1.0, GlobalConfig.health_check_period_s)

        async def pull_one(handle):
            if handle.address is None or handle.proc.poll() is not None:
                return None
            wid = handle.worker_id.hex()
            try:
                return await self.worker_clients.get(handle.address).call(
                    "obs_pull", {"ack": self._obs_acks.get(wid)},
                    timeout=timeout,
                )
            except Exception:  # noqa: BLE001 — worker may be mid-exit
                # Nothing is lost: the worker staged the reply and will
                # re-deliver it on the next (un-acked) pull.
                from ..util import flight_recorder as fr

                fr.count_suppressed("obs_pull")
                return None

        handles = list(self.workers.values())
        replies = await asyncio.gather(*(pull_one(h) for h in handles))
        live = {h.worker_id.hex() for h in handles}
        for wid in [w for w in self._obs_acks if w not in live]:
            del self._obs_acks[wid]
        batches = [
            b for b in replies
            if b and (b.get("events") or b.get("profile_events")
                      or b.get("metrics") or b.get("span_drops"))
        ]
        self._obs_workers_pulled += sum(1 for b in replies if b)
        if not batches:
            return
        n_events = sum(
            len(b.get("events") or ()) + len(b.get("profile_events") or ())
            for b in batches
        )
        try:
            await self.cp_client.call(
                "obs_report",
                {"node_id": self.node_id.hex(), "batches": batches},
                retries=1,
            )
        except Exception as e:  # noqa: BLE001 — workers re-deliver un-acked batches
            logger.debug("obs_report failed (will re-pull): %s", e)
            return
        self._obs_events_forwarded += n_events
        for b in batches:
            if b.get("batch_id") is not None and b.get("worker_id"):
                self._obs_acks[b["worker_id"]] = b["batch_id"]

    # --------------------------------------------------------------- workers
    def _spawn_worker(
        self, env_extra: Dict[str, str], env_key: tuple, nice: bool = False
    ) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        env = dict(os.environ)
        env.update(env_extra)
        env.update(
            RAY_TPU_WORKER_ID=worker_id.hex(),
            RAY_TPU_AGENT_ADDRESS=self.server.address,
            # The leader may have moved since this agent started: point
            # new workers at the client's CURRENT resolved address.
            RAY_TPU_CP_ADDRESS=self.cp_client.address,
            RAY_TPU_SESSION_ID=self.session_id,
            RAY_TPU_NODE_ID=self.node_id.hex(),
            # Log lines (and crash dumps) must reach the file when they
            # happen, not when a block-buffered stdio flushes — a killed
            # worker would otherwise leave an empty log.
            PYTHONUNBUFFERED="1",
        )
        if self.cp_ha_dir:
            env["RAY_TPU_CP_HA_DIR"] = self.cp_ha_dir
        log_dir = os.environ.get("RAY_TPU_LOG_DIR", "/tmp/ray_tpu")
        os.makedirs(log_dir, exist_ok=True)
        out = open(os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.log"), "ab")
        # A pip runtime env runs the worker under its venv's interpreter
        # (reference: per-env virtualenv workers, _private/runtime_env/pip.py).
        python = env.get("RAY_TPU_RT_VENV_PY") or sys.executable
        argv = [python, "-m", "ray_tpu.core.worker_main"]
        container = env.get("RAY_TPU_RT_CONTAINER")
        if container:
            # Container runtime env: the worker command runs inside
            # podman/docker with host network/pid/ipc (reference:
            # _private/runtime_env/image_uri.py).
            from .runtime_env import container_argv

            argv = container_argv(container, env, argv)
        proc = subprocess.Popen(
            argv,
            env=env,
            stdout=out,
            stderr=subprocess.STDOUT,
            start_new_session=True,
            # Prestarted workers import under SCHED_IDLE so pool refill
            # only uses CPU nothing else wants; _prestart_loop restores
            # SCHED_OTHER once the worker registers (before pooling).
            # Both paths ignore SIGUSR1 until the real dump handler is
            # installed (see _ignore_usr1).
            preexec_fn=_sched_idle if nice else _ignore_usr1,
        )
        handle = WorkerHandle(worker_id, proc, env_key)
        self.isolation.attach_worker(proc.pid)
        self.workers[worker_id] = handle
        return handle

    def handle_register_worker(self, payload, conn):
        worker_id = payload["worker_id"]
        handle = self.workers.get(worker_id)
        if handle is None:
            return {"ok": False}
        handle.address = payload["address"]
        handle.ready.set()
        conn.metadata["worker_id"] = worker_id
        return {"ok": True}

    def _pool_floor(self) -> int:
        """Target number of idle default-env workers kept warm.

        Reference: ``WorkerPool::PrestartWorkers`` keeps pre-started
        workers around so tasks AND actor creations skip the interpreter
        cold start (ray ``src/ray/raylet/worker_pool.h:281``).
        ``prestart_workers``: 0 disables, N>0 is an explicit floor, -1
        auto-sizes to the node's CPU count.
        """
        n = GlobalConfig.prestart_workers
        if n < 0:
            n = int(self.resources.total.get("CPU"))
        return n

    def _replenish_pool(self):
        """Kick the background prestart loop toward the pool floor.

        Fired at agent start and whenever a pooled worker is consumed or
        dies.  Actual spawning is debounced and serialized in
        ``_prestart_loop`` so replenishment never competes with a live
        creation burst for CPU (interpreter startup is ~0.4s of pure
        import work per worker)."""
        if self._pool_floor() <= 0:
            return
        if self._prestart_task is None or self._prestart_task.done():
            self._prestart_task = asyncio.get_running_loop().create_task(
                self._prestart_loop()
            )

    # Hot-demand window: a pop that found the pool EMPTY within this many
    # seconds means demand is outrunning supply — refills must run at
    # normal priority (SCHED_IDLE imports starve completely on a busy
    # core) and in parallel, or a creation burst cold-starts every worker.
    _PRESTART_HOT_WINDOW_S = 5.0
    _PRESTART_HOT_BATCH = 4

    async def _prestart_loop(self):
        key = self._default_env_key
        while True:
            # Task-leased default-env workers count toward the floor: on a
            # saturated node every slot is busy doing real work, spawning
            # "replacements" would only steal CPU from it, and task leases
            # RETURN their workers to the pool.  Actor-held workers do not
            # count — an actor keeps its process until death, so its pool
            # slot is genuinely consumed and must be refilled.
            have = len(self.idle_pool.get(key, [])) + sum(
                1 for h in self.workers.values()
                if h.leased and not h.is_actor and h.env_key == key
            ) + len(self._prestart_inflight)
            deficit = self._pool_floor() - have
            if deficit <= 0:
                # Fill complete: close any forced-hot window so post-fill
                # refills (e.g. during a measured creation burst) drop
                # back to polite SCHED_IDLE mode.
                self._prestart_hot_until = 0.0
                return
            now = time.monotonic()
            hot = (
                self._prestart_first
                or now < self._prestart_hot_until
                or now - self._pool_miss_at < self._PRESTART_HOT_WINDOW_S
            )
            if not hot:
                quiet = time.monotonic() - self._last_pop
                if quiet < 0.5:
                    await asyncio.sleep(0.5 - quiet)
                    continue
            if GlobalConfig.memory_monitor_period_s > 0:
                # Don't refill the pool while the OOM defense is shedding
                # memory — fresh interpreters would re-consume what the
                # kill policy just freed.
                from .memory_monitor import system_memory_fraction

                if system_memory_fraction() > GlobalConfig.memory_monitor_threshold:
                    await asyncio.sleep(1.0)
                    continue
            batch = min(deficit, self._PRESTART_HOT_BATCH if hot else 1)
            handles = []
            spawn_failed = False
            for _ in range(batch):
                # A mid-batch spawn failure (EMFILE, fork failure) must
                # not strand the already-spawned handles in
                # _prestart_inflight — finish() below is what discards
                # them — or the inflated `have` count would disable
                # refill permanently.
                try:
                    h = self._spawn_worker(
                        dict(self._default_env), key, nice=not hot
                    )
                except Exception:  # noqa: BLE001 — spawn is best-effort
                    spawn_failed = True
                    break
                self._prestart_inflight.add(h)
                handles.append(h)

            async def finish(handle):
                try:
                    await self._wait_worker_ready(handle)
                    # Only the interpreter-import phase may ride
                    # SCHED_IDLE; a registered idle worker must run at
                    # normal priority or a busy box starves its
                    # agent-liveness pings and the watchdog kills it.
                    try:
                        os.sched_setscheduler(
                            handle.proc.pid, os.SCHED_OTHER,
                            os.sched_param(0),
                        )
                    except Exception:  # raylint: waive[RTL003] sched boost is a nicety; proc may have exited
                        pass
                    if handle.proc.poll() is None and not handle.leased:
                        self.idle_pool.setdefault(key, []).append(handle)
                except Exception:  # noqa: BLE001 — prestart is best-effort
                    self._kill_worker_proc(handle)
                    await asyncio.sleep(1.0)
                finally:
                    self._prestart_inflight.discard(handle)

            await asyncio.gather(*(finish(h) for h in handles))
            if spawn_failed:
                await asyncio.sleep(1.0)  # back off before retrying spawns
            self._prestart_first = False

    async def _wait_worker_ready(self, handle: WorkerHandle):
        """Wait until the worker registers; fail fast if its process dies
        first (an import-time crash must not cost the full startup
        timeout)."""
        deadline = time.monotonic() + GlobalConfig.worker_startup_timeout_s
        while True:
            try:
                await asyncio.wait_for(handle.ready.wait(), timeout=0.2)
                return
            except asyncio.TimeoutError:
                code = handle.proc.poll()
                if code is not None:
                    raise RuntimeError(
                        f"worker exited with code {code} before registering"
                    )
                if time.monotonic() > deadline:
                    raise asyncio.TimeoutError(
                        "worker did not register within "
                        f"{GlobalConfig.worker_startup_timeout_s}s"
                    )

    async def _pop_worker(self, env_extra: Dict[str, str]) -> WorkerHandle:
        env_key = tuple(sorted(env_extra.items()))
        pool = self.idle_pool.get(env_key)
        handle = None
        while pool:
            h = pool.pop()
            if h.proc.poll() is None:
                handle = h
                break
        if env_key == self._default_env_key:
            self._last_pop = time.monotonic()
            if handle is None:
                # Demand outran supply: flip the prestart loop into hot
                # mode and promote any SCHED_IDLE spawns already in
                # flight (a niced import never finishes on a busy core).
                self._pool_miss_at = self._last_pop
                for h in self._prestart_inflight:
                    try:
                        os.sched_setscheduler(
                            h.proc.pid, os.SCHED_OTHER, os.sched_param(0)
                        )
                    except Exception:  # raylint: waive[RTL003] sched boost is a nicety; proc may have exited
                        pass
            self._replenish_pool()
        if handle is None:
            handle = self._spawn_worker(env_extra, env_key)
            handle.leased = True
            try:
                await self._wait_worker_ready(handle)
            except Exception:
                # Kill the half-started interpreter — nothing else tracks
                # it (the monitor only reaps procs that already exited).
                self._kill_worker_proc(handle)
                raise
            return handle
        handle.leased = True
        return handle

    def _return_worker(self, handle: WorkerHandle):
        handle.leased = False
        handle.last_idle = time.monotonic()
        if handle.proc.poll() is None and not handle.is_actor:
            self.idle_pool.setdefault(handle.env_key, []).append(handle)

    def _kill_worker_proc(self, handle: WorkerHandle):
        """SIGTERM, then SIGKILL if the process outlives a short grace.  A
        worker that joined ``jax.distributed`` does not exit on SIGTERM
        (the runtime's preemption notifier takes the signal); it would keep
        its lease, and the chips of that lease, for good."""

        def force():
            if handle.proc.poll() is None:
                handle.proc.kill()

        try:
            if handle.proc.poll() is None:
                handle.proc.terminate()
                asyncio.get_running_loop().call_later(_KILL_GRACE_S, force)
        except Exception as e:
            logger.debug("worker terminate failed: %s", e)

    async def _monitor_workers_loop(self):
        while True:
            await asyncio.sleep(0.5)
            for worker_id, handle in list(self.workers.items()):
                if handle.proc.poll() is not None:
                    del self.workers[worker_id]
                    if handle.address is not None:
                        await self.worker_clients.close(handle.address)
                    pool = self.idle_pool.get(handle.env_key)
                    if pool and handle in pool:
                        pool.remove(handle)
                    if handle.env_key == self._default_env_key:
                        self._replenish_pool()
                    # Release any lease held by this worker.
                    for lease_id, lease in list(self.leases.items()):
                        if lease.worker is handle:
                            self._release_lease(lease_id)
                    if handle.is_actor and handle.actor_id is not None:
                        try:
                            await self.cp_client.call(
                                "actor_worker_died",
                                {
                                    "actor_id": handle.actor_id,
                                    "cause": f"worker exited with code "
                                    f"{handle.proc.returncode}",
                                },
                                retries=2,
                            )
                        except Exception as e:
                            logger.warning("actor-death notify failed: %s", e)

    async def handle_kill_worker(self, payload, conn):
        for handle in self.workers.values():
            if handle.address == payload["worker_address"]:
                handle.is_actor = False  # suppress death report: intentional
                handle.actor_id = None
                self._kill_worker_proc(handle)
                return True
        return False

    # ---------------------------------------------------------------- leases
    def _resource_pool(self, pg_id, bundle_index, resources: Optional[ResourceSet] = None):
        """Resolve the PG bundle pool a lease draws from (None = node pool).
        For the wildcard index (-1), picks the lowest-indexed bundle of the
        group that can actually fit ``resources`` right now."""
        if pg_id is None:
            return None
        pool = self.bundles.get((pg_id, bundle_index))
        if pool is None and bundle_index == -1:
            fallback = None
            for (pid, _bi), p in sorted(
                self.bundles.items(), key=lambda kv: kv[0][1]
            ):
                if pid != pg_id:
                    continue
                if fallback is None:
                    fallback = p
                if resources is None or resources.is_subset_of(p.available):
                    return p
            return fallback  # all full: caller re-queues against this one
        return pool

    async def handle_request_lease(self, payload, conn):
        """Grant a worker lease, queue it, or reply with a spillback target."""
        t0 = time.monotonic()
        fut = asyncio.get_running_loop().create_future()
        self._lease_queue.append((payload, fut, conn))
        self._drain_lease_queue()
        reply = await fut
        from ..util import flight_recorder as fr

        if reply.get("granted"):
            result = "granted"
        elif reply.get("spillback"):
            result = "spillback"
        else:
            result = "retry"  # infeasible right now; requester re-asks
        fr.histogram(
            LEASE_GRANT_WAIT_HIST, time.monotonic() - t0,
            {"result": result},
        )
        return reply

    def _drain_lease_queue(self):
        still_waiting = []
        for payload, fut, conn in self._lease_queue:
            if fut.done():
                continue
            granted = self._try_grant(payload, fut, conn)
            if not granted:
                still_waiting.append((payload, fut, conn))
        self._lease_queue = still_waiting

    def _try_grant(self, payload, fut, conn=None) -> bool:
        resources = ResourceSet(payload.get("resources") or {})
        pg_id = payload.get("placement_group_id")
        bundle_index = payload.get("bundle_index", -1)
        bundle = self._resource_pool(pg_id, bundle_index, resources)
        if pg_id is not None:
            if bundle is None:
                # The bundle lives on another node (or the PG is still
                # pending): ask the control plane for the bundle's node and
                # spill the lease there instead of failing the task.
                asyncio.get_running_loop().create_task(
                    self._spillback(payload, fut, resources)
                )
                return True
            if not resources.is_subset_of(bundle.available):
                return False
            bundle.available = bundle.available - resources
        else:
            if not self.resources.could_ever_fit(resources):
                asyncio.get_running_loop().create_task(
                    self._spillback(payload, fut, resources)
                )
                return True
            if not self.resources.acquire(resources):
                return False
        instances = self._acquire_instances(resources)
        if instances is None:
            # Accounting says the amount fits but chip instances are too
            # fragmented right now — undo and stay queued.
            if bundle is not None:
                bundle.available = bundle.available + resources
            else:
                self.resources.release(resources)
            return False
        asyncio.get_running_loop().create_task(
            self._finish_grant(
                payload, fut, resources, instances, pg_id, bundle_index, conn
            )
        )
        return True

    def _acquire_instances(self, resources: ResourceSet) -> Optional[Dict[str, List[int]]]:
        """Returns granted instance ids per unit resource, or None if any
        requested unit resource can't be instance-assigned (never grant a
        TPU lease without chip isolation)."""
        instances: Dict[str, List[int]] = {}
        acquired: List[tuple] = []
        for name in ResourceInstanceSet.UNIT_RESOURCES:
            amount = resources.get(name)
            if amount > 0 and name in self.instances.instances:
                got = self.instances.acquire(name, amount)
                if got is None:
                    for n, a, ids in acquired:
                        self.instances.release(n, a, ids)
                    return None
                instances[name] = got
                acquired.append((name, amount, got))
        return instances

    def _release_instances(self, resources: ResourceSet, instances: Dict[str, List[int]]):
        for name, ids in instances.items():
            self.instances.release(name, resources.get(name), ids)

    def _apply_chip_isolation(self, env_extra: Dict[str, str], instances):
        """The accelerator environment of a lease's worker.

        A lease without chips always starts on the CPU platform: the chip
        belongs to one process at a time, and a chipless worker that
        imports jax (serve controller and proxy, data workers, every
        default-pool worker) must never load the TPU library.  A lease
        with chips sees exactly those chips, and its platform is left
        alone — ``tpu_detect.leased_platform_verified`` makes a worker
        whose jax then comes up on anything else an error, not a CPU run.

        Variables as libtpu 0.0.34 obeys them on a v5e 2x2 host (PR 22
        chip probe): ``TPU_VISIBLE_CHIPS`` selects the chips; a sub-host
        lease also needs its chip bounds (reference ``accelerators/tpu.py``
        sets the same pair) — a 2-chip lease comes up with ``2,1,1`` on an
        x-adjacent pair ((0,1) and (2,3) ran: what first-fit hands out);
        (0,2) and (1,2) abort the process under either ``2,1,1`` or
        ``1,2,1``, and without bounds libtpu refuses ("expected 4, actual
        2").  A whole-host lease keeps the host's own bounds."""
        chips = instances.get("TPU")
        if not chips:
            env_extra["JAX_PLATFORMS"] = "cpu"
            return
        env_extra[GlobalConfig.tpu_visible_chips_env] = ",".join(
            str(i) for i in chips
        )
        bounds = _SUB_HOST_CHIP_BOUNDS.get(len(chips))
        if bounds and len(chips) < len(self.instances.instances["TPU"]):
            env_extra["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
            env_extra["TPU_HOST_BOUNDS"] = "1,1,1"

    async def _finish_grant(self, payload, fut, resources, instances, pg_id,
                            bundle_index, conn=None):
        env_extra = dict(payload.get("env_vars") or {})
        self._apply_chip_isolation(env_extra, instances)
        try:
            worker = await self._pop_worker(env_extra)
        except Exception as e:  # noqa: BLE001
            self._release_pool_resources(resources, instances, pg_id, bundle_index)
            self._drain_lease_queue()
            if not fut.done():
                fut.set_exception(e)
            return
        lease_id = self._next_lease_id
        self._next_lease_id += 1
        lease = Lease(
            lease_id, worker, resources, instances, pg_id, bundle_index
        )
        lease.retriable = payload.get("retriable", True)
        # The lease belongs to the requesting DRIVER, identified two ways:
        # by connection (fast death signal) and by stable owner_id (the
        # driver's RPC address) — a retrying client that reconnects after
        # a transient transport failure re-associates its leases via
        # owner_ping/request_lease instead of losing them (ADVICE r3: a
        # healthy driver's leases must not die with one socket).
        lease.owner_conn = conn
        lease.owner_id = payload.get("owner_id")
        self.leases[lease_id] = lease
        if conn is not None and getattr(conn, "closed", False):
            # Owner died while we were starting its worker: reap now —
            # on_connection_closed already ran and cannot see this lease.
            # MUST precede the re-association below: binding the owner to
            # this dead conn (and cancelling its grace timer) would orphan
            # the owner's OTHER leases forever (no further disconnect
            # event will fire for an already-closed connection).
            self._reap_lease(lease_id)
            if not fut.done():
                fut.set_exception(
                    ConnectionError("lease requester disconnected")
                )
            return
        if lease.owner_id:
            self._owner_conns[lease.owner_id] = conn
            timer = self._owner_reap_timers.pop(lease.owner_id, None)
            if timer:
                timer.cancel()
        if not fut.done():
            fut.set_result(
                {
                    "granted": True,
                    "lease_id": lease_id,
                    "worker_address": worker.address,
                    "worker_id": worker.worker_id,
                    "instances": instances,
                }
            )

    async def _spillback(self, payload, fut, resources: ResourceSet):
        try:
            reply = await self.cp_client.call(
                "pick_node_for_lease",
                {
                    "resources": resources.to_dict(),
                    "strategy": payload.get("strategy"),
                    "preferred": None,
                    "placement_group_id": payload.get("placement_group_id"),
                    "bundle_index": payload.get("bundle_index", -1),
                    "job_id": payload.get("job_id"),
                    # Stable requester identity: the control plane dedupes
                    # its autoscaler demand windows by it, so one lease
                    # pool retrying does not read as N pending tasks.
                    "owner_id": payload.get("owner_id"),
                },
            )
        except Exception as e:  # noqa: BLE001
            if not fut.done():
                fut.set_exception(e)
            return
        if not fut.done():
            if reply.get("infeasible"):
                if reply.get("fatal"):
                    # No amount of scaling fixes this (e.g. removed PG,
                    # bad bundle index): surface the error now.
                    fut.set_exception(ValueError(reply["error"]))
                    return
                # Infeasible *now* — stay queued and retry (the reference
                # queues infeasible work indefinitely; the autoscaler sees
                # the demand via the control plane's unplaceable window and
                # may add a node that fits).
                fut.set_result({"granted": False, "retry": True})
            elif reply.get("node_id") is None:
                fut.set_result({"granted": False, "retry": True})
            elif reply["agent_address"] == self.server.address:
                # The control plane pointed back at THIS node (e.g. a PG
                # bundle recorded here that _try_grant couldn't find) —
                # spilling to ourselves would loop forever.
                fut.set_exception(
                    ValueError(
                        "lease unroutable: target node is this node but "
                        "the local grant failed"
                    )
                )
            else:
                fut.set_result(
                    {"granted": False, "spillback": reply["agent_address"]}
                )

    def _release_pool_resources(self, resources, instances, pg_id, bundle_index):
        self._release_instances(resources, instances)
        bundle = self._resource_pool(pg_id, bundle_index)
        if bundle is not None:
            bundle.available = bundle.available + resources
        else:
            # No bundle, or one dropped while this lease lived: the node
            # pool gets them back now (see _drop_bundles).
            self.resources.release(resources)

    def _release_lease(self, lease_id: int):
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return
        self._release_pool_resources(
            lease.resources, lease.instances, lease.pg_id, lease.bundle_index
        )
        self._return_worker(lease.worker)
        self._drain_lease_queue()

    def handle_return_lease(self, payload, conn):
        self._release_lease(payload["lease_id"])
        return True

    def on_connection_closed(self, conn):
        """A peer connection dropped.  If it was a lease-holding driver,
        reap its leases (reference: the raylet reclaims a dead owner's
        leased workers) — a crashed/exited driver must not pin node
        resources forever.  Order matters: purge the dead driver's QUEUED
        requests first, because releasing a lease re-drains the queue and
        would otherwise grant the freed resources straight back to the
        dead driver.  Leased workers are KILLED, not pooled: they may be
        mid-task for the dead driver and must not serve the next lease.
        Worker-registration connections are handled by the process monitor.
        """
        kept = []
        for payload, fut, qconn in self._lease_queue:
            if qconn is conn:
                # Resolve the handler coroutine so it doesn't await forever;
                # the error reply goes nowhere (connection is gone), which
                # the dispatch layer tolerates.
                if not fut.done():
                    fut.set_exception(
                        ConnectionError("lease requester disconnected")
                    )
            else:
                kept.append((payload, fut, qconn))
        self._lease_queue = kept
        affected = [
            (lid, lease) for lid, lease in self.leases.items()
            if getattr(lease, "owner_conn", None) is conn
        ]
        owners_with_id = set()
        for lid, lease in affected:
            owner_id = getattr(lease, "owner_id", None)
            if owner_id:
                owners_with_id.add(owner_id)
            else:
                # Legacy/no-id lease: the connection WAS the identity.
                logger.info("reaping lease %d from disconnected driver", lid)
                self._reap_lease(lid)
        # Owners bound to this conn with NO leases: nothing to grace —
        # drop the mapping now so dead connections don't accumulate.
        for owner_id, oconn in list(self._owner_conns.items()):
            if oconn is conn and owner_id not in owners_with_id:
                self._owner_conns.pop(owner_id, None)
                timer = self._owner_reap_timers.pop(owner_id, None)
                if timer:
                    timer.cancel()
        # Identified owners get a reconnection grace window: a retrying
        # client that lost one socket re-associates via owner_ping /
        # request_lease; only an owner that stays silent is reaped.
        for owner_id in owners_with_id:
            if self._owner_conns.get(owner_id) is not conn:
                continue  # already re-associated to a newer connection
            timer = self._owner_reap_timers.pop(owner_id, None)
            if timer:
                timer.cancel()
            self._owner_reap_timers[owner_id] = (
                asyncio.get_running_loop().call_later(
                    GlobalConfig.lease_owner_grace_s,
                    self._reap_owner_if_silent, owner_id, conn,
                )
            )

    def _reap_owner_if_silent(self, owner_id: str, dead_conn):
        """Grace expired: reap the owner's leases unless it reconnected."""
        self._owner_reap_timers.pop(owner_id, None)
        current = self._owner_conns.get(owner_id)
        if current is not dead_conn and current is not None and not getattr(
            current, "closed", False
        ):
            return  # owner came back on a new connection; leases live on
        for lid, lease in list(self.leases.items()):
            if getattr(lease, "owner_id", None) == owner_id:
                logger.info(
                    "reaping lease %d from silent owner %s", lid, owner_id
                )
                self._reap_lease(lid)
        self._owner_conns.pop(owner_id, None)

    def handle_owner_ping(self, payload, conn):
        """Driver liveness + lease re-association (sent periodically and
        after client reconnects)."""
        owner_id = payload.get("owner_id")
        if not owner_id:
            # oneway handler (clients only .notify): no reply frame ever
            # goes out, so returning a value would just be dead code.
            return
        prev = self._owner_conns.get(owner_id)
        self._owner_conns[owner_id] = conn
        timer = self._owner_reap_timers.pop(owner_id, None)
        if timer:
            timer.cancel()
        if prev is not conn:
            for lease in self.leases.values():
                if getattr(lease, "owner_id", None) == owner_id:
                    lease.owner_conn = conn
        return

    def _reap_lease(self, lease_id: int):
        """Release a dead owner's lease: free resources, KILL the worker
        (it may still be running the dead driver's task)."""
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return
        self._release_pool_resources(
            lease.resources, lease.instances, lease.pg_id, lease.bundle_index
        )
        self._kill_worker_proc(lease.worker)
        self._drain_lease_queue()

    # ---------------------------------------------------------------- actors
    async def handle_create_actor_worker(self, payload, conn):
        spec: ActorSpec = payload["spec"]
        resources = ResourceSet(spec.resources)
        bundle = self._resource_pool(spec.placement_group_id, spec.bundle_index, resources)
        if bundle is not None:
            if not resources.is_subset_of(bundle.available):
                raise ValueError("bundle resources exhausted")
            bundle.available = bundle.available - resources
        else:
            if not self.resources.acquire(resources):
                raise ValueError("insufficient resources for actor")
        instances = self._acquire_instances(resources)
        give_back = lambda: self._release_pool_resources(  # noqa: E731
            resources, instances or {}, spec.placement_group_id,
            spec.bundle_index,
        )
        if instances is None:
            give_back()
            raise ValueError("accelerator instances fragmented; retry")
        env_extra = dict(spec.env_vars)
        self._apply_chip_isolation(env_extra, instances)
        try:
            # Actor creations pop the same idle pool as task leases — a
            # pooled worker (pre-started, or recycled after running task
            # code) hosts the new actor instance, exactly like the
            # reference (``WorkerPool::PopWorker``,
            # src/ray/raylet/worker_pool.h:281, which also reuses workers
            # that executed tasks).  Once the actor is initialized the
            # process belongs to it: on actor death it is killed, never
            # re-pooled (_return_worker).
            worker = await self._pop_worker(env_extra)
            worker.is_actor = True
            worker.actor_id = spec.actor_id
            # Initialize the actor instance in the worker.
            reply = await self.worker_clients.get(worker.address).call(
                "actor_init",
                {"spec": spec, "incarnation": payload.get("incarnation", 0)},
                timeout=GlobalConfig.worker_startup_timeout_s,
            )
            if not reply.get("ok"):
                # Application error (user __init__ raised): kill the worker,
                # report non-retryably so the control plane marks the actor
                # DEAD instead of respawning forever.
                worker.is_actor = False
                worker.actor_id = None
                self._kill_worker_proc(worker)
                give_back()
                self._drain_lease_queue()
                return {"init_error": str(reply.get("error"))}
        except Exception:
            worker_handle = locals().get("worker")
            if worker_handle is not None:
                worker_handle.is_actor = False
                worker_handle.actor_id = None
                self._kill_worker_proc(worker_handle)
            give_back()
            self._drain_lease_queue()
            raise
        lease_id = self._next_lease_id
        self._next_lease_id += 1
        self.leases[lease_id] = Lease(
            lease_id,
            worker,
            resources,
            instances,
            spec.placement_group_id,
            spec.bundle_index,
            is_actor=True,
        )
        return {"worker_address": worker.address, "worker_id": worker.worker_id}

    # ---------------------------------------------------- placement bundles
    def _prepare_pg(self, pg_id: PlacementGroupID, bundles: dict) -> bool:
        """Reserve one group's bundles; atomic per group — on any bundle
        not fitting, every bundle already reserved HERE rolls back."""
        reserved = []
        for idx, spec in bundles.items():
            rs = ResourceSet(spec)
            if not self.resources.acquire(rs):
                for i in reserved:
                    pool = self.bundles.pop((pg_id, i))
                    self.resources.release(pool.total)
                return False
            self.bundles[(pg_id, idx)] = BundlePool(spec)
            reserved.append(idx)
        return True

    def handle_prepare_bundles(self, payload, conn):
        return {"ok": self._prepare_pg(payload["pg_id"], payload["bundles"])}

    def handle_prepare_bundles_batch(self, payload, conn):
        """Phase-1 reservation for SEVERAL placement groups in one RPC.
        Per-group atomic: a group that doesn't fit rolls back its own
        bundles and reports ok=False without affecting batch siblings."""
        return {
            "results": {
                g["pg_id"]: self._prepare_pg(g["pg_id"], g["bundles"])
                for g in payload["groups"]
            }
        }

    def handle_commit_bundles(self, payload, conn):
        self._commit_pg(payload["pg_id"])
        return True

    def _commit_pg(self, pg_id):
        for key, pool in self.bundles.items():
            if key[0] == pg_id:
                pool.committed = True

    def handle_commit_bundles_batch(self, payload, conn):
        for pg_id in payload["pg_ids"]:
            self._commit_pg(pg_id)
        return True

    def handle_reserve_bundles_batch(self, payload, conn):
        """Fused prepare+commit for groups placed wholly on this node —
        the control plane's single-node fast path (two-phase commit only
        pays for itself when a group spans agents)."""
        results = {}
        for g in payload["groups"]:
            ok = self._prepare_pg(g["pg_id"], g["bundles"])
            if ok:
                self._commit_pg(g["pg_id"])
            results[g["pg_id"]] = ok
        return {"results": results}

    def handle_cancel_bundles(self, payload, conn):
        return self._drop_bundles(payload["pg_id"])

    def handle_cancel_bundles_batch(self, payload, conn):
        for pg_id in payload["pg_ids"]:
            self._drop_bundles(pg_id, drain=False)
        self._drain_lease_queue()
        return True

    def handle_return_bundles(self, payload, conn):
        return self._drop_bundles(payload["pg_id"])

    def handle_return_bundles_batch(self, payload, conn):
        for pg_id in payload["pg_ids"]:
            self._drop_bundles(pg_id, drain=False)
        self._drain_lease_queue()
        return True

    def _held_pg_ids(self):
        """Distinct placement groups with live reservations on this node —
        shipped with (re-)registration so the control plane can reconcile:
        a group removed (or evicted) while this node was unreachable, or
        while the CP itself was restarting, must not pin resources here
        forever."""
        return list({key[0] for key in self.bundles})

    def _drop_stale_pgs(self, pg_ids) -> None:
        for pg_id in pg_ids or ():
            logger.info(
                "dropping stale bundle reservation for pg %s "
                "(control-plane reconciliation)", pg_id.hex()[:12],
            )
            self._drop_bundles(pg_id, drain=False)
        if pg_ids:
            self._drain_lease_queue()

    def _drop_bundles(self, pg_id, drain: bool = True):
        for key in [k for k in self.bundles if k[0] == pg_id]:
            pool = self.bundles.pop(key)
            # Only what no lease holds.  A lease still charged to the
            # bundle (an actor being killed: a TPU process takes seconds
            # to tens of seconds to let go of its chips) returns its share
            # when it ends — else the node advertises chips whose
            # instances are taken, and the next gang spins on
            # "instances fragmented".
            self.resources.release(pool.available)
        if drain:
            self._drain_lease_queue()
        return True

    # --------------------------------------------------------------- objects
    def handle_seal_object(self, payload, conn):
        # Guard against seal-after-free: seals are pipelined oneway frames
        # and a fast owner free (different connection for task-return
        # objects) may have already deleted the entry from the tiers.
        # Registering a dead oid would leak directory accounting forever.
        oid = payload["object_id"]
        if payload.get("tier") == "spill":
            # Arena-oversized object written straight to the disk spill
            # tier by its creator: index it as spilled (never shm-LRU'd).
            from .object_store import spill_path

            if os.path.exists(spill_path(self.session_id, oid)):
                self.directory.register_spilled(oid, payload["size"])
        elif self.shm_store.contains(oid):
            self.directory.seal(oid, payload["size"])
        # oneway handler (clients only .notify): the return value of a
        # msg_id-0 frame is silently dropped, so don't fake an ack.
        return

    def handle_free_objects(self, payload, conn):
        for oid in payload["object_ids"]:
            if oid in self._pull_futures:
                self._freed_during_pull.add(oid)
            self.directory.free(oid)
        return True

    def handle_list_objects(self, payload, conn):
        """Local sealed-object inventory for the state API (snapshot taken
        under the directory's tier lock — the spill thread mutates tiers
        concurrently)."""
        out = self.directory.inventory()
        for row in out:
            row["node_id"] = self.node_id.hex()
        return out

    def handle_object_info(self, payload, conn):
        size = self.directory.size_of(payload["object_id"])
        return {"exists": size is not None, "size": size}

    def handle_get_object_chunk(self, payload, conn):
        oid = payload["object_id"]
        if not self.directory.contains(oid) and not self.shm_store.contains(oid):
            return {"exists": False}
        view = self.shm_store.raw_bytes(oid)
        off, length = payload["offset"], payload["length"]
        # Out-of-band chunk: the pinned arena view rides the reply frame
        # as a raw segment — no bytes() copy on the serving agent (the pin
        # holds the block until the transport flushes the frame).
        from .serialization import oob_bytes

        return {
            "exists": True,
            "total": len(view),
            "data": oob_bytes(view[off : off + length]),
        }

    async def _pull_into_local(self, oid: ObjectID, from_agent: str):
        """Dedup'd pull of one object into local shm — the shared body of
        the single and batch pull RPCs.  Joiners of an in-flight pull are
        shielded (one requester's cancellation must not kill the pull for
        the rest) and only the future's owner pops the dedup entry (a
        cancelled joiner must not evict a still-running pull — a third
        requester would start a duplicate)."""
        if self.directory.contains(oid):
            return
        fut = self._pull_futures.get(oid)
        owner_of_fut = fut is None
        if owner_of_fut:
            fut = asyncio.get_running_loop().create_task(
                self._do_pull(oid, from_agent)
            )
            self._pull_futures[oid] = fut
        try:
            if owner_of_fut:
                await fut
            else:
                await asyncio.shield(fut)
        finally:
            if owner_of_fut:
                self._pull_futures.pop(oid, None)
                self._freed_during_pull.discard(oid)

    async def handle_pull_object(self, payload, conn):
        """Pull an object from a remote node into local shm (dedup'd)."""
        await self._pull_into_local(payload["object_id"], payload["from_agent"])
        return {"ok": True}

    async def handle_pull_objects(self, payload, conn):
        """Batch fan-in for the data-plane fast path: pull many objects
        concurrently (dedup'd against in-flight singles) with per-object
        failure isolation — one dead source must not fail the batch.
        Returns ``errors`` aligned with ``items`` (None on success)."""

        async def pull_one(oid: ObjectID, from_agent: str):
            try:
                await self._pull_into_local(oid, from_agent)
                return None
            except Exception as e:  # noqa: BLE001 — reported per-slot
                return f"{type(e).__name__}: {e}"

        errors = await asyncio.gather(
            *(pull_one(oid, src) for oid, src in payload["items"])
        )
        return {"errors": list(errors)}

    async def _do_pull(self, oid: ObjectID, from_agent: str):
        client = self.agent_clients.get(from_agent)
        chunk = GlobalConfig.object_chunk_bytes
        first = await client.call(
            "get_object_chunk", {"object_id": oid, "offset": 0, "length": chunk}
        )
        if not first["exists"]:
            raise KeyError(f"object {oid} not on {from_agent}")
        total = first["total"]
        parts = [first["data"]]
        got = len(first["data"])
        while got < total:
            part = await client.call(
                "get_object_chunk",
                {"object_id": oid, "offset": got, "length": chunk},
            )
            parts.append(part["data"])
            got += len(part["data"])
        payload = b"".join(parts)
        # Executor: the store write is a full-payload copy — for an
        # arena-oversized object, a multi-hundred-MB DISK write — and must
        # not stall the agent loop (heartbeats, lease grants).
        size, tier = await asyncio.get_running_loop().run_in_executor(
            None, self.shm_store.create_from_bytes, oid, payload
        )
        if oid in self._freed_during_pull:
            # Freed while the pull was in flight: sealing now would
            # register a dead oid forever.  Delete the just-written copy
            # instead (free is idempotent across tiers).
            self._freed_during_pull.discard(oid)
            self.directory.free(oid)
            return
        if tier == "spill":
            self.directory.register_spilled(oid, size)
        else:
            self.directory.seal(oid, size)

    async def handle_remediate(self, payload, conn):
        """Remediation directive fan-out: forward the directives to every
        live local worker's ``remediate`` handler.  The remediation
        controller broadcasts through agents (one RPC per node) so
        per-process actuators — the collective tuner, registered
        in-process hooks — are reachable without per-worker addressing.
        Per-worker failures are isolated, mirroring the obs pull."""
        from ..util import flight_recorder as fr

        directives = payload.get("directives", ())
        timeout = max(1.0, GlobalConfig.health_check_period_s)

        async def one(handle):
            if handle.address is None or handle.proc.poll() is not None:
                return None
            try:
                return await self.worker_clients.get(handle.address).call(
                    "remediate", {"directives": directives}, timeout=timeout,
                )
            except Exception:  # noqa: BLE001 — worker may be mid-exit
                fr.count_suppressed("remediate_fanout")
                return None

        replies = await asyncio.gather(
            *(one(h) for h in list(self.workers.values()))
        )
        done = [r for r in replies if r]
        return {"workers": len(done), "results": done}

    async def handle_prepare_evict(self, payload, conn):
        """Checkpoint fan-out ahead of a preemption: every local worker
        holding a lease of the victim placement group gets a
        ``prepare_evict`` call so its workload can checkpoint through its
        existing restart machinery before the bundle is reclaimed.
        Best-effort with per-worker isolation (like ``remediate``): a
        wedged worker forfeits its checkpoint, never the eviction."""
        from ..util import flight_recorder as fr

        pg_id = payload["pg_id"]
        timeout = max(1.0, float(
            payload.get("timeout")
            or GlobalConfig.sched_evict_checkpoint_timeout_s
        ))
        cause = payload.get("cause", "")
        targets = []
        seen = set()
        for lease in list(self.leases.values()):
            if lease.pg_id != pg_id:
                continue
            handle = lease.worker
            if handle.address is None or handle.address in seen:
                continue
            if handle.proc.poll() is not None:
                continue
            seen.add(handle.address)
            targets.append(handle)

        async def one(handle):
            try:
                reply = await self.worker_clients.get(handle.address).call(
                    "prepare_evict", {"cause": cause}, timeout=timeout,
                    retries=1,
                )
                return bool(reply and reply.get("checkpointed"))
            except Exception:  # noqa: BLE001 — evict proceeds regardless
                fr.count_suppressed("prepare_evict_fanout")
                return False

        results = await asyncio.gather(*(one(h) for h in targets))
        return {"acks": sum(1 for r in results if r), "workers": len(targets)}

    def handle_ping(self, payload, conn):
        return "pong"

    def handle_prestart_pool(self, payload, conn):
        """Force the warm pool toward its floor at normal priority NOW.

        Reference analog: ``ray._private.state.prestart_workers`` /
        ``WorkerPool::PrestartWorkers`` (raylet ``worker_pool.h:281``) —
        callers that know a creation burst is coming (benchmarks, batch
        drivers) warm the pool deterministically instead of relying on
        the quiet-time background refill, whose SCHED_IDLE imports can
        starve arbitrarily long on a contended core."""
        # Hold hot mode open until this fill completes (the 5 s pop-miss
        # window is too short for a full 16-worker fill on one core).
        self._prestart_hot_until = time.monotonic() + 120.0
        self._replenish_pool()
        key = self._default_env_key
        return {
            "idle": len(self.idle_pool.get(key, [])),
            "inflight": len(self._prestart_inflight),
            "floor": self._pool_floor(),
        }

    def handle_debug_state(self, payload, conn):
        return {
            "node_id": self.node_id.hex(),
            "resources": self._snapshot(),
            "num_workers": len(self.workers),
            "idle": {str(k): len(v) for k, v in self.idle_pool.items()},
            "idle_pids": sorted(
                h.proc.pid for v in self.idle_pool.values() for h in v
            ),
            "prestart_inflight": len(self._prestart_inflight),
            "pool_floor": self._pool_floor(),
            "leases": len(self.leases),
            "queued_leases": len(self._lease_queue),
            "objects": len(self.directory.object_ids()),
            "object_bytes": self.directory.used,
            "spilled_objects": len(self.directory._spilled),
            "spilled_bytes": self.directory.spilled_bytes,
            "num_spilled_total": self.directory.num_spilled,
            "rpc_stats": dict(self.server.stats),
            "rpc_lanes": self.server.lane_stats(),
            # Aggregator introspection: rounds counts obs pulls (ridden on
            # the heartbeat); background_loops names every periodic task
            # this agent runs so tests can pin "no new periodic RPC loop".
            "obs": {
                "rounds": self._obs_rounds,
                "workers_pulled": self._obs_workers_pulled,
                "events_forwarded": self._obs_events_forwarded,
            },
            "background_loops": sorted(
                t.get_coro().__qualname__ for t in self._bg
            ),
        }



def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--cp-address", required=True)
    parser.add_argument("--session-id", required=True)
    parser.add_argument(
        "--owns-session-shm", default="0",
        help="1 = this agent owns session shm cleanup on parent death "
        "(set for the head node's agent only)",
    )
    parser.add_argument("--resources", required=True, help="JSON dict")
    parser.add_argument("--labels", default="{}", help="JSON dict")
    parser.add_argument(
        "--cp-ha-dir", default=None,
        help="control-plane HA directory; the CP client follows the "
        "published leader endpoint across failovers",
    )
    args = parser.parse_args()

    def _unlink_session_arena(session_id=args.session_id):
        from .object_store import arena_path

        try:
            os.unlink(arena_path(session_id))
        except OSError:
            pass
        try:
            os.unlink(arena_path(session_id) + ".owner")
        except OSError:
            pass

    if args.owns_session_shm == "1":
        # This agent owns its session's arena: stamp ownership (pid +
        # starttime, PID-reuse-proof) and sweep arenas orphaned by
        # SIGKILLed heads of PAST sessions — their reaper never ran, and
        # nothing else ever deletes them (head-owned cleanup).
        from .object_store import arena_path as _ap
        from .reaper import _proc_start_time
        from .shm import SHM_DIR, _PREFIX

        try:
            with open(_ap(args.session_id) + ".owner", "w") as f:
                f.write(f"{os.getpid()} {_proc_start_time(os.getpid())}")
        except OSError:
            pass
        for fname in os.listdir(SHM_DIR):
            if not (fname.startswith(f"{_PREFIX}_") and
                    fname.endswith("_arena")):
                continue
            path = os.path.join(SHM_DIR, fname)
            if path == _ap(args.session_id):
                continue
            try:
                with open(path + ".owner") as f:
                    pid_s, _, start_s = f.read().partition(" ")
                alive = _proc_start_time(int(pid_s)) == start_s
            except (OSError, ValueError):
                # No ownership stamp: NEVER assume dead (mmap writes don't
                # reliably bump mtime, so age is not proof) — leave it.
                continue
            if not alive:
                logger.info("sweeping orphan session arena %s", fname)
                # The whole dead session's shm: arena + per-object
                # segments (rtpu_<sid>_<objhex>) + owner stamp.
                from .shm import cleanup_session

                dead_sid = fname[len(_PREFIX) + 1:-len("_arena")]
                cleanup_session(dead_sid)
                try:
                    os.unlink(path + ".owner")
                except OSError:
                    pass

    from .reaper import watch_parent_process

    watch_parent_process(
        on_exit=(
            _unlink_session_arena
            if args.owns_session_shm == "1"
            else None
        )
    )
    import json

    logging.basicConfig(
        level=GlobalConfig.log_level,
        format="%(asctime)s %(levelname)s node_agent: %(message)s",
    )

    async def run():
        from .stack_dump import install_signal_dumpers

        install_signal_dumpers(asyncio.get_running_loop())
        agent = NodeAgent(
            args.host,
            args.port,
            args.cp_address,
            args.session_id,
            json.loads(args.resources),
            json.loads(args.labels),
            cp_ha_dir=args.cp_ha_dir,
        )
        await agent.start()
        stopping = asyncio.Event()
        asyncio.get_running_loop().add_signal_handler(
            signal.SIGTERM, stopping.set)
        await stopping.wait()
        await agent.stop()

    asyncio.run(run())


if __name__ == "__main__":
    main()
