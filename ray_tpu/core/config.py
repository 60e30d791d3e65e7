"""Typed, env-var-overridable configuration knobs.

Equivalent of the reference's ``RAY_CONFIG(type, name, default)`` macro table
(Ray ``src/ray/common/ray_config_def.h``, overridden via ``RAY_<name>`` env
vars).  Here each knob is declared once in ``_KNOBS`` and can be overridden by
``RAY_TPU_<name>`` in the environment or programmatically via
``Config.override`` (the analog of the driver-shipped ``_system_config``).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_ENV_PREFIX = "RAY_TPU_"


def _parse(typ, raw: str):
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if typ in (dict, list):
        return json.loads(raw)
    return typ(raw)


# name -> (type, default, doc)
_KNOBS: Dict[str, tuple] = {
    # -- RPC layer --
    "rpc_connect_timeout_s": (float, 10.0, "TCP connect timeout"),
    "rpc_call_timeout_s": (float, 60.0, "Default RPC deadline"),
    "rpc_retry_base_delay_s": (float, 0.05, "Exponential backoff base"),
    "rpc_retry_max_delay_s": (float, 2.0, "Backoff cap"),
    "rpc_max_retries": (int, 8, "Retryable RPC attempts"),
    "rpc_retry_jitter": (
        bool, True,
        "Decorrelated-jitter backoff (AWS-style: sleep = uniform(base, "
        "prev*3) capped) instead of the deterministic doubling schedule.  "
        "Deterministic backoff synchronizes every client's reconnect "
        "attempt after a control-plane restart — a thundering herd",
    ),
    "rpc_native_codec": (
        bool, True,
        "Use the C frame codec (librtpu_native.so rtpu_frame_*) for v2 "
        "wire frames when the native library loads; the pure-Python codec "
        "is the always-available, byte-identical fallback",
    ),
    "rpc_direct_submit": (
        bool, True,
        "User-thread direct submit: eligible sync-path actor pushes "
        "serialize and send() on the submitting thread under the "
        "connection's write lock, skipping the call_soon_threadsafe "
        "self-pipe wake and the per-call submission task on the loop",
    ),
    "rpc_timeout_wheel_ms": (
        int, 50,
        "Bucket granularity of the shared RPC timeout wheel (one coarse "
        "timer services every in-flight call deadline on a loop; a "
        "deadline fires at most one bucket late).  0 restores per-call "
        "asyncio.wait_for timers",
    ),
    "rpc_service_lanes": (
        int, 0,
        "Event-loop lanes per RPC service (0 = auto: min(4, cpus) for the "
        "many-client servers — control plane, node agent, driver owner "
        "service — and 1 for worker executors).  Connections pin to a "
        "lane at accept time, preserving per-connection ordering; "
        "handlers outside LANE_SAFE_METHODS forward to the primary loop",
    ),
    "owner_table_shards": (
        int, 16,
        "Shards of the per-worker owned-object table (power of two).  "
        "Lane-side get/probe resolution indexes shards independently so "
        "many borrower connections resolve concurrently",
    ),
    "pg_commit_batch_max": (
        int, 64,
        "Max placement groups per control-plane group-commit sweep: "
        "concurrent create/remove requests arriving while a sweep is in "
        "flight coalesce into the next one (single bundle-reservation "
        "sweep + one prepare/commit RPC pass per node per batch)",
    ),
    "testing_rpc_failure": (str, "", "Chaos spec: 'method:prob_req:prob_resp,…'"),
    "testing_network_delay": (
        str, "",
        "Latency chaos: 'method:prob:delay_ms[:jitter_ms],…' ('*' = all)",
    ),
    # -- control plane --
    "cp_persistence": (int, 1, "Durable sqlite control-plane tables (restart FT)"),
    "cp_ha": (
        int, 0,
        "Control-plane high availability: the head spawns two CP "
        "candidates contending for a leader lease over a shared journal "
        "(core/cp_ha.py); the warm standby takes over within the lease "
        "TTL when the leader dies",
    ),
    "cp_lease_ttl_s": (
        float, 2.0,
        "Leader lease validity window: a standby may take over this long "
        "after the leader's last renewal.  The detect half of the "
        "failover window — keep well above cp_lease_poll_s",
    ),
    "cp_lease_poll_s": (
        float, 0.25,
        "Standby lease-acquisition poll (and journal tail) period",
    ),
    "cp_journal_fsync_interval_s": (
        float, 0.05,
        "Journal fsync batching: appends flush to the OS immediately "
        "(process kill -9 loses nothing) and fsync at most this often "
        "(whole-host crash window, the synchronous=NORMAL trade)",
    ),
    "cp_journal_compact_bytes": (
        int, 8 << 20,
        "Journal bytes past the last snapshot before the leader compacts "
        "into a fresh snapshot",
    ),
    "health_check_period_s": (float, 1.0, "Agent heartbeat period"),
    "health_check_timeout_s": (float, 10.0, "Mark node dead after this long"),
    "resource_sync_period_s": (float, 0.2, "Resource view gossip period"),
    # -- scheduling --
    "scheduler_spread_threshold": (float, 0.5, "Pack until this utilization, then spread"),
    # -- multi-tenant arbitration --
    "sched_default_priority": (
        int, 100,
        "Priority assigned to jobs that register without one (higher = "
        "more important).  Serve deployments and other latency-critical "
        "work should register above it, batch/training below",
    ),
    "sched_preemption_enabled": (
        bool, True,
        "Checkpoint-then-evict preemption: a higher-priority bundle that "
        "cannot place may evict lower-priority placement groups (victims "
        "checkpoint via prepare_evict, are re-queued PENDING, and resume "
        "automatically when capacity frees)",
    ),
    "sched_preemption_burst": (
        int, 3,
        "Token-bucket capacity of each job's preemption budget: at most "
        "this many victim evictions in a burst, refilling one per "
        "sched_preemption_cooldown_s.  Bounds the damage a crash-looping "
        "high-priority job can do",
    ),
    "sched_preemption_cooldown_s": (
        float, 30.0, "Seconds to refill one preemption token"
    ),
    "sched_preemption_quarantine_s": (
        float, 600.0,
        "A job that drains its preemption budget is quarantined from "
        "preempting (not from running) for this long",
    ),
    "sched_evict_checkpoint_timeout_s": (
        float, 10.0,
        "Deadline for a victim's prepare_evict checkpoint fan-out; on "
        "expiry the eviction proceeds anyway (the restart path falls "
        "back to the last driver-side checkpoint)",
    ),
    "drain_timeout_s": (
        float, 60.0,
        "Deadline for a draining node to empty (residents evicted via "
        "prepare_evict, leases finished); on expiry the autoscaler "
        "terminates anyway — the restart machinery recovers whatever "
        "was still resident",
    ),
    "drain_poll_period_s": (
        float, 0.5,
        "How often the autoscaler polls drain_status for nodes it is "
        "retiring",
    ),
    "scheduler_top_k_fraction": (float, 0.2, "Top-k random choice fraction"),
    "lease_idle_timeout_s": (float, 0.3, "Return idle leased worker after"),
    "task_push_keepalive_s": (
        float, 60.0,
        "Re-send a task push if no reply within this window (dedup makes "
        "resends exactly-once; converts silent reply loss into a bounded "
        "delay instead of an infinite wait)",
    ),
    "lease_owner_grace_s": (
        float, 8.0,
        "Reconnect window before a disconnected owner's leases are reaped",
    ),
    "worker_startup_timeout_s": (
        float, 300.0,
        "Deadline for a worker process to start AND for an actor's "
        "constructor to return (the creation chain derives its deadlines "
        "from this one).  A constructor that builds a model on a cold chip "
        "— backend start, weights, first compiles — takes minutes",
    ),
    "max_tasks_in_flight_per_worker": (int, 10, "Pipelined pushes per leased worker"),
    # -- object store --
    "max_inline_object_bytes": (int, 100 * 1024, "Inline small objects in RPCs"),
    "lineage_pinning": (int, 1, "Pin task args while returns live (reconstruction)"),
    "borrow_handoff_grace_s": (
        float, 10.0,
        "Keep escaped/borrowed refs alive this long past their last local "
        "ref so in-flight borrower increfs never race a free",
    ),
    "max_object_reconstructions": (int, 3, "Lineage re-execution attempts per get"),
    "object_store_memory_bytes": (int, 2 * 1024**3, "Per-node shm budget"),
    "object_store_prefault": (
        bool, False,
        "Fault in every arena page at creation (plasma preallocate analog): "
        "slower startup + committed tmpfs, full-bandwidth first-touch puts",
    ),
    "object_chunk_bytes": (int, 5 * 1024 * 1024, "Chunk size for node-to-node transfer"),
    "memory_store_fallback_bytes": (int, 512 * 1024 * 1024, "In-process store budget"),
    "object_spill_threshold_bytes": (
        int, 0,
        "Objects larger than this are written straight to the disk spill "
        "tier instead of shm (0 = auto: anything larger than the arena, "
        "object_store_memory_bytes — a put that can never fit shm must "
        "not gamble on tmpfs overcommit, whose failure mode is SIGBUS)",
    ),
    "object_spill_max_bytes": (
        int, 0,
        "Disk spill-tier capacity (0 = unlimited).  A put that would "
        "exceed it raises ObjectStoreFullError instead of filling the "
        "disk — spill exhaustion must be a clear error, never a hang",
    ),
    # -- submission backpressure --
    "task_queue_memory_cap_bytes": (
        int, 256 * 1024 * 1024,
        "Byte budget for pending task submissions (serialized args of "
        "tasks not yet completed).  Submitting threads block when a new "
        "submission would cross it, so a fast producer's queue cannot "
        "grow driver RSS without bound (0 = unlimited)",
    ),
    "task_queue_block_timeout_s": (
        float, 300.0,
        "How long a submission may block on the queue-memory cap before "
        "raising PendingTaskBackpressureTimeout",
    ),
    # -- workers --
    "num_workers_soft_limit": (int, 0, "0 = num_cpus"),
    "worker_niceness": (int, 0, "Nice level for spawned workers"),
    "prestart_workers": (int, 0, "Idle-pool floor per node (0 off, -1 = CPU count)"),
    # -- OOM defense --
    "memory_monitor_period_s": (float, 1.0, "0 disables the memory monitor"),
    "memory_monitor_threshold": (float, 0.95, "Kill workers above this usage"),
    "memory_monitor_fake_usage_file": (
        str, "", "Testing: read usage fraction from this file instead of /proc"
    ),
    # -- fault tolerance --
    "task_max_retries_default": (int, 3, "Default retries for idempotent tasks"),
    "actor_max_restarts_default": (int, 0, "Default actor restarts"),
    # -- isolation --
    "enable_resource_isolation": (
        bool, False,
        "Place workers in a cgroup-v2 subtree with cpu/memory limits "
        "(needs a writable /sys/fs/cgroup; silently disabled otherwise)",
    ),
    "worker_cgroup_memory_limit_bytes": (
        int, 0, "0 = no memory.max on the workers cgroup"
    ),
    # -- TPU --
    "tpu_visible_chips_env": (str, "TPU_VISIBLE_CHIPS", "Env var used for chip isolation"),
    # -- collectives --
    "collective_autotune": (
        bool, True,
        "Online per-bucket collective algorithm selection (flat/ring/"
        "tree/two-level by op, message size, world size, ICI-vs-DCN "
        "topology), fed by the flight recorder's achieved-bandwidth "
        "capture.  Off = the static heuristic table only",
    ),
    "collective_quantized_allreduce": (
        bool, False,
        "Process default for SUM-allreduce block quantization (int8 "
        "blocks + per-block scales, EQuARX-style) on float payloads — "
        "~4x fewer wire bytes on bandwidth-bound gradient exchange with "
        "a bounded per-block error.  OFF by default; per-call "
        "allreduce(..., quantized=True) overrides",
    ),
    "collective_quant_block_size": (
        int, 256, "Elements per quantization block (one fp32 scale each)"
    ),
    # -- data --
    "data_max_tasks_per_op": (int, 8, "Streaming executor in-flight cap per op"),
    "data_memory_budget_per_op_bytes": (
        int, 256 * 1024 * 1024, "Estimated in-flight output bytes cap per op"
    ),
    "data_memory_budget_total_bytes": (
        int, 0, "Pipeline-wide in-flight budget split across ops "
        "(0 = object_store_memory_bytes * data_memory_budget_fraction)"
    ),
    "data_memory_budget_fraction": (
        float, 0.5, "Fraction of the shm budget the data pipeline may hold"
    ),
    "data_output_queue_depth": (
        int, 16, "Completed-but-unconsumed blocks buffered per streaming "
        "op before its launches stall (scheduler output bound)"
    ),
    "data_target_block_size_bytes": (
        int, 0, "Dynamic block shaping target: map outputs above it are "
        "split, undersized runs coalesced before the next exchange "
        "(0 = shaping off; ExecutionOptions can override per-plan)"
    ),
    "data_autoscale_interval_s": (
        float, 0.1, "Min seconds between actor-pool autoscale decisions"
    ),
    "data_autoscale_idle_s": (
        float, 0.5, "Sustained starvation (idle actor, empty input queue) "
        "before an autoscaling pool kills an actor above min_size"
    ),
    "data_straggler_wait_slice_s": (
        float, 5.0, "Per-pass bound on the scheduler's blocking "
        "completion wait (straggler harvest loops, never parks unbounded)"
    ),
    # -- serve --
    "serve_health_check_timeout_s": (
        float, 10.0, "Per-sweep deadline for replica health replies"
    ),
    "serve_health_failure_threshold": (
        int, 30, "Consecutive health timeouts before a replica is replaced "
        "(building a real model and its first-request jax compiles keep a "
        "replica silent for minutes on a cold chip; a DEAD replica fails "
        "its check at once and never waits for this)"
    ),
    # -- usage stats --
    "usage_stats_enabled": (bool, True, "Cluster-local usage recording"),
    # -- task events / observability --
    "enable_task_events": (bool, True, "Record task lifecycle events"),
    "enable_flight_recorder": (
        bool, True,
        "Runtime-internal telemetry: per-task phase timings, collective "
        "op/bytes/bandwidth capture, object-store and backpressure "
        "counters (ray_tpu_* metrics + timeline phase rows).  Guarded at "
        "<5% round-trip overhead by tests/test_flight_recorder.py "
        "(TestObsOverheadEnvelope, a slow test)",
    ),
    "enable_obs_aggregator": (
        bool, True,
        "Node-agent pull of each local worker's span/task-event/metric "
        "deltas, ridden on the existing heartbeat (one obs_report RPC "
        "per beat; no new periodic loop).  Workers drop their own "
        "task-event flush to a slow backup cadence while pulled",
    ),
    "enable_remediation": (
        bool, False,
        "Auto-attach the SLO remediation controller (util/remediation.py) "
        "when the dashboard starts: findings are mapped to bounded "
        "actuator actions (serve scale-up, pipeline-stage respawn, "
        "tuner re-probe) each aggregation beat.  Off by default — "
        "explicit remediation.start() always works",
    ),
    "remediation_beat_s": (
        float, 0.0,
        "Remediation controller beat period; 0 follows the node-agent "
        "heartbeat (health_check_period_s), the cadence aggregated "
        "telemetry actually arrives on",
    ),
    "task_events_flush_period_s": (float, 0.5, "Worker buffer flush period"),
    "task_events_max_buffer": (int, 10000, "Per-worker unflushed event cap"),
    "task_events_max_stored": (int, 100000, "Control-plane stored task cap"),
    # -- logging --
    "log_level": (str, "INFO", "Python log level for system processes"),
    "session_dir": (str, "", "Session directory (default: /tmp/ray_tpu/session_*)"),
    "event_stats_print_period_s": (float, 0.0, "0 disables periodic handler-latency dumps"),
}


class Config:
    """Process-wide configuration singleton.

    Knob reads are hot-path (RPC timeouts, inline thresholds, event gates
    fire per task), so each knob is resolved once — env var consulted at
    first access, like the reference's process-start env parse — and cached
    in the instance ``__dict__`` where subsequent reads bypass
    ``__getattr__`` entirely.  ``override()`` updates the cache;
    ``reload()`` drops it (tests that mutate the environment)."""

    def __init__(self):
        self._overrides: Dict[str, Any] = {}

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            typ, default, _doc = _KNOBS[name]
        except KeyError:
            raise AttributeError(f"unknown config knob {name!r}") from None
        if name in self._overrides:
            value = self._overrides[name]
        else:
            raw = os.environ.get(_ENV_PREFIX + name)
            value = _parse(typ, raw) if raw is not None else default
        self.__dict__[name] = value
        return value

    def override(self, **kwargs):
        for k, v in kwargs.items():
            if k not in _KNOBS:
                raise ValueError(f"unknown config knob {k!r}")
            self._overrides[k] = v
            self.__dict__[k] = v

    def reload(self):
        """Drop cached knob values so the next access re-reads the env."""
        for k in _KNOBS:
            self.__dict__.pop(k, None)

    def overrides_as_env(self) -> Dict[str, str]:
        """Serialize programmatic overrides as env vars to ship to child
        processes (the analog of passing _system_config through argv)."""
        env = {}
        for k, v in self._overrides.items():
            typ = _KNOBS[k][0]
            env[_ENV_PREFIX + k] = json.dumps(v) if typ in (dict, list) else str(v)
        return env

    def snapshot(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in _KNOBS}


GlobalConfig = Config()
