"""Single registry of the runtime's built-in metric names.

Every ``ray_tpu_*`` metric the runtime emits is declared HERE and only
here — runtime modules import the constants instead of spelling the
string at the record site.  ``raylint`` rule **RTL004** enforces this:
a ``ray_tpu_*`` string literal anywhere else in the package is a lint
violation, and every name declared here must be documented in
``docs/observability.md``.  One registry means the exposition surface
(``/metrics``, ``metrics.snapshot()``) can be enumerated without
grepping the runtime, and a renamed or deleted metric fails lint
instead of silently orphaning its dashboard.
"""

from __future__ import annotations

from typing import Dict

# --------------------------------------------------------- task lifecycle
TASK_PHASE_HIST = "ray_tpu_task_phase_s"
BACKPRESSURE_WAIT_HIST = "ray_tpu_backpressure_wait_s"
BACKPRESSURE_BLOCKED_TOTAL = "ray_tpu_backpressure_blocked_total"
TASK_EVENTS_DROPPED_TOTAL = "ray_tpu_task_events_dropped_total"
TRACE_SPANS_DROPPED_TOTAL = "ray_tpu_trace_spans_dropped_total"

# --------------------------------------------- cluster observability plane
SLO_VIOLATIONS_TOTAL = "ray_tpu_slo_violations_total"

# -------------------------------------------------- self-healing remediation
REMEDIATION_ACTIONS_TOTAL = "ray_tpu_remediation_actions_total"
REMEDIATION_QUARANTINED = "ray_tpu_remediation_quarantined"

# ------------------------------------------------- per-request serving SLO
SERVE_TTFT_HIST = "ray_tpu_serve_ttft_s"
SERVE_INTER_TOKEN_HIST = "ray_tpu_serve_inter_token_s"
SERVE_QUEUE_WAIT_HIST = "ray_tpu_serve_queue_wait_s"
SERVE_REQUESTS_TOTAL = "ray_tpu_serve_requests_total"
SERVE_AUTOSCALE_EVENTS_TOTAL = "ray_tpu_serve_autoscale_events_total"
SERVE_REPLICAS = "ray_tpu_serve_replicas"
SERVE_MUX_CACHE_EVENTS_TOTAL = "ray_tpu_serve_mux_cache_events_total"

# ------------------------------------------------ LLM serving (JaxLLMEngine)
LLM_BATCH_OCCUPANCY = "ray_tpu_llm_batch_occupancy"
LLM_BATCH_BUCKET = "ray_tpu_llm_batch_bucket"
LLM_QUEUE_DEPTH = "ray_tpu_llm_queue_depth"
LLM_DECODE_STEPS_TOTAL = "ray_tpu_llm_decode_steps_total"
LLM_ADMITTED_TOTAL = "ray_tpu_llm_admitted_total"
LLM_RETIRED_TOTAL = "ray_tpu_llm_retired_total"
LLM_PREFIX_CACHE_HITS_TOTAL = "ray_tpu_llm_prefix_cache_hits_total"
LLM_PREFIX_CACHE_MISSES_TOTAL = "ray_tpu_llm_prefix_cache_misses_total"

# ------------------------------------------------------------ collectives
COLLECTIVE_OPS_TOTAL = "ray_tpu_collective_ops_total"
COLLECTIVE_BYTES_TOTAL = "ray_tpu_collective_bytes_total"
COLLECTIVE_DURATION_HIST = "ray_tpu_collective_duration_s"
COLLECTIVE_BANDWIDTH_HIST = "ray_tpu_collective_bandwidth_bytes_per_s"
ICI_SCALING_EFFICIENCY = "ray_tpu_ici_scaling_efficiency"
# Algorithm selection / online autotuner (docs/collective.md)
COLLECTIVE_ALGO_OPS_TOTAL = "ray_tpu_collective_algo_ops_total"
COLLECTIVE_TUNER_EXPLORATIONS_TOTAL = (
    "ray_tpu_collective_tuner_explorations_total"
)
COLLECTIVE_TUNER_COMMITS_TOTAL = "ray_tpu_collective_tuner_commits_total"
COLLECTIVE_TUNER_BEST_BANDWIDTH = (
    "ray_tpu_collective_tuner_best_bandwidth_bytes_per_s"
)
COLLECTIVE_QUANTIZED_OPS_TOTAL = "ray_tpu_collective_quantized_ops_total"
COLLECTIVE_QUANTIZED_BYTES_SAVED_TOTAL = (
    "ray_tpu_collective_quantized_bytes_saved_total"
)

# ----------------------------------------------------------- object store
OBJECT_STORE_FULL_ERRORS_TOTAL = "ray_tpu_object_store_full_errors_total"
OBJECT_STORE_SPILL_BYTES_TOTAL = "ray_tpu_object_store_spill_bytes_total"
OBJECT_STORE_SPILL_RECLAIMED_TOTAL = (
    "ray_tpu_object_store_spill_reclaimed_bytes_total"
)
OBJECT_STORE_LRU_EVICTIONS_TOTAL = "ray_tpu_object_store_lru_evictions_total"
OBJECT_STORE_USED_BYTES = "ray_tpu_object_store_used_bytes"
OBJECT_STORE_CAPACITY_BYTES = "ray_tpu_object_store_capacity_bytes"
OBJECT_STORE_NUM_OBJECTS = "ray_tpu_object_store_num_objects"
OBJECT_STORE_SPILL_TIER_BYTES = "ray_tpu_object_store_spill_tier_bytes"
OBJECT_STORE_SPILL_TIER_OBJECTS = "ray_tpu_object_store_spill_tier_objects"

# ---------------------------------------------------- data-plane fast path
GET_BATCH_CALLS_TOTAL = "ray_tpu_get_batch_calls_total"
GET_BATCH_REFS_TOTAL = "ray_tpu_get_batch_refs_total"
LOCATION_CACHE_HITS_TOTAL = "ray_tpu_object_location_cache_hits_total"
LOCATION_CACHE_MISSES_TOTAL = "ray_tpu_object_location_cache_misses_total"
LOCATION_CACHE_INVALIDATIONS_TOTAL = (
    "ray_tpu_object_location_cache_invalidations_total"
)
RPC_OOB_FRAMES_TOTAL = "ray_tpu_rpc_oob_frames_total"
RPC_OOB_BYTES_TOTAL = "ray_tpu_rpc_oob_bytes_total"
RPC_BATCH_FRAMES_TOTAL = "ray_tpu_rpc_batch_frames_total"
RPC_BATCHED_CALLS_TOTAL = "ray_tpu_rpc_batched_calls_total"

# ------------------------------------------------- data streaming scheduler
DATA_QUEUE_DEPTH = "ray_tpu_data_queue_depth"
DATA_STRAGGLER_WAIT_HIST = "ray_tpu_data_straggler_wait_s"
DATA_AUTOSCALE_EVENTS_TOTAL = "ray_tpu_data_autoscale_events_total"
DATA_POOL_SIZE = "ray_tpu_data_pool_size"
DATA_BLOCKS_SPLIT_TOTAL = "ray_tpu_data_blocks_split_total"
DATA_BLOCKS_COALESCED_TOTAL = "ray_tpu_data_blocks_coalesced_total"
DATA_BLOCKS_EMITTED_TOTAL = "ray_tpu_data_blocks_emitted_total"
TASKS_CANCELLED_TOTAL = "ray_tpu_tasks_cancelled_total"

# ------------------------------------------------- sharded control plane
RPC_LANE_FRAMES_TOTAL = "ray_tpu_rpc_lane_frames_total"
RPC_LANE_FORWARDED_TOTAL = "ray_tpu_rpc_lane_forwarded_total"
RPC_LANE_CONNECTIONS = "ray_tpu_rpc_lane_connections"
RPC_LANE_QUEUE_DEPTH = "ray_tpu_rpc_lane_queue_depth"
RPC_LANE_DISPATCH_WAIT_HIST = "ray_tpu_rpc_lane_dispatch_wait_s"
OWNER_SHARD_LOOKUPS_TOTAL = "ray_tpu_owner_shard_lookups_total"
OWNER_SHARD_FAST_ENTRIES_TOTAL = "ray_tpu_owner_shard_fast_entries_total"
OWNER_SHARD_FORWARDED_ENTRIES_TOTAL = (
    "ray_tpu_owner_shard_forwarded_entries_total"
)
OWNER_SHARD_OBJECTS_MAX = "ray_tpu_owner_shard_objects_max"
PG_COMMIT_BATCHES_TOTAL = "ray_tpu_pg_commit_batches_total"
PG_COMMIT_BATCHED_GROUPS_TOTAL = "ray_tpu_pg_commit_batched_groups_total"
PG_COMMIT_FUSED_TOTAL = "ray_tpu_pg_commit_fused_total"
PG_COMMIT_ROLLBACKS_TOTAL = "ray_tpu_pg_commit_rollbacks_total"

# ------------------------------------------------- pipeline parallelism
PIPELINE_STAGE_FWD_HIST = "ray_tpu_pipeline_stage_fwd_s"
PIPELINE_STAGE_BWD_HIST = "ray_tpu_pipeline_stage_bwd_s"
PIPELINE_STAGE_STALL_HIST = "ray_tpu_pipeline_stage_stall_s"
PIPELINE_BUBBLE_FRACTION = "ray_tpu_pipeline_bubble_fraction"
PIPELINE_ACTIVATION_BYTES_TOTAL = "ray_tpu_pipeline_activation_bytes_total"
PIPELINE_ACTIVATION_BANDWIDTH_HIST = (
    "ray_tpu_pipeline_activation_bandwidth_bytes_per_s"
)
PIPELINE_MICROBATCHES_TOTAL = "ray_tpu_pipeline_microbatches_total"
PIPELINE_STAGE_RESTARTS_TOTAL = "ray_tpu_pipeline_stage_restarts_total"

# ------------------------------------------------------------- scheduling
LEASE_GRANT_WAIT_HIST = "ray_tpu_lease_grant_wait_s"
LEASE_QUEUE_DEPTH = "ray_tpu_lease_queue_depth"
LEASES_HELD = "ray_tpu_leases_held"

# ------------------------------------------- multi-tenant arbitration (PR 15)
SCHED_PREEMPTIONS_TOTAL = "ray_tpu_sched_preemptions_total"
SCHED_PREEMPTION_VICTIMS_TOTAL = "ray_tpu_sched_preemption_victims_total"
SCHED_PREEMPTIONS_DENIED_TOTAL = "ray_tpu_sched_preemptions_denied_total"
SCHED_ADMISSION_QUEUED_TOTAL = "ray_tpu_sched_admission_queued_total"

# ------------------------------------------------------ podracer RL (PR 9)
RL_ENV_STEPS_TOTAL = "ray_tpu_rl_env_steps_total"
RL_LEARNER_UPDATES_TOTAL = "ray_tpu_rl_learner_updates_total"
RL_ENV_STEPS_PER_S = "ray_tpu_rl_env_steps_per_s"
RL_LEARNER_STEPS_PER_S = "ray_tpu_rl_learner_steps_per_s"
RL_PARAM_BROADCAST_BYTES_TOTAL = "ray_tpu_rl_param_broadcast_bytes_total"
RL_PARAM_STALENESS_HIST = "ray_tpu_rl_param_staleness"
RL_STALE_TRAJS_DROPPED_TOTAL = "ray_tpu_rl_stale_trajs_dropped_total"
RL_TRAJ_QUEUE_DEPTH = "ray_tpu_rl_traj_queue_depth"
RL_RUNNER_RESTARTS_TOTAL = "ray_tpu_rl_runner_restarts_total"

# -------------------------------------------- control-plane HA (PR 16)
CP_ROLE = "ray_tpu_cp_role"
CP_LEASE_EPOCH = "ray_tpu_cp_lease_epoch"
CP_FAILOVERS_TOTAL = "ray_tpu_cp_failovers_total"
CP_JOURNAL_RECORDS_TOTAL = "ray_tpu_cp_journal_records_total"
CP_JOURNAL_LAG_RECORDS = "ray_tpu_cp_journal_lag_records"

# ------------------------------------------------ elastic capacity (PR 20)
AUTOSCALER_LAUNCHES_TOTAL = "ray_tpu_autoscaler_launches_total"
AUTOSCALER_TERMINATIONS_TOTAL = "ray_tpu_autoscaler_terminations_total"
AUTOSCALER_DRAINS_TOTAL = "ray_tpu_autoscaler_drains_total"
AUTOSCALER_PENDING_DEMAND = "ray_tpu_autoscaler_pending_demand"
AUTOSCALER_DRAIN_DURATION_HIST = "ray_tpu_autoscaler_drain_duration_s"
TRAIN_ELASTIC_RESIZES_TOTAL = "ray_tpu_train_elastic_resizes_total"

# ------------------------------------------------- runtime self-diagnosis
EXCEPTION_SUPPRESSED_TOTAL = "ray_tpu_exception_suppressed_total"
DEBUG_LOCK_CYCLES_TOTAL = "ray_tpu_debug_lock_cycles_total"
DEBUG_LOCK_HELD_WAIT_HIST = "ray_tpu_debug_lock_held_blocked_wait_s"
DEBUG_LANE_VIOLATIONS_TOTAL = "ray_tpu_debug_lane_violations_total"

# Name -> one-line description.  ``raylint`` checks each key appears in
# docs/observability.md; ``registered_names()`` is the enumeration API.
METRICS: Dict[str, str] = {
    TASK_PHASE_HIST: "executor-side task phase durations (histogram)",
    BACKPRESSURE_WAIT_HIST: "submission backpressure block time (histogram)",
    BACKPRESSURE_BLOCKED_TOTAL: "submissions that blocked on the task-queue "
                                "memory cap",
    TASK_EVENTS_DROPPED_TOTAL: "task events lost to flush failure or "
                               "buffer shedding",
    TRACE_SPANS_DROPPED_TOTAL: "tracing spans shed from the task-event "
                               "profile channel (traces with drops are "
                               "flagged truncated)",
    SLO_VIOLATIONS_TOTAL: "SLO/anomaly rule findings, by rule "
                          "(straggler, bandwidth drift, restart storm, "
                          "queue pressure)",
    REMEDIATION_ACTIONS_TOTAL: "remediation-controller decisions, by "
                               "rule/action/outcome (applied, skipped, "
                               "failed, rate_limited, quarantined, "
                               "no_actuator)",
    REMEDIATION_QUARANTINED: "targets currently quarantined by the "
                             "remediation controller (gauge; nonzero "
                             "means a human is needed)",
    SERVE_TTFT_HIST: "serving time-to-first-result per deployment/"
                     "replica (histogram; full latency for unary "
                     "requests)",
    SERVE_INTER_TOKEN_HIST: "gap between consecutive streamed chunks "
                            "per deployment/replica (histogram)",
    SERVE_QUEUE_WAIT_HIST: "request wait for a replica user-concurrency "
                           "slot per deployment/replica (histogram)",
    SERVE_REQUESTS_TOTAL: "serving requests completed, by deployment/"
                          "outcome/streaming",
    SERVE_AUTOSCALE_EVENTS_TOTAL: "serve replica autoscale decisions, by "
                                  "deployment/direction (up, down, "
                                  "drain_retired, drain_forced)",
    SERVE_REPLICAS: "serve replicas per deployment — routable + still-"
                    "draining (gauge)",
    SERVE_MUX_CACHE_EVENTS_TOTAL: "multiplexed model-cache events on "
                                  "replicas, by event (hit, miss, "
                                  "eviction)",
    LLM_BATCH_OCCUPANCY: "engine slots that hold a request when a step "
                         "returns (gauge)",
    LLM_BATCH_BUCKET: "the engine's decode batch size, its slot count "
                      "(gauge)",
    LLM_QUEUE_DEPTH: "requests waiting for an engine slot (gauge)",
    LLM_DECODE_STEPS_TOTAL: "engine steps executed",
    LLM_ADMITTED_TOTAL: "requests admitted into the running batch at a "
                        "token boundary",
    LLM_RETIRED_TOTAL: "requests retired from the running batch at a "
                       "token boundary",
    LLM_PREFIX_CACHE_HITS_TOTAL: "requests routed to a replica that served "
                                 "their prefix before, by site (router)",
    LLM_PREFIX_CACHE_MISSES_TOTAL: "requests whose prefix no replica had "
                                   "served, by site",
    COLLECTIVE_OPS_TOTAL: "collective ops executed, by op/backend",
    COLLECTIVE_BYTES_TOTAL: "collective payload bytes, by op/backend",
    COLLECTIVE_DURATION_HIST: "collective op duration (histogram)",
    COLLECTIVE_BANDWIDTH_HIST: "achieved collective bandwidth (histogram)",
    ICI_SCALING_EFFICIENCY: "calibrated partition-retention ratio per mesh "
                            "size",
    COLLECTIVE_ALGO_OPS_TOTAL: "collective ops by selected algorithm, "
                               "size bucket, and topology (tuner "
                               "decisions)",
    COLLECTIVE_TUNER_EXPLORATIONS_TOTAL: "tuner selections that probed a "
                                         "non-committed algorithm",
    COLLECTIVE_TUNER_COMMITS_TOTAL: "tuner (re)commits to a bucket's "
                                    "measured-best algorithm",
    COLLECTIVE_TUNER_BEST_BANDWIDTH: "mean achieved bandwidth of the "
                                     "committed algorithm per bucket "
                                     "(gauge)",
    COLLECTIVE_QUANTIZED_OPS_TOTAL: "block-quantized allreduce ops "
                                    "executed (opt-in)",
    COLLECTIVE_QUANTIZED_BYTES_SAVED_TOTAL: "logical minus wire bytes for "
                                            "quantized exchanges (int8 "
                                            "payload + per-block scales)",
    OBJECT_STORE_FULL_ERRORS_TOTAL: "ObjectStoreFullError occurrences",
    OBJECT_STORE_SPILL_BYTES_TOTAL: "bytes ever written to the spill tier",
    OBJECT_STORE_SPILL_RECLAIMED_TOTAL: "spill-tier bytes reclaimed by "
                                        "refcount frees",
    OBJECT_STORE_LRU_EVICTIONS_TOTAL: "sealed objects LRU-evicted from the "
                                      "arena",
    OBJECT_STORE_USED_BYTES: "arena bytes in use (gauge)",
    OBJECT_STORE_CAPACITY_BYTES: "arena capacity (gauge)",
    OBJECT_STORE_NUM_OBJECTS: "sealed objects resident in the arena (gauge)",
    OBJECT_STORE_SPILL_TIER_BYTES: "bytes currently on the disk spill tier "
                                   "(gauge)",
    OBJECT_STORE_SPILL_TIER_OBJECTS: "objects currently on the disk spill "
                                     "tier (gauge)",
    GET_BATCH_CALLS_TOTAL: "vectorized get_object_batch owner RPCs issued",
    GET_BATCH_REFS_TOTAL: "borrowed refs resolved through batched owner "
                          "calls",
    LOCATION_CACHE_HITS_TOTAL: "borrowed gets served from the owner-"
                               "location cache (no owner round-trip)",
    LOCATION_CACHE_MISSES_TOTAL: "borrowed gets that consulted the owner "
                                 "for locations",
    LOCATION_CACHE_INVALIDATIONS_TOTAL: "location-cache entries dropped on "
                                        "fetch failure or owner pruning",
    RPC_OOB_FRAMES_TOTAL: "RPC frames written with out-of-band buffer "
                          "segments (framing v2)",
    RPC_OOB_BYTES_TOTAL: "payload bytes that skipped the frame pickle "
                         "stream (framing v2)",
    RPC_BATCH_FRAMES_TOTAL: "batch container frames written",
    RPC_BATCHED_CALLS_TOTAL: "calls multiplexed into batch containers",
    DATA_QUEUE_DEPTH: "blocks parked in a streaming op's input queue "
                      "(gauge, by op)",
    DATA_STRAGGLER_WAIT_HIST: "scheduler time blocked waiting for ANY "
                              "in-flight block to complete (histogram)",
    DATA_AUTOSCALE_EVENTS_TOTAL: "actor-pool autoscale decisions, by "
                                 "op/direction",
    DATA_POOL_SIZE: "target size of an autoscaling pool op — actor "
                    "handles held, creation is async (gauge, by op)",
    DATA_BLOCKS_SPLIT_TOTAL: "oversized map outputs split by dynamic "
                             "block shaping",
    DATA_BLOCKS_COALESCED_TOTAL: "undersized blocks merged by dynamic "
                                 "block shaping",
    DATA_BLOCKS_EMITTED_TOTAL: "blocks emitted downstream by streaming "
                               "ops, by op",
    TASKS_CANCELLED_TOTAL: "cancel requests accepted owner-side via "
                           "ray_tpu.cancel (best-effort; an executing "
                           "task still completes)",
    RPC_LANE_FRAMES_TOTAL: "frames dispatched per RPC service lane, by "
                           "role/lane",
    RPC_LANE_FORWARDED_TOTAL: "lane frames forwarded to the primary loop "
                              "(non-lane-safe handlers + slow-path punts)",
    RPC_LANE_CONNECTIONS: "connections currently pinned to a lane (gauge)",
    RPC_LANE_QUEUE_DEPTH: "frames read but not yet fully handled on a "
                          "lane (gauge)",
    RPC_LANE_DISPATCH_WAIT_HIST: "frame-read to handler-start latency per "
                                 "lane (histogram; one window-mean sample "
                                 "per metrics flush)",
    OWNER_SHARD_LOOKUPS_TOTAL: "owner-table shard lookups (all shards "
                               "summed)",
    OWNER_SHARD_FAST_ENTRIES_TOTAL: "owner get/probe entries served by the "
                                    "lock-free READY fast path (any lane)",
    OWNER_SHARD_FORWARDED_ENTRIES_TOTAL: "owner get entries that needed the "
                                         "primary loop (unset event, loss "
                                         "report, reconstruction)",
    OWNER_SHARD_OBJECTS_MAX: "objects in the largest owner-table shard "
                             "(gauge; balance indicator)",
    PG_COMMIT_BATCHES_TOTAL: "placement-group group-commit sweeps executed",
    PG_COMMIT_BATCHED_GROUPS_TOTAL: "PG create/remove ops that shared a "
                                    "sweep with at least one other op",
    PG_COMMIT_FUSED_TOTAL: "single-node PGs committed via the fused "
                           "prepare+commit agent RPC",
    PG_COMMIT_ROLLBACKS_TOTAL: "whole-group rollbacks after a partial "
                               "bundle-reservation failure",
    PIPELINE_STAGE_FWD_HIST: "pipeline-stage forward-op duration, by stage "
                             "(histogram)",
    PIPELINE_STAGE_BWD_HIST: "pipeline-stage backward-op duration, by stage "
                             "(histogram)",
    PIPELINE_STAGE_STALL_HIST: "per-step time a stage spent blocked waiting "
                               "for a neighbor's tensor (histogram)",
    PIPELINE_BUBBLE_FRACTION: "measured pipeline bubble: stall over wall "
                              "per step (gauge, overall + by stage)",
    PIPELINE_ACTIVATION_BYTES_TOTAL: "bytes streamed between adjacent "
                                     "pipeline stages (activations + grads)",
    PIPELINE_ACTIVATION_BANDWIDTH_HIST: "achieved per-push inter-stage "
                                        "transfer bandwidth (histogram)",
    PIPELINE_MICROBATCHES_TOTAL: "microbatches executed by pipeline stages "
                                 "(forward+backward pairs)",
    PIPELINE_STAGE_RESTARTS_TOTAL: "stage actors restarted from the last "
                                   "synchronized checkpoint",
    RL_ENV_STEPS_TOTAL: "environment transitions generated, by arch "
                        "(anakin/sebulba/impala)",
    RL_LEARNER_UPDATES_TOTAL: "learner gradient updates applied, by arch",
    RL_ENV_STEPS_PER_S: "rollout throughput of the last measured window "
                        "(gauge, by arch/devices)",
    RL_LEARNER_STEPS_PER_S: "learner update throughput of the last "
                            "measured window (gauge, by arch)",
    RL_PARAM_BROADCAST_BYTES_TOTAL: "serialized-once parameter bytes fanned "
                                    "out to env runners (wire bytes x "
                                    "fan-out)",
    RL_PARAM_STALENESS_HIST: "behavior-policy staleness in learner versions "
                             "at consume time (histogram)",
    RL_STALE_TRAJS_DROPPED_TOTAL: "trajectories discarded for exceeding "
                                  "the staleness bound",
    RL_TRAJ_QUEUE_DEPTH: "trajectories parked in the learner's inbound "
                         "queue (gauge)",
    RL_RUNNER_RESTARTS_TOTAL: "env-runner actors killed and respawned by "
                              "the actor manager, by group",
    LEASE_GRANT_WAIT_HIST: "lease request wait until grant/spillback/retry "
                           "(histogram)",
    LEASE_QUEUE_DEPTH: "lease requests parked on the node agent (gauge)",
    LEASES_HELD: "leases currently held by the node agent (gauge)",
    SCHED_PREEMPTIONS_TOTAL: "checkpoint-then-evict preemption events "
                             "(one per victim placement group)",
    SCHED_PREEMPTION_VICTIMS_TOTAL: "placement groups evicted as "
                                    "preemption victims, by victim "
                                    "priority",
    SCHED_PREEMPTIONS_DENIED_TOTAL: "preemption attempts denied by the "
                                    "per-job token-bucket budget or "
                                    "quarantine",
    SCHED_ADMISSION_QUEUED_TOTAL: "requests queued (not failed) by "
                                  "per-job quota admission, by job",
    EXCEPTION_SUPPRESSED_TOTAL: "intentionally suppressed exceptions, by "
                                "site (RTL003 accounting)",
    DEBUG_LOCK_CYCLES_TOTAL: "lock-order cycles detected by DebugLock "
                             "(potential deadlocks)",
    DEBUG_LOCK_HELD_WAIT_HIST: "time blocked acquiring a lock while already "
                               "holding another (histogram)",
    DEBUG_LANE_VIOLATIONS_TOTAL: "cross-lane mutations caught by the "
                                 "RAY_TPU_DEBUG_LANES checker (RTL007's "
                                 "dynamic twin)",
    CP_ROLE: "control-plane role of this process (gauge: 1 = leader, "
             "0 = standby)",
    CP_LEASE_EPOCH: "current leader-lease fencing epoch (gauge)",
    CP_FAILOVERS_TOTAL: "leader-lease epoch bumps observed beyond the "
                        "first election (each is one failover)",
    CP_JOURNAL_RECORDS_TOTAL: "control-plane journal records appended by "
                              "this leader",
    CP_JOURNAL_LAG_RECORDS: "worst standby replication lag in journal "
                            "records (gauge; leader-side view)",
    AUTOSCALER_LAUNCHES_TOTAL: "autoscaler node launches, by node type and "
                               "outcome (ok, error, backoff)",
    AUTOSCALER_TERMINATIONS_TOTAL: "autoscaler node terminations, by "
                                   "outcome (drained, timeout, direct, "
                                   "reclaimed, error)",
    AUTOSCALER_DRAINS_TOTAL: "drain state machines started/resolved, by "
                             "outcome (started, drained, timeout, "
                             "cancelled)",
    AUTOSCALER_PENDING_DEMAND: "unmet resource demands feeding the "
                               "scaling decision this round (gauge)",
    AUTOSCALER_DRAIN_DURATION_HIST: "mark-unschedulable to provider-"
                                    "terminate wall time per drained node "
                                    "(histogram)",
    TRAIN_ELASTIC_RESIZES_TOTAL: "elastic-trainer world-size crossovers, "
                                 "by direction (grow, shrink)",
}


def registered_names() -> frozenset:
    return frozenset(METRICS)


def is_registered(name: str) -> bool:
    return name in METRICS
