"""Job kind ``train_dp``: a model through ``JaxTrainer``, data parallel over
the gang's chips.  ``train_loop`` runs in every worker (it travels there by
reference: the checkout's root is on the workers' ``PYTHONPATH``); ``run`` is
the driver's side.  The model's pieces (config class, init, loss, plain
reference, operation count) come from ``families/<family>.py``, named by
the configuration's ``family`` key.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import statistics
import time

# |program loss - float32 reference loss| / reference loss, two rows of the
# first batch.  The program computes in bf16, but the loss is a mean over
# 2 x 1024 targets of a value near ln(50257) = 10.8, so the roundings average
# out: measured 3e-6 .. 6e-5 on the chip (24 runs, PR 24).  8-bit arithmetic
# or a dropped layer moves the loss by more than 1e-3.
LOSS_RTOL = 5e-4
TRACE_SECONDS = 4.0  # of the steady window, not the whole of it
TRACE_FROM_STEP = 2


def split_setup(t_start: float, t_fit: float, t_enter: float,
                t_window: float) -> dict:
    """The start of a training run, in two numbers (wall clock, one host).
    ``gang_ready_s``: ``fit()`` called -> the first line of rank 0's loop:
    placement, the workers' processes, ``jax.distributed`` and the chips'
    runtimes coming up, which identical code moves by +-7 s on four chips
    (PERF.md PR 38).  ``setup_s``: everything else from ``run.py``'s start
    to the window's first step (cluster, imports, state, compile from the
    cache, reference, warm steps), which repeats.  The two add up to what
    ``setup_s`` was until PR 38."""
    gang_ready_s = t_enter - t_fit
    return {"gang_ready_s": gang_ready_s,
            "setup_s": (t_window - t_start) - gang_ready_s}


def train_loop(config: dict) -> None:
    t_enter = time.time()
    _last = [time.perf_counter()]

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import ray_tpu.train as train
    from benchmarks.lib.device import device_memory
    from ray_tpu.util.tracing import start_profile, stop_profile

    fam = importlib.import_module("benchmarks.families." + config["family"])
    phases, memory_at = {}, {}

    def phase(name):  # where set-up goes, on an earlier line of the output
        now = time.perf_counter()
        phases[name] = now - _last[0]
        _last[0] = now
        if name != "imports":  # the counters as they stand after each phase
            memory_at[name] = device_memory()

    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **kw):
        key = name.rsplit("/", 1)[-1]
        if key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    phase("imports")
    ctx = train.get_context()
    rank, world = ctx.world_rank, ctx.world_size
    cfg, per_worker = config["model"], config["chips_per_worker"]
    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    devices = [d for p in sorted(by_proc) for d in by_proc[p][:per_worker]]
    if len(devices) != per_worker * world:
        raise RuntimeError(
            f"{len(devices)} devices, gang has {per_worker * world}")
    if devices[0].platform != config["platform"]:
        raise RuntimeError(f"worker's jax came up on {devices[0].platform}")
    phase("backend_up")
    mesh = Mesh(np.array(devices), ("data",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    B, S, seed = config["batch"], config["seq"], config["seed"]
    rows_here = B // world
    rng = np.random.default_rng([seed, rank])
    targets_below = config["target_vocab"]  # padding rows are never a target

    def next_inputs(stop: bool):
        """The input pipeline: a fresh batch drawn on the host, put on this
        process's chips; and this process's vote on whether to stop."""
        rows = rng.integers(0, targets_below, (rows_here, S + 1), dtype=np.int32)
        tokens = jax.make_array_from_process_local_data(split, rows)
        votes = jax.make_array_from_process_local_data(
            split, np.full((per_worker,), int(stop), np.int32))
        return rows, tokens, votes

    # The key is an argument: closed over, the seed is a constant of the
    # program and every new seed compiles it anew (17 s; PR 24).
    params = jax.jit(
        lambda key: fam.init(key, cfg), out_shardings=whole
    )(jax.random.PRNGKey(seed))
    tx = optax.adamw(config["learning_rate"])
    opt_state = jax.jit(tx.init, out_shardings=whole)(params)

    jax.block_until_ready(opt_state)
    phase("init_state")

    def shard_grads(p, tok, votes):
        loss, grads = jax.value_and_grad(lambda q: fam.loss(q, tok, cfg))(p)
        return (jax.lax.pmean(loss, "data"), jax.lax.pmean(grads, "data"),
                jax.lax.pmax(votes.max(), "data"))

    def step(p, o, tok, votes):
        loss, grads, stop = jax.shard_map(
            shard_grads, mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P(), P()), check_vma=False)(p, tok, votes)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss, stop

    rows0, tokens, votes = next_inputs(False)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens, votes).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    phase("compile_step")

    # Correctness, outside the window: the program's loss against the plain
    # float32 reference, same weights, the first rows of the first batch.
    # Rank 0 alone: the weights are replicated, and only process 0 may write
    # the compile cache, so the other ranks would compile both programs anew
    # in every run (93 s against 7 s, PR 24).
    prog_loss = ref_loss = None
    if rank == 0:
        local = jax.tree.map(lambda a: a.addressable_data(0), params)
        ref_rows = jnp.asarray(rows0[: config["reference_rows"]])
        prog_loss = float(
            jax.jit(lambda p, t: fam.loss(p, t, cfg))(local, ref_rows))
        ref_loss = float(jax.jit(
            lambda p, t: fam.reference_loss(p, t, cfg))(local, ref_rows))
        del local
    phase("reference_check")

    losses, step_ms, input_ms = [], [], []
    for _ in range(config["warmup_steps"]):
        params, opt_state, loss, _stop = compiled(
            params, opt_state, tokens, votes)
        losses.append(float(loss))
        _rows, tokens, votes = next_inputs(False)
    t0 = time.perf_counter()
    params, opt_state, loss, _stop = compiled(params, opt_state, tokens, votes)
    losses.append(float(loss))
    warm_step_s = time.perf_counter() - t0
    n_warm = len(losses)
    phase("warm_steps")

    trace_dir = config.get("trace_dir")
    trace_steps = max(3, round(TRACE_SECONDS / warm_step_s)) if trace_dir else 0
    tracing = False
    t_window_wall = time.time()
    t_window = time.perf_counter()
    n = 0
    while True:
        if trace_dir and n == TRACE_FROM_STEP:
            start_profile(os.path.join(trace_dir, f"rank{rank}"))
            tracing = True
        t0 = time.perf_counter()
        stop_vote = t0 - t_window >= config["seconds"]
        _rows, tokens, votes = next_inputs(stop_vote)
        t1 = time.perf_counter()
        params, opt_state, loss, stop = compiled(
            params, opt_state, tokens, votes)
        loss.block_until_ready()
        t2 = time.perf_counter()
        loss_f, stop_now = float(loss), bool(int(stop))
        train.report({"step": n, "loss": loss_f})
        n += 1
        losses.append(loss_f)
        input_ms.append((t1 - t0) * 1e3)
        step_ms.append((t2 - t1) * 1e3)
        if tracing and (n == TRACE_FROM_STEP + trace_steps or stop_now):
            stop_profile()
            tracing = False
        if stop_now:
            break
    elapsed = time.perf_counter() - t_window

    memory_at["end"] = device_memory()
    out = {
        "rank": rank, "world": world, "t_enter": t_enter,
        "t_window_wall": t_window_wall, "elapsed_s": elapsed, "steps": n,
        "tokens_per_step": B * S, "losses": losses, "n_warm": n_warm,
        "step_ms": step_ms, "input_ms": input_ms,
        "compile_s": compile_s, "prog_loss": prog_loss, "ref_loss": ref_loss,
        "pallas_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce"),
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "devices": len(devices), "processes": jax.process_count(),
        "memory_stats": memory_at["end"], "memory_at": memory_at,
        "program_bytes": {
            "arguments": int(mem.argument_size_in_bytes),
            "temporaries": int(mem.temp_size_in_bytes),
            "outputs": int(mem.output_size_in_bytes),
            "aliased": int(mem.alias_size_in_bytes)} if mem else None,
        "traced_steps": trace_steps, "phases": phases, **cache_events,
    }
    with open(os.path.join(config["out_dir"], f"rank{rank}.json"), "w") as f:
        json.dump(out, f)


def run(job) -> dict:
    """Driver's side.  ``job``: see run.py's ``Job``."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    from ..lib.cluster import check, log
    from ..lib.device import measured_peak

    mix, cfg = job.mix, job.config
    fam = importlib.import_module("benchmarks.families." + cfg["family"])
    size = dict(mix, **mix["tiny"]) if job.rehearse else mix
    model = cfg["tiny"] if job.rehearse else cfg["model"]
    workers, per_worker = mix["num_workers"], mix["chips_per_worker"]
    check(workers * per_worker == job.chips,
          f"mix needs {workers * per_worker} chips, cell says {job.chips}")
    # The config object, not its dict: unpickling it imports jax in the worker
    # before the loop starts.  A first ``import jax`` inside the loop races
    # with the worker's lease check (core/tpu_detect.leased_platform_verified
    # imports jax._src.xla_bridge from another thread while jax is half
    # imported) and fails on the chip every time; see PERF.md.
    loop_config = dict(
        family=cfg["family"], model=fam.config(model),
        batch=size["global_batch"], seq=size["seq"],
        seed=job.seed, seconds=job.seconds,
        learning_rate=mix["learning_rate"], warmup_steps=mix["warmup_steps"],
        reference_rows=mix["reference_rows"], chips_per_worker=per_worker,
        target_vocab=min(model["vocab_size"],
                         cfg["published"]["vocab_size"]),
        platform="cpu" if job.rehearse else "tpu", out_dir=job.out_dir,
        trace_dir=job.trace_dir)
    t_fit = time.time()
    result = JaxTrainer(
        train_loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(
            num_workers=workers,
            resources_per_worker={"CPU": 1, "TPU": per_worker}),
    ).fit()
    if result.error is not None:
        raise RuntimeError("training gang failed") from result.error
    ranks = []
    for r in range(workers):
        with open(os.path.join(job.out_dir, f"rank{r}.json")) as f:
            ranks.append(json.load(f))
    r0 = ranks[0]
    reported = [m for m in (result.metrics_history or []) if "step" in m]
    measured = r0["losses"][r0["n_warm"]:]
    bad = [x for x in r0["losses"] if not math.isfinite(x)]
    rel = abs(r0["prog_loss"] - r0["ref_loss"]) / abs(r0["ref_loss"])
    log(f"train: {r0['devices']} x {r0['kind']} in {r0['processes']} "
        f"process(es); compile {r0['compile_s']:.1f}s (cache hits "
        f"{r0['cache_hits']}, misses {r0['cache_misses']}); {r0['steps']} "
        f"steps in {r0['elapsed_s']:.3f}s; losses {r0['losses'][:3]} .. "
        f"{r0['losses'][-1]}; program loss {r0['prog_loss']:.6f} vs "
        f"float32 reference {r0['ref_loss']:.6f} (rel {rel:.2e}); pallas "
        f"calls {r0['pallas_calls']}, all-reduces {r0['all_reduces']}")
    log(f"train: program bytes by the compiler {r0['program_bytes']}; "
        "device counters after each phase (in use / reserved / peak in use "
        "/ peak reserved, MB): " + json.dumps({
            k: [v.get(c, 0) // 10 ** 6 for c in (
                "bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
                "peak_bytes_reserved")]
            for k, v in r0["memory_at"].items()}))
    for r in ranks:
        log(f"train: rank {r['rank']} entered the loop "
            f"{r['t_enter'] - t_fit:.1f}s after fit(); set-up phases (s) "
            + json.dumps({k: round(v, 2) for k, v in r["phases"].items()}))
    problems = []
    if bad:
        problems.append(f"{len(bad)} losses not finite")
    if rel > LOSS_RTOL:
        problems.append(f"loss off the reference by {rel:.2e} > {LOSS_RTOL}")
    if len(reported) != r0["steps"]:
        problems.append(f"{len(reported)} steps reported through "
                        f"train.report, {r0['steps']} run")
    if r0["devices"] != job.chips or r0["processes"] != workers:
        problems.append(f"{r0['devices']} devices in {r0['processes']} "
                        f"processes, wanted {job.chips} in {workers}")
    if not job.rehearse and r0["pallas_calls"] < 3:
        problems.append(f"flash attention fell back: {r0['pallas_calls']} "
                        "tpu_custom_call(s) in the step")
    if job.chips > 1 and r0["all_reduces"] < 1:
        problems.append("no all-reduce in the compiled step")
    tokens_per_s = r0["steps"] * r0["tokens_per_step"] / r0["elapsed_s"]
    start = split_setup(job.t_start_wall, t_fit, r0["t_enter"],
                        r0["t_window_wall"])
    peak = max(measured_peak(r["memory_stats"]) for r in ranks)
    per_token = fam.train_flops_per_token(model, size["seq"])
    return {
        "problems": problems,
        "attempted": r0["steps"],
        "failed": len([x for x in measured if not math.isfinite(x)]),
        "end_to_end": {
            "train_tokens_per_s": tokens_per_s, **start,
        },
        "device": {"platform": r0["platform"], "kind": r0["kind"],
                   "count": r0["devices"], "memory_peak_bytes": peak},
        # What the per-layer readers may read (host clock, benchmark's own).
        "stats": {
            "gang_ready_s": start["gang_ready_s"],
            "step_ms": r0["step_ms"], "input_ms": r0["input_ms"],
            "steps": r0["steps"],
            "traced_steps": r0["traced_steps"],
            "rows_per_chip": size["global_batch"] // job.chips,
            "seq": size["seq"], "model": model,
            "flops_per_token": per_token,
        },
        "compared": {"loss_rel_err": [rel, LOSS_RTOL]},
        "notes": {
            "gang_ready_s": start["gang_ready_s"],
            "ranks_entered_s": [r["t_enter"] - t_fit for r in ranks],
            "fit_called_s": t_fit - job.t_start_wall,
            "phases_rank0": r0["phases"],
            "median_step_ms": statistics.median(r0["step_ms"]),
            "flops_per_token": per_token,
            "model_flops_per_s_per_chip": tokens_per_s * per_token / job.chips,
            "loss_rel_err": rel, "compile_s": r0["compile_s"],
            "cache_hits": r0["cache_hits"], "cache_misses": r0["cache_misses"],
            "program_bytes": r0["program_bytes"],
            "memory_stats": r0["memory_stats"],
        },
    }
