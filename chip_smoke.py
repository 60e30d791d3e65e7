#!/usr/bin/env python3
"""chip_smoke.py — ray_tpu's lease -> worker -> JAX path on the TPU, once.

The quickest proof that the system still starts on the chip.  It drives the
entry points a user calls, at the full width of models the repo supports
(weights random, made from ``--seed``):

  core   ``ray_tpu.init()`` detects the chip and registers ``TPU``; a
         ``num_tpus=1`` actor's jax comes up on it; while it holds the chip
         a ``num_tpus=0`` task imports jax and computes on the CPU.
  serve  TinyLlama-1.1B (bf16, 22 layers, d 2048, vocab 32000) behind
         ``serve.run(build_openai_app(cfg, num_tpus=1))`` and the HTTP
         proxy: completions of three prompt lengths, a repeat, a stream.
  train  GPT-2 small (flash attention + remat, B=32, S=1024) through
         ``JaxTrainer``: five AdamW steps; the compiled step must contain
         the Pallas kernels.

``--chips 4`` (one four-chip host) runs only what exists across chips:
four one-chip leases at once, the train step data-parallel in two layouts
against a one-chip run of the same seed, and prefill / decode on separate
chips against the one-chip server.

This process never initialises a jax backend — a chip belongs to one
process, and here that is always a worker that holds the lease.  Each phase
fails the script on its first error.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, with the device as the workers'
jax reported it.  ``--rehearse-cpu`` walks the same code at tiny widths on
CPU workers; it is chosen only by that flag and prints ``rehearsal_ok``,
never ``ok``.
"""

from __future__ import annotations

import argparse
import ctypes
import glob
import json
import os
import signal
import sys
import time
import traceback
import urllib.request

LEASE_WAIT_S = 120  # an undetected / ungrantable chip is a failure, not a hang
REQUEST_WAIT_S = 900  # first request: engine build + cold compiles
EXIT_WAIT_S = 30  # after shutdown(): nothing this script started is left
TRAIN_STEPS = 5
# Per-step loss of a four-chip run against the one-chip run of the same
# seed (relative): same batch, same init; only the order of the gradient
# sum differs, in bf16, through five Adam steps.
LOSS_RTOL = 0.02


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ sizes
def sizes(rehearse: bool) -> dict:
    from ray_tpu.models import GPT2Config, LlamaConfig

    if rehearse:
        return dict(
            platform="cpu",
            llm=LlamaConfig.tiny(), llm_name="llama-tiny-random",
            max_batch=4, max_seq_len=128, max_tokens=8,
            prompt_lens=(5, 40, 100),
            gpt2=GPT2Config.tiny(attention="flash", remat=True),
            batch=8, seq=64,
        )
    return dict(
        platform="tpu",
        llm=LlamaConfig.tinyllama_1b(), llm_name="tinyllama-1.1b-random",
        max_batch=8, max_seq_len=2048, max_tokens=32,
        prompt_lens=(16, 300, 1500),
        gpt2=GPT2Config.small(attention="flash", remat=True),
        batch=32, seq=1024,
    )


def prompts_of(lens) -> list:
    text = "the quick brown fox jumps over the lazy dog; "
    return [(text * (n // len(text) + 1))[:n] for n in lens]


# ------------------------------------------------------------- the cluster
def start_cluster(chips: int, rehearse: bool) -> None:
    import ray_tpu
    from ray_tpu.core import native, tpu_detect

    found = tpu_detect.num_local_chips()
    log(f"chips detected from device files: {found}")
    if rehearse:
        ray_tpu.init(num_cpus=8, resources={"TPU": chips})
    else:
        check(found > 0, "no TPU chip detected (/dev/accel*, /dev/vfio/N)")
        ray_tpu.init()  # resources auto-detected
    total = ray_tpu.cluster_resources().get("TPU", 0)
    check(total == chips,
          f"node registered TPU={total}, this run needs TPU={chips}")
    log("native data plane: " + (
        "librtpu_native.so loaded" if native.available()
        else "NOT built/loaded - python fallbacks in use"))
    log(f"compile cache: {os.environ['JAX_COMPILATION_CACHE_DIR']}")


def wait_chips_free(chips: int) -> None:
    """The previous phase's workers are gone (a lease is returned only
    after its worker process exited) before the next one asks."""
    import ray_tpu

    deadline = time.monotonic() + LEASE_WAIT_S
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        check(time.monotonic() < deadline,
              f"TPU not released within {LEASE_WAIT_S}s: "
              f"{ray_tpu.available_resources()}")
        time.sleep(0.5)


def print_worker_logs() -> None:
    """On failure: the session's logs live under the temp dir and vanish
    with the machine."""
    from ray_tpu import api

    node = api._local_node
    if node is None:
        return
    paths = sorted(glob.glob(os.path.join(node.log_dir, "*.log")),
                   key=os.path.getmtime)[-8:]
    for path in paths:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - 3000))
            tail = f.read().decode("utf-8", "replace")
        print(f"----- tail of {path}\n{tail}", flush=True)


# -------------------------------------------------------------- phase: core
def phase_core(sz: dict, chips: int) -> dict:
    """``chips`` one-chip leases alive at once, each on its own chip; a
    chipless task computes on the CPU meanwhile."""
    import ray_tpu
    from ray_tpu.core import tpu_detect

    @ray_tpu.remote(num_tpus=1)
    class ChipProbe:
        def devices(self):
            import jax

            ds = jax.devices()
            return {
                "platform": ds[0].platform, "kind": ds[0].device_kind,
                "count": len(ds), "pid": os.getpid(),
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            }

    actors = [ChipProbe.remote() for _ in range(chips)]
    infos = ray_tpu.get([a.devices.remote() for a in actors],
                        timeout=LEASE_WAIT_S)
    for info in infos:
        log(f"num_tpus=1 actor: {info}")
        check(info["platform"] == sz["platform"],
              f"chip-lease worker came up on {info['platform']}")
    if sz["platform"] == "tpu":
        check(all(i["count"] == 1 for i in infos),
              "a one-chip lease must see exactly one device")
        check(tpu_detect.num_local_chips() == chips,
              "chips detected from device files != chips jax was given")
    leased = sorted(i["visible_chips"] for i in infos)
    check(len(set(leased)) == chips, f"leases share a chip: {leased}")
    check(len({i["pid"] for i in infos}) == chips, "leases share a process")

    @ray_tpu.remote(num_tpus=0)
    def chipless():
        import jax
        import jax.numpy as jnp

        return jax.devices()[0].platform, float(jnp.arange(8.0).sum())

    platform, total = ray_tpu.get(chipless.remote(), timeout=LEASE_WAIT_S)
    log(f"num_tpus=0 task while the chip is held: jax on {platform}")
    check(platform == "cpu" and total == 28.0,
          f"chipless worker: platform {platform}, sum {total}")
    for a in actors:
        ray_tpu.kill(a)
    return {"platform": infos[0]["platform"], "kind": infos[0]["kind"],
            "count": infos[0]["count"]}


# ------------------------------------------------------------- phase: serve
def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=REQUEST_WAIT_S) as resp:
        out = json.loads(resp.read())
    check("result" in out, f"server error: {out}")
    return out["result"]


def _post_stream(url: str, body: dict) -> str:
    req = urllib.request.Request(
        url, json.dumps(dict(body, stream=True)).encode(),
        {"Content-Type": "application/json"})
    text, done = "", False
    with urllib.request.urlopen(req, timeout=REQUEST_WAIT_S) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            chunk = json.loads(line[len("data: "):])
            check("error" not in chunk, f"stream error: {chunk}")
            text += chunk["choices"][0]["text"]
    check(done, "stream ended without [DONE]")
    return text


def serve_and_ask(sz: dict, app, label: str, device_of=None) -> list:
    """Run ``app`` behind the HTTP proxy, ask the smoke's completions, check
    them, tear the app down.  Returns the greedy texts, one per prompt."""
    from ray_tpu import serve
    from ray_tpu.core.rpc import find_free_port
    from ray_tpu.llm import ByteTokenizer

    t0 = time.perf_counter()
    handle = serve.run(app)
    base = serve.start_http_proxy(
        port=find_free_port(), request_timeout_s=REQUEST_WAIT_S)
    url = base + "/v1/completions"
    tok, asked = ByteTokenizer(), sz["max_tokens"]
    prompts = prompts_of(sz["prompt_lens"])
    texts = []
    for i, prompt in enumerate(prompts + prompts[:1]):
        t1 = time.perf_counter()
        out = _post(url, {"prompt": prompt, "max_tokens": asked})
        usage, text = out["usage"], out["choices"][0]["text"]
        log(f"{label}: prompt of {len(prompt)} bytes -> "
            f"{usage['completion_tokens']} tokens in "
            f"{time.perf_counter() - t1:.1f}s"
            + (" (replica build + cold compiles included)" if i == 0 else ""))
        check(1 <= usage["completion_tokens"] <= asked,
              f"asked for {asked} tokens, usage says {usage}")
        check(usage["prompt_tokens"] == len(tok.encode(prompt))
              and usage["total_tokens"]
              == usage["prompt_tokens"] + usage["completion_tokens"],
              f"usage does not add up: {usage}")
        texts.append(text)
    check(texts[-1] == texts[0], "the same greedy prompt gave two texts")
    streamed = _post_stream(url, {"prompt": prompts[1], "max_tokens": asked})
    check(streamed == texts[1],
          f"streamed text {streamed!r} != unary text {texts[1]!r}")
    if device_of is not None:
        info = device_of(handle)
        log(f"{label}: replica on {info}")
        check(info["platform"] == sz["platform"],
              f"replica's jax came up on {info['platform']}")
    serve.shutdown()
    log(f"{label}: phase took {time.perf_counter() - t0:.1f}s")
    return texts[:-1]


def engine_cfg(sz: dict, seed: int, max_batch=None):
    from ray_tpu.llm import EngineConfig

    return EngineConfig(
        model=sz["llm"], max_batch_size=max_batch or sz["max_batch"],
        max_seq_len=sz["max_seq_len"], seed=seed)


def phase_serve(sz: dict, seed: int, max_batch=None) -> list:
    from ray_tpu.llm import build_openai_app

    app = build_openai_app(
        engine_cfg(sz, seed, max_batch), model_name=sz["llm_name"], num_tpus=1)
    return serve_and_ask(
        sz, app, "serve",
        device_of=lambda h: h.device_info.remote().result(timeout=60))


def phase_serve_disagg(sz: dict, seed: int, max_batch: int) -> list:
    from ray_tpu.llm import build_disagg_openai_app

    app = build_disagg_openai_app(
        engine_cfg(sz, seed, max_batch), model_name=sz["llm_name"], num_tpus=1)
    return serve_and_ask(sz, app, "serve-disagg")


# ------------------------------------------------------------- phase: train
def train_loop(config: dict) -> None:
    """The user's training loop: GPT-2, data-parallel over every chip of
    the gang (one chip: a mesh of one), one fixed batch, AdamW."""
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import ray_tpu.train as train
    from ray_tpu.models import gpt2_init, gpt2_loss

    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **kw):
        key = name.rsplit("/", 1)[-1]
        if key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    cfg, per_worker = config["gpt2"], config["chips_per_worker"]
    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    devices = [d for p in sorted(by_proc) for d in by_proc[p][:per_worker]]
    want = per_worker * train.get_context().world_size
    assert len(devices) == want, f"{len(devices)} devices, gang has {want}"
    assert devices[0].platform == config["platform"], devices[0].platform
    mesh = Mesh(np.array(devices), ("data",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    B, S, seed = config["batch"], config["seq"], config["seed"]
    batch = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, S + 1), dtype=np.int32)
    tokens = jax.make_array_from_callback(
        batch.shape, split, lambda idx: batch[idx])
    params = jax.jit(
        lambda: gpt2_init(jax.random.PRNGKey(seed), cfg), out_shardings=whole
    )()
    tx = optax.adamw(1e-4)
    opt_state = jax.jit(tx.init, out_shardings=whole)(params)

    def shard_grads(p, tok):
        loss, grads = jax.value_and_grad(lambda q: gpt2_loss(q, tok, cfg))(p)
        return jax.lax.pmean(loss, "data"), jax.lax.pmean(grads, "data")

    def step(p, o, tok):
        loss, grads = jax.shard_map(
            shard_grads, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=(P(), P()), check_vma=False)(p, tok)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()

    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    stats = jax.local_devices()[0].memory_stats() or {}
    train.report({
        "losses": losses, "step_s": step_s,
        "compile_s": compile_s,
        "pallas_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce"),
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "devices": len(devices), "processes": jax.process_count(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "tokens_per_step": B * S, **cache_events,
    })


def phase_train(sz: dict, seed: int, num_workers: int,
                chips_per_worker: int) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    label = f"train[{num_workers} worker x {chips_per_worker} chip]"
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config=dict(
            gpt2=sz["gpt2"], batch=sz["batch"], seq=sz["seq"], seed=seed,
            steps=TRAIN_STEPS, chips_per_worker=chips_per_worker,
            platform=sz["platform"]),
        scaling_config=ScalingConfig(
            num_workers=num_workers,
            resources_per_worker={"CPU": 1, "TPU": chips_per_worker}),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"{label} failed") from result.error
    m = result.metrics
    losses = m["losses"]
    log(f"{label}: {m['devices']} x {m['kind']} in {m['processes']} "
        f"process(es); step compile {m['compile_s']:.1f}s (persistent cache "
        f"hits {m['cache_hits']}, misses {m['cache_misses']} in this "
        f"worker); losses {[round(x, 4) for x in losses]}; pallas calls "
        f"{m['pallas_calls']}, all-reduces {m['all_reduces']}; phase "
        f"{time.perf_counter() - t0:.1f}s")
    if m["platform"] == "tpu":  # a CPU rehearsal's clock says nothing
        steady = sorted(m["step_s"][1:])[len(m["step_s"][1:]) // 2]
        log(f"{label}: median step {steady:.4f}s = "
            f"{m['tokens_per_step'] / steady:.0f} tokens/s; peak HBM "
            f"{m['peak_bytes_in_use']} bytes")
    check(m["devices"] == num_workers * chips_per_worker, "wrong mesh size")
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if sz["platform"] == "tpu":
        check(m["pallas_calls"] >= 3,
              "flash attention fell back: the compiled step holds "
              f"{m['pallas_calls']} tpu_custom_call(s)")
    if m["devices"] > 1:
        check(m["all_reduces"] > 0, "no all-reduce in the compiled step")
    return m


def check_same_losses(ref: dict, got: dict, label: str) -> None:
    for i, (a, b) in enumerate(zip(ref["losses"], got["losses"])):
        check(abs(a - b) <= LOSS_RTOL * abs(a),
              f"{label}: step {i + 1} loss {b} vs one-chip {a} "
              f"(rtol {LOSS_RTOL})")
    log(f"{label}: per-step loss within {LOSS_RTOL} of the one-chip run")


# ------------------------------------------------------- nothing left behind
def adopt_orphans() -> None:
    """Workers run in sessions of their own under the node agent; should
    one outlive it, it becomes this process's child (not init's), so the
    census below sees it."""
    PR_SET_CHILD_SUBREAPER = 36
    check(ctypes.CDLL(None, use_errno=True).prctl(
        PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) == 0, "prctl(subreaper) failed")


def children() -> list:
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except OSError:
            continue  # gone meanwhile
        state, ppid = stat[stat.rindex(")") + 2:].split()[:2]
        if int(ppid) == os.getpid():
            out.append((int(pid), state, cmd[:120]))
    return out


def stop_everything() -> list:
    """``serve.shutdown()`` + ``ray_tpu.shutdown()``, then a census: what
    shutdown() left running is killed and reaped here, and returned — the
    caller fails on it."""
    import ray_tpu
    from ray_tpu import serve

    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    finally:
        ray_tpu.shutdown()

    def reap():
        try:
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        except ChildProcessError:
            pass

    reap()
    leaked = [c for c in children() if c[1] != "Z"]
    for pid, _state, cmd in leaked:
        log(f"LEFT RUNNING by ray_tpu.shutdown(): pid {pid}: {cmd}")
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + EXIT_WAIT_S
    while children() and time.monotonic() < deadline:
        reap()
        time.sleep(0.1)
    return leaked + children()


# -------------------------------------------------------------------- main
def run(args) -> dict:
    sz = sizes(args.rehearse_cpu)
    start_cluster(args.chips, args.rehearse_cpu)
    if args.chips == 1:
        device = phase_core(sz, 1)
        wait_chips_free(1)
        phase_serve(sz, args.seed)
        wait_chips_free(1)
        phase_train(sz, args.seed, num_workers=1, chips_per_worker=1)
        return device
    # Four chips: only what exists across chips, and what it is compared to.
    phase_core(sz, 4)
    wait_chips_free(4)
    one = phase_train(sz, args.seed, num_workers=1, chips_per_worker=1)
    wait_chips_free(4)
    four = phase_train(sz, args.seed, num_workers=1, chips_per_worker=4)
    check_same_losses(one, four, "one worker x four chips")
    device = {"platform": four["platform"], "kind": four["kind"],
              "count": four["devices"]}
    wait_chips_free(4)
    check_same_losses(
        one, phase_train(sz, args.seed, num_workers=4, chips_per_worker=1),
        "four workers x one chip")
    wait_chips_free(4)
    # Two slots keep the replicas' caches small; batch size does not enter
    # a greedy result.
    mono = phase_serve(sz, args.seed, max_batch=2)
    wait_chips_free(4)
    disagg = phase_serve_disagg(sz, args.seed, max_batch=2)
    check(disagg == mono,
          f"disaggregated texts {disagg!r} != one-chip server's {mono!r}")
    log("serve-disagg: greedy texts equal the one-chip server's")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on CPU workers; never prints ok")
    args = ap.parse_args()
    # The cluster's processes import ray_tpu from this checkout, whatever
    # directory the script was started from.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__)),
         os.environ.get("PYTHONPATH", "")])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")

    import ray_tpu  # noqa: F401 - alone, without the repo, this fails here

    adopt_orphans()
    t0 = time.perf_counter()
    device = None
    try:
        device = run(args)
    except BaseException:
        traceback.print_exc()
        print_worker_logs()
    finally:
        leaked = stop_everything()
    if leaked:
        log(f"processes outlived shutdown(): {leaked}")
    if device is None or leaked:
        log("FAILED")
        return 1
    bridge = sys.modules.get("jax._src.xla_bridge")
    check(bridge is None or not bridge.backends_are_initialized(),
          "this process initialised a jax backend")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal_ok": True, "device": device}))
        return 0
    check(device["platform"] == "tpu", f"ran on {device}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
