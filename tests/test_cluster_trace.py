"""The cluster trace written out: ``ray_tpu.shutdown()`` leaves the session's
wall-clock spans in ``<log dir>/spans.jsonl``, and they cover a request's way
in and out of the engine (proxy, replica, engine hand-over) and the start of
a replica and of a gang.  One session for the whole file: a tiny engine behind
``serve.run`` and the HTTP proxy streams a few requests, a two-worker CPU
``fit()`` runs, then the driver shuts down and the tests read the file.
CPU only; every wait is bounded.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu.core.rpc import find_free_port
from ray_tpu.llm import EngineConfig, build_openai_app
from ray_tpu.models import GPT2Config
from ray_tpu.util import tracing

N_REQUESTS, MAX_TOKENS, WORKERS = 3, 6, 2
WAIT_S = 120


def stream_one(url: str) -> dict:
    """One SSE request: its trace id (the response's header), its frames."""
    req = urllib.request.Request(
        url, data=json.dumps({"prompt": "hello", "max_tokens": MAX_TOKENS,
                              "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    deadline = time.monotonic() + 60.0
    while True:  # the proxy's socket comes up asynchronously
        try:
            with urllib.request.urlopen(req, timeout=WAIT_S) as resp:
                trace_id = resp.headers["x-ray-tpu-trace-id"]
                raw = resp.read().decode()
            break
        except urllib.error.HTTPError:
            raise
        except (urllib.error.URLError, ConnectionError):
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)
    frames = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    assert frames[-1] == "[DONE]"
    return {"trace_id": trace_id, "chunks": len(frames) - 1}


def stored_spans() -> list:
    """The store's span rows now (what ``write_spans`` will find)."""
    w = ray_tpu.core.core_worker.global_worker()
    reply = w._run_sync(w.cp.call("list_task_events", {"limit": 1}))
    return [ev for ev in reply["profile_events"]
            if (ev.get("extra") or {}).get("span")]


def wait_for(cond, what: str, given_up=lambda: False) -> None:
    """A killed actor takes its last unpulled spans with it (an agent pulls
    its workers' once a heartbeat): wait for them instead of sleeping."""
    deadline = time.monotonic() + WAIT_S
    while not cond(collections.Counter(s["name"] for s in stored_spans())):
        assert time.monotonic() < deadline and not given_up(), what
        time.sleep(0.2)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import ray_tpu.serve as serve
    from ray_tpu import api
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ray_tpu.init(num_cpus=8)
    try:
        log_dir = api._local_node.log_dir
        serve.run(build_openai_app(EngineConfig(
            model=GPT2Config.tiny(vocab_size=384), max_batch_size=4,
            max_seq_len=64)))
        url = serve.start_http_proxy(port=find_free_port()) + "/v1/completions"
        requests = [stream_one(url) for _ in range(N_REQUESTS)]
        wait_for(lambda n: n["engine.stream"] >= N_REQUESTS
                 and n["serve.request.stream"] >= N_REQUESTS
                 and n["serve.replica.spawn"] >= 1,
                 "the replica's spans never reached the store")

        release = str(tmp_path_factory.mktemp("gang") / "release")
        over = threading.Event()

        def train_loop(config):  # nested: pickled by value
            import os
            import time

            import jax
            import jax.numpy as jnp

            import ray_tpu.train as train

            value = float(jax.jit(lambda x: x * 2 + 1)(jnp.ones(())))
            train.report({"value": value})
            deadline = time.monotonic() + 90
            while not os.path.exists(config["release"]):
                assert time.monotonic() < deadline
                time.sleep(0.1)

        def let_go():
            try:
                wait_for(lambda n: n["train.worker.loop"] >= WORKERS
                         and n["train.worker.jax_init"] >= WORKERS
                         and n["xla.compile"] >= 1 + WORKERS,
                         "the gang's spans never reached the store",
                         given_up=over.is_set)
            finally:
                open(release, "w").close()

        watcher = threading.Thread(target=let_go, daemon=True)
        watcher.start()
        result = JaxTrainer(
            train_loop, train_loop_config={"release": release},
            scaling_config=ScalingConfig(num_workers=WORKERS),
            jax_platform="cpu").fit()
        over.set()
        watcher.join(timeout=WAIT_S)
        serve.stop_http_proxy()
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    with open(os.path.join(log_dir, "spans.jsonl")) as f:
        head, *rows = [json.loads(line) for line in f]
    return {"head": head, "rows": rows, "requests": requests,
            "result": result, "log_dir": log_dir}


def named(session, name):
    return [r for r in session["rows"] if r["name"] == name]


def ancestors(session, row):
    """Names from ``row``'s parent up to its root, by ``parent_id``."""
    by_id = {r["span_id"]: r for r in session["rows"]}
    out = []
    while row["parent_id"] in by_id:
        parent = by_id[row["parent_id"]]
        assert parent["trace_id"] == row["trace_id"]
        out.append(parent["name"])
        row = parent
    return out


def test_the_file_is_written_at_shutdown_with_its_first_row(session):
    head = session["head"]
    assert head["session"] == os.path.basename(
        session["log_dir"])[len("session_"):]
    assert head["dropped_spans"] == 0
    assert head["spans"] == len(session["rows"]) > 0
    for row in session["rows"]:
        assert set(row) == {"name", "start", "end", "trace_id", "span_id",
                            "parent_id", "worker_id", "node_id",
                            "attributes"}
        assert row["start"] <= row["end"] and row["worker_id"]
    # Span rows only: the task timeline's phase rows stay in the store.
    assert not [r for r in session["rows"] if r["name"].startswith("phase:")]


def test_every_request_has_its_way_in_and_out_under_one_trace(session):
    assert len({r["trace_id"] for r in session["requests"]}) == N_REQUESTS
    for request in session["requests"]:
        rows = {r["name"]: r for r in session["rows"]
                if r["trace_id"] == request["trace_id"]}
        assert set(rows) >= {"serve.http.stream", "serve.request.stream",
                             "task:handle_request_streaming", "engine.stream"}
        http, replica = rows["serve.http.stream"], rows["serve.request.stream"]
        a = http["attributes"]
        assert http["start"] <= a["first_write_unix_ns"] / 1e9 <= (
            a["last_write_unix_ns"] / 1e9) <= http["end"]
        assert 1 <= a["writes"] <= a["chunks"] == request["chunks"]
        assert a["chunks"] == replica["attributes"]["chunks"]
        assert 0 <= a["route_ms"] <= (http["end"] - http["start"]) * 1e3
        assert replica["attributes"]["sem_wait_ms"] >= 0
        assert replica["attributes"]["ttft_s"] > 0
        # The legs lie in order on one host's wall clock.
        stream = rows["engine.stream"]
        s = stream["attributes"]
        assert http["start"] <= replica["start"] <= stream["start"] <= (
            s["admitted_unix_ns"] / 1e9) <= s["first_token_unix_ns"] / 1e9 <= (
            a["first_write_unix_ns"] / 1e9)
        assert 1 <= s["deltas"] <= s["tokens"] == MAX_TOKENS
        assert ancestors(session, stream) == [
            "task:handle_request_streaming", "serve.http.stream"]
        # Proxy and replica are two processes.
        assert http["worker_id"] != replica["worker_id"] == stream["worker_id"]


def test_the_replicas_start_is_a_tree(session):
    [run] = named(session, "serve.run")
    [spawn] = named(session, "serve.replica.spawn")
    [init] = named(session, "serve.replica.init")
    [build] = named(session, "llm.engine.build")
    assert ancestors(session, build) == [
        "serve.replica.init", "serve.replica.spawn", "task:deploy",
        "serve.run"]
    assert run["start"] <= spawn["start"] <= init["start"] <= build["start"]
    assert build["end"] <= init["end"] <= spawn["end"]
    assert init["worker_id"] == build["worker_id"] != spawn["worker_id"]
    compiles = [r for r in named(session, "llm.engine.compile")]
    assert {(r["attributes"]["program"], r["attributes"].get("rung"))
            for r in compiles} == {("prefill_one", 64), ("decode_step", None)}
    [weights] = named(session, "llm.engine.weights")
    [relayout] = named(session, "llm.engine.relayout")
    assert relayout["attributes"] == {"relaid_param_bytes": 0}  # the CPU
    for row in (*compiles, weights, relayout):
        assert row["parent_id"] == build["span_id"]
        assert build["start"] <= row["start"] <= row["end"] <= build["end"]


def test_the_gangs_start_is_a_tree(session):
    assert session["result"].error is None
    [fit] = named(session, "train.fit")
    children = {r["name"]: r for r in session["rows"]
                if r["parent_id"] == fit["span_id"]}
    assert set(children) >= {"train.placement", "train.backend"}
    assert fit["start"] <= children["train.placement"]["start"] <= (
        children["train.placement"]["end"]) <= (
        children["train.backend"]["start"]) <= (
        children["train.backend"]["end"]) <= fit["end"]
    inits = named(session, "train.worker.jax_init")
    assert sorted(r["attributes"]["rank"] for r in inits) == list(
        range(WORKERS))
    assert len({r["worker_id"] for r in inits}) == WORKERS
    for row in inits:
        a = row["attributes"]  # its own length, in its three parts
        assert a["import_s"] >= 0 and a["initialize_s"] > 0
        assert a["import_s"] + a["initialize_s"] + a["runtime_s"] == (
            pytest.approx(row["end"] - row["start"], abs=0.05))
        assert ancestors(session, row) == [
            "task:init_jax_distributed", "train.backend", "train.fit"]
        assert children["train.backend"]["start"] <= row["start"] <= (
            row["end"]) <= children["train.backend"]["end"]
    loops = named(session, "train.worker.loop")
    assert sorted(r["attributes"]["rank"] for r in loops) == list(
        range(WORKERS))
    for row in loops:
        assert row["start"] == row["end"] >= children["train.backend"]["end"]
        assert ancestors(session, row) == ["task:run", "train.fit"]
    # The root is the context of the gang's start alone: the driver's polls
    # while the job runs (five a second a worker) write no span.
    assert not named(session, "task:poll")


def test_a_compilation_is_a_row_in_its_process(session):
    rows = named(session, "xla.compile")
    assert rows and all(
        "backend_compile" in r["attributes"]["event"]
        or "compilation_cache" in r["attributes"]["event"] for r in rows)
    # The gang's loop compiled one program a worker; the replica's build
    # compiled in pool threads, which carry the build's context.
    gang = {r["worker_id"] for r in named(session, "train.worker.loop")}
    assert {r["worker_id"] for r in rows} >= gang
    [build] = named(session, "llm.engine.build")
    in_build = [r for r in rows if r["worker_id"] == build["worker_id"]
                and build["start"] <= r["start"] and r["end"] <= build["end"]]
    assert in_build
    for row in rows:
        assert row["trace_id"] and row["start"] <= row["end"]


def test_the_listener_is_registered_when_jax_loads_and_not_before():
    """``import ray_tpu`` must not import jax, and the hook that waits for
    ``jax._src.monitoring`` (a private name, which ``jax.monitoring``
    re-exports) has to find it still: a jax that moves the module would
    leave every process without ``xla.compile`` rows, in silence."""
    code = (
        "import sys, ray_tpu\n"
        "from ray_tpu.core import compile_cache as cc\n"
        "assert not any(m == 'jax' or m.startswith('jax.')"
        " for m in sys.modules), 'import ray_tpu imported jax'\n"
        "import jax.monitoring\n"
        "private = sys.modules[cc._MONITORING]\n"
        "assert (jax.monitoring.register_event_duration_secs_listener"
        " is private.register_event_duration_secs_listener)\n"
        "assert private.get_event_duration_listeners().count("
        "cc._on_duration) == 1\n"
        "cc.trace_compilations()\n"
        "assert private.get_event_duration_listeners().count("
        "cc._on_duration) == 1\n")
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=WAIT_S, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]


def test_get_trace_and_the_file_agree_on_a_request(session):
    """The file holds what ``get_trace`` would have returned: the same
    store, read once more before it stops."""
    request = session["requests"][0]
    rows = [r for r in session["rows"]
            if r["trace_id"] == request["trace_id"]]
    assert len(rows) >= 4
    assert len({r["span_id"] for r in rows}) == len(rows)


def test_a_span_recorded_a_moment_ago_in_another_process_is_in_the_file(
        tmp_path):
    """``write_spans`` waits for no heartbeat: it flushes this process's
    buffer and has every agent pull its workers' once more before it reads
    the store."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def traced():
            with tracing.start_span("just.now", {"k": 1}):
                return os.getpid()

        with tracing.start_span("root") as root:
            pid = ray_tpu.get(traced.remote(), timeout=60)
        path = str(tmp_path / "spans.jsonl")
        written = tracing.write_spans(path, "by-hand")
        with open(path) as f:
            head, *rows = [json.loads(line) for line in f]
    finally:
        ray_tpu.shutdown()
    assert pid != os.getpid()
    assert head == {"session": "by-hand", "dropped_spans": 0,
                    "spans": written} and written == len(rows)
    mine = {r["name"]: r for r in rows if r["trace_id"] == root.trace_id}
    assert set(mine) == {"root", "task:traced", "just.now"}
    assert mine["just.now"]["attributes"] == {"k": 1}
    assert mine["just.now"]["parent_id"] == mine["task:traced"]["span_id"]
    assert mine["task:traced"]["parent_id"] == mine["root"]["span_id"]
    assert mine["root"]["worker_id"] != mine["just.now"]["worker_id"]
    assert not os.path.exists(path + ".tmp")


def test_a_driver_that_did_not_start_the_head_writes_nothing(tmp_path):
    """``write_spans`` is the head's driver's: with no cluster it has no
    store to read."""
    with pytest.raises(Exception):
        tracing.write_spans(str(tmp_path / "spans.jsonl"))
    assert not os.listdir(tmp_path)
