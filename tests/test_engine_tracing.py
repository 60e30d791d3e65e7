"""The serving engine's and the train session's spans and counters.

``util.tracing.host_span`` is ``jax.profiler.TraceAnnotation``: under a
profiler session the spans land in the profiler's own trace (on the CPU
backend too), which ``benchmarks/lib/host_spans.py`` reads back.  Span names
and attributes are a contract (PERF.md lists the metric that reads each).
Nothing here times anything.
"""

import subprocess
import sys
import threading
import time

import pytest

from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from ray_tpu.llm.engine import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import GPT2Config
from ray_tpu.ops.decode_attention import live_extent
from ray_tpu.util import tracing

SLOTS, SEQ = 4, 64
PROMPTS = ["a", "bc", "def", "ghij", "klmno", "pqrstu"]
MAX_TOKENS = [3, 5, 2, 7, 4, 6]


def make_engine():
    return JaxLLMEngine(EngineConfig(
        model=GPT2Config.tiny(vocab_size=384),
        max_batch_size=SLOTS, max_seq_len=SEQ))


def by_hand(engine):
    """Step until nothing is unfinished, as a caller that IS the loop."""
    while engine.has_unfinished():
        engine.step()


def run_scenario(engine):
    """Six requests of known lengths through four slots, stepped by hand,
    counted as ``engine.counts`` counts: slots occupied when ``step()``
    returns.
    A stop token that never comes, so every request runs to max_tokens."""
    for prompt, n in zip(PROMPTS, MAX_TOKENS):
        engine.add_request(
            prompt, SamplingParams(max_tokens=n, stop_token=-1))
    outside = []
    while engine.has_unfinished():
        engine.step()
        outside.append(sum(1 for s in engine.slots if s is not None))
    return outside


def traced(tmp_path, body):
    """Run ``body`` under a profiler session; the program's spans per thread
    (nested), as the benchmark's library reads them."""
    tracing.start_profile(str(tmp_path))
    try:
        body()
    finally:
        tracing.stop_profile()
    [path] = tr.find_traces(str(tmp_path))
    return hs.from_planes(hs.load_host(path), {})


@pytest.fixture(scope="module")
def scenario(tmp_path_factory):
    """One traced run of the scenario: (engine, outside counts, spans).
    Stepped by hand from the first request on: the engine has no loop."""
    engine = make_engine()
    engine.add_request("warm", SamplingParams(max_tokens=2, stop_token=-1))
    by_hand(engine)
    before = engine.stats()
    out = {}
    trace = traced(tmp_path_factory.mktemp("trace"),
                   lambda: out.update(outside=run_scenario(engine)))
    return engine, before, out["outside"], trace


def test_every_span_of_the_table_is_there(scenario):
    _engine, _before, _outside, trace = scenario
    names = {s.name for s in trace.spans()}
    assert names == {
        "engine.lock_wait", "engine.step", "engine.admit",
        "engine.prefill.dispatch", "engine.decode.dispatch", "engine.sample",
        "engine.retire", "engine.counts"}


def test_spans_nest_per_thread(scenario):
    _engine, _before, outside, trace = scenario
    [roots] = trace.threads  # one thread drove the engine
    assert {r.name for r in roots} == {"engine.lock_wait", "engine.step"}
    steps = trace.spans("engine.step")
    assert len(steps) == len(outside)
    for step in steps:
        kinds = [c.name for c in step.children]
        # Admissions, then the decode's dispatch, then ONE sample span (the
        # sampler's dispatch and every read of the step), then the counts.
        admits = kinds.count("engine.admit")
        decoded = "engine.decode.dispatch" in kinds
        assert kinds == (["engine.admit"] * admits
                         + ["engine.decode.dispatch"] * decoded
                         + ["engine.sample", "engine.counts"])
        sample = step.children[-2]
        assert all(a.end <= b.start for a, b in zip(step.children,
                                                    step.children[1:]))
        # Requests retire where their last token is read: inside it.
        assert {c.name for c in sample.children} <= {"engine.retire"}
    assert len(trace.spans("engine.retire")) >= 1
    for admit in trace.spans("engine.admit"):
        assert [c.name for c in admit.children] == [
            "engine.prefill.dispatch", "engine.sample"]
        assert admit.children[1].stats == {"slots": 1}
    # A lock wait is over before the step it waited for begins.
    assert all(not r.children for r in roots if r.name == "engine.lock_wait")


def test_attributes_at_entry(scenario):
    engine, before, outside, trace = scenario
    steps = trace.spans("engine.step")
    assert [s.stats["seq"] for s in steps] == list(
        range(before["steps"], before["steps"] + len(steps)))
    admits = trace.spans("engine.admit")
    assert len(admits) == len(PROMPTS)
    for admit, prompt in zip(admits, PROMPTS):
        st = admit.stats
        assert set(st) == {"request_id", "slot", "prompt_len", "padded_len",
                           "queue_wait_ms",  # no cluster trace: no trace_id
                           "unix_ns"}
        assert st["prompt_len"] == len(engine.tokenizer.encode(prompt))
        assert st["padded_len"] == SEQ and 0 <= st["slot"] < SLOTS
        assert st["queue_wait_ms"] >= 0
    for step in steps:
        for child in step.children:
            if child.name == "engine.decode.dispatch":
                st = child.stats
                assert set(st) == {"active", "longest", "read_positions",
                                   "cache_positions", "live_positions"}
                assert st["active"] >= 1
                # how far the step's attention reads the cache: the
                # program's own bound, from the host's copy of ``pos``
                assert st["cache_positions"] == SEQ
                assert st["read_positions"] == live_extent(
                    st["longest"], SEQ) <= SEQ
                assert 1 <= st["live_positions"] <= st["longest"] < SEQ
            if child.name == "engine.sample":  # the rows that rode the step
                assert child.stats["slots"] == sum(
                    c.stats["active"] for c in step.children
                    if c.name == "engine.decode.dispatch")


def test_counts_once_per_step_and_equal_to_the_outside_count(scenario):
    _engine, _before, outside, trace = scenario
    counts = trace.spans("engine.counts")
    assert [c.stats["occupied"] for c in counts] == outside
    assert sum(c.stats["admitted"] for c in counts) == len(PROMPTS)
    assert sum(c.stats["retired"] for c in counts) == len(PROMPTS)
    assert counts[0].stats["waiting"] == len(PROMPTS) - SLOTS
    assert counts[-1].stats == {"occupied": 0, "waiting": 0, "admitted": 0,
                                "retired": counts[-1].stats["retired"],
                                "waiters": 0, "host_syncs": 1, "overrun": 0}
    assert all(set(c.stats) == {"occupied", "waiting", "admitted", "retired",
                                "waiters", "host_syncs", "overrun"}
               for c in counts)
    # One read for the tokens of the step BEFORE, if it decoded (the first
    # step here follows a drained engine), one for each admission's first.
    decoded = [any(c.name == "engine.decode.dispatch" for c in step.children)
               for step in trace.spans("engine.step")]
    assert decoded == [True] * (len(counts) - 1) + [False]  # the last reads
    assert [c.stats["host_syncs"] for c in counts] == [
        before + c.stats["admitted"]
        for before, c in zip([0] + decoded, counts)]


def fresh_stats(engine, base):
    now = engine.stats()
    return {k: now[k] - base[k] for k in now
            if k not in ("occupied", "waiting",
                         "queue_wait_s_total", "lock_wait_s_total")}


def test_stats_are_exact_and_the_same_with_and_without_a_session(scenario):
    engine, before, outside, _trace = scenario
    with_session = fresh_stats(engine, before)
    n = len(PROMPTS)
    assert with_session == {
        "steps": len(outside),
        "loop_steps": 0,  # stepped by hand: no loop, no thread
        # Every step decodes but the last, which reads what the one before
        # it sampled.
        "decode_steps": len(outside) - 1,
        "admitted": n, "retired": n, "cancelled": 0,
        "prompt_tokens": sum(len(engine.tokenizer.encode(p)) for p in PROMPTS),
        "padded_prompt_tokens": n * SEQ,
        "generated_tokens": sum(MAX_TOKENS),
        # The inside count equals the outside count, step for step.
        "occupied_slot_steps": sum(outside),
        # One token read a decode step, one a prefilled admission.
        "host_syncs": len(outside) - 1 + n,
        "overrun_row_steps": 0,  # every request ran to its max_tokens
        "stream_deltas": 0, "stream_delta_tokens": 0,  # nobody streamed
        "relaid_param_bytes": 0,  # set at build; on the CPU nothing moves
    }
    assert engine.stats()["occupied"] == engine.occupied() == 0
    assert engine.stats()["waiting"] == 0
    base = engine.stats()
    outside_again = run_scenario(engine)  # no profiler session now
    assert outside_again == outside
    assert fresh_stats(engine, base) == with_session
    assert sum(outside) / len(outside) == (
        with_session["occupied_slot_steps"] / with_session["steps"])
    assert engine._loop is None


def test_stats_only_grow_and_waits_are_counted():
    engine = make_engine()
    zero = engine.stats()
    # stats() takes the lock itself: its own wait is already counted.
    assert all(v == 0 for k, v in zero.items() if k != "lock_wait_s_total")
    assert set(zero) == {
        "steps", "loop_steps", "decode_steps", "admitted", "retired",
        "cancelled", "prompt_tokens", "padded_prompt_tokens",
        "generated_tokens",
        "occupied_slot_steps", "host_syncs", "overrun_row_steps",
        "stream_deltas", "stream_delta_tokens", "relaid_param_bytes",
        "queue_wait_s_total", "lock_wait_s_total", "occupied", "waiting"}
    engine.add_request("queued", SamplingParams(max_tokens=9, stop_token=-1))
    assert engine.stats()["waiting"] == 1 and engine.occupied() == 0
    engine.step()
    one = engine.stats()
    assert one["occupied"] == engine.occupied() == 1 and one["waiting"] == 0
    assert one["queue_wait_s_total"] > 0 and one["lock_wait_s_total"] > 0
    engine.generate(["x"], SamplingParams(max_tokens=3))
    two = engine.stats()
    assert all(two[k] >= one[k] for k in one if k not in ("occupied", "waiting"))
    # The one step by hand is not the loop's; generate()'s are.
    assert one["loop_steps"] == 0 and two["loop_steps"] == two["steps"] - 1


def test_cancel_counts_what_it_dropped():
    engine = make_engine()
    rid = engine.add_request("in the queue", SamplingParams(max_tokens=4))
    engine.cancel_request(rid)
    held = engine.add_request("in a slot", SamplingParams(max_tokens=9))
    engine.step()
    engine.cancel_request(held)
    engine.cancel_request(held)  # nothing left to drop
    stats = engine.stats()
    assert stats["cancelled"] == 2 and stats["occupied"] == 0
    # The step in flight sampled a token for the row: it is read and dropped.
    assert engine.has_unfinished() and stats["overrun_row_steps"] == 0
    assert engine.step() == [] and not engine.has_unfinished()
    assert engine.stats()["overrun_row_steps"] == 1


def test_two_streams_wait_on_their_mailboxes_and_only_the_loop_steps(tmp_path):
    engine = make_engine()
    params = SamplingParams(max_tokens=12, stop_token=-1)
    engine.generate(["warm"], params)
    before = engine.stats()
    gate = threading.Barrier(2)

    def stream(prompt):
        gate.wait()
        for _delta in engine.generate_stream(prompt, params):
            pass

    def both():
        threads = [threading.Thread(target=stream, args=(p,))
                   for p in ("first", "second")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    trace = traced(tmp_path, both)
    after = engine.stats()
    # ONE thread stepped, and a step is all it did: its turn at the lock is
    # no wait.
    [stepping] = [roots for roots in trace.threads
                  if any(r.name == "engine.step" for r in roots)]
    assert {r.name for r in stepping} == {"engine.step"}
    assert after["loop_steps"] - before["loop_steps"] == len(stepping) == (
        after["steps"] - before["steps"])
    # The streams took the lock from outside, once each: the cancel that
    # ends a stream, for at most a step.
    waiting = [roots for roots in trace.threads if roots is not stepping]
    assert len(waiting) == 2
    for roots in waiting:
        [wait] = roots
        assert wait.name == "engine.lock_wait" and not wait.children
        assert "request_id" in wait.stats
    steps = sorted(trace.spans("engine.step"), key=lambda s: s.start)
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    assert [s.stats["seq"] for s in steps] == list(
        range(steps[0].stats["seq"], steps[0].stats["seq"] + len(steps)))
    assert len(trace.spans("engine.admit")) == 2
    # Callers blocked on a mailbox at the end of a step: both, at some step.
    assert max(c.stats["waiters"] for c in trace.spans("engine.counts")) == 2


def test_admit_carries_the_cluster_trace_id(tmp_path):
    engine = make_engine()

    def body():
        with tracing.start_span("request") as span:
            body.trace_id = span.trace_id
            engine.add_request("traced", SamplingParams(max_tokens=2))
        engine.add_request("untraced", SamplingParams(max_tokens=2))
        while engine.has_unfinished():
            engine.step()

    t0 = time.time_ns()
    first, second = traced(tmp_path, body).spans("engine.admit")
    assert first.stats["trace_id"] == body.trace_id
    assert "trace_id" not in second.stats
    # The anchor between the two clocks: the wall clock at the span's entry,
    # on every admission, traced by the cluster or not.
    assert t0 <= first.stats["unix_ns"] <= second.stats["unix_ns"] <= (
        time.time_ns())
    # One session, one clock relation: the two readings of (wall clock -
    # the trace's clock) agree to well under the check's 2 ms.
    offsets = [s.stats["unix_ns"] - s.start for s in (first, second)]
    assert abs(offsets[0] - offsets[1]) < 2e6


def test_one_engine_stream_row_a_streamed_request(monkeypatch):
    """``engine.stream``: one wall-clock span a streamed request, recorded
    when the stream ends under its caller's context, from the stamps the
    request's mailbox holds; ``stats()`` sums what the rows count."""
    rows = []
    monkeypatch.setattr(tracing, "_record", rows.append)
    engine = make_engine()
    # The build, with the host's part of the load, the weights' move into
    # the decode step's layouts and one span a program.
    assert sorted(r.name for r in rows) == [
        "llm.engine.build", "llm.engine.compile", "llm.engine.compile",
        "llm.engine.relayout", "llm.engine.weights"]
    build = rows[-1]
    assert build.name == "llm.engine.build"
    programs = {(r.attributes["program"], r.attributes.get("rung"))
                for r in rows if r.name == "llm.engine.compile"}
    assert programs == {("prefill_one", SEQ), ("decode_step", None)}
    assert all((r.trace_id, r.parent_id) == (build.trace_id, build.span_id)
               for r in rows[:-1])
    del rows[:]
    engine.generate(["warm"], SamplingParams(max_tokens=2, stop_token=-1))
    assert not rows  # a unary request writes none
    before = engine.stats()
    lengths = {"first": 9, "second": 1, "third": 5}  # one whose first is last
    got, contexts = {}, {}

    def stream(prompt):
        with tracing.start_span("caller") as span:
            contexts[prompt] = (span.trace_id, span.span_id)
            got[prompt] = list(engine.generate_stream(prompt, SamplingParams(
                max_tokens=lengths[prompt], stop_token=-1)))

    t0 = time.time()
    threads = [threading.Thread(target=stream, args=(p,)) for p in lengths]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    streams = [r for r in rows if r.name == "engine.stream"]
    assert len(streams) == len(lengths)
    assert len({r.attributes["request_id"] for r in streams}) == len(lengths)
    by_parent = {(r.trace_id, r.parent_id): r for r in streams}
    for prompt, n in lengths.items():
        row = by_parent[contexts[prompt]]
        a = row.attributes
        assert set(a) == {"request_id", "admitted_unix_ns",
                          "first_token_unix_ns", "deltas", "tokens"}
        assert t0 <= row.start <= a["admitted_unix_ns"] / 1e9 <= (
            a["first_token_unix_ns"] / 1e9) <= row.end <= time.time()
        assert 1 <= a["deltas"] <= a["tokens"] == n
    after = engine.stats()
    assert after["stream_delta_tokens"] - before["stream_delta_tokens"] == (
        sum(lengths.values()))
    assert after["stream_deltas"] - before["stream_deltas"] == sum(
        r.attributes["deltas"] for r in streams)
    engine.shutdown()


def test_start_span_opens_no_annotation(tmp_path):
    def body():
        with tracing.start_span("cluster.side", {"k": 1}):
            with tracing.host_span("engine.marker", k=1):
                pass

    trace = traced(tmp_path, body)
    assert [s.name for s in trace.spans()] == ["engine.marker"]
    [path] = tr.find_traces(str(tmp_path))
    from jax.profiler import ProfileData

    every = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "cluster.side" not in every


def test_host_span_is_a_no_op_that_does_not_import_jax():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "assert 'jax' not in sys.modules, 'importing tracing loaded jax'\n"
        "a = tracing.host_span('engine.x', n=1)\n"
        "b = tracing.host_span('engine.y')\n"
        "assert a is b  # the shared no-op\n"
        "with a:\n"
        "    with b:\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'host_span loaded jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_add_is_never_lost_to_a_concurrent_cancel():
    """``add_request`` appends without the engine lock; ``cancel_request``
    used to rebind the queue to a filtered copy, so an append that fell
    between the copy and the rebind landed in the discarded list."""
    engine = make_engine()
    params = SamplingParams(max_tokens=1)
    victims = [engine.add_request("victim", params) for _ in range(300)]
    added = []
    stop = threading.Event()

    def adder():
        while not stop.is_set() and len(added) < 3000:
            added.append(engine.add_request("keep", params))

    t = threading.Thread(target=adder)
    t.start()
    for rid in victims:
        engine.cancel_request(rid)
    stop.set()
    t.join()
    queue = engine._waiting
    assert [w[0] for w in queue] == added  # none lost, order kept
    assert engine.stats()["cancelled"] == len(victims)
    # The queue is the same list it was: a rebind would lose appends.
    engine.cancel_request(added[0])
    assert engine._waiting is queue and queue[0][0] == added[1]


def test_train_report_runs_inside_a_span(tmp_path):
    from ray_tpu.train import session

    got = []
    session._set_session(session.TrainContext(
        world_rank=0, world_size=1, local_rank=0, node_rank=0,
        _report_fn=lambda metrics, ckpt: got.append(metrics)))
    try:
        trace = traced(tmp_path, lambda: [
            session.report({"step": i}) for i in range(3)])
    finally:
        session._clear_session()
    assert got == [{"step": 0}, {"step": 1}, {"step": 2}]
    assert [s.name for s in trace.spans()] == ["train.report"] * 3


def test_serve_app_exposes_stats_and_profile_hooks(tmp_path):
    from ray_tpu.llm.serve_app import LLMServer

    server = LLMServer.func_or_class(EngineConfig(
        model=GPT2Config.tiny(vocab_size=384), max_batch_size=2,
        max_seq_len=32))
    assert server.start_profile(str(tmp_path)) == str(tmp_path)
    try:
        list(server.stream_chunks({"prompt": "hi", "max_tokens": 3}))
    finally:
        server.stop_profile()
    stats = server.engine_stats()
    assert stats["admitted"] == stats["retired"] == 1
    [path] = tr.find_traces(str(tmp_path))
    names = {s.name for s in hs.from_planes(hs.load_host(path), {}).spans()}
    assert {"engine.step", "engine.admit", "engine.counts"} <= names


def test_each_step_feeds_the_metrics_registry(monkeypatch):
    from ray_tpu.util import flight_recorder

    rows = []
    monkeypatch.setattr(
        flight_recorder, "record_llm_step",
        lambda *a: rows.append(a))
    engine = make_engine()
    outside = run_scenario(engine)
    assert [r[0] for r in rows] == outside          # occupancy
    assert sum(r[2] for r in rows) == len(PROMPTS)  # admitted
    assert sum(r[3] for r in rows) == len(PROMPTS)  # retired
    assert {r[4] for r in rows} == {SLOTS}          # bucket
    assert rows[0][1] == len(PROMPTS) - SLOTS       # queue depth


def test_cache_read_pct_metric_reads_the_dispatch_span(tmp_path):
    """``cache_read_pct.serve`` as ``BENCHMARK.json`` and its metric file
    define it, on a traced run of an engine whose cache has two extents (1024
    positions, blocks of 512): a step whose longest row is short reads half
    the cache, one with a long row all of it, by the function the program
    bounds its loop with; the short request's ids are the same beside the
    long neighbour as alone; spans without the attributes (the parent's)
    give nothing, not an error."""
    import json
    import os
    import types

    from benchmarks.readers import span_stat

    name, seq = "cache_read_pct.serve", 1024
    with open(os.path.join(hs.ROOT, "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert (entry["moves"], entry["layer"], entry["better"]) == (
        "serve_tokens_per_s", "model step", "lower")
    # every serving cell with a full-extent cache: PR 46's four, the
    # Mistral-4 cell (PR 48), the Laguna cell (PR 52), the Olmo-Hybrid cell
    # (PR 56), the Granite cell (PR 60), the Mistral long-prompt cell
    # (PR 66), the Kimi-Linear cell (its latent layers) and the Mistral
    # decode-only cell (PR 67)
    assert len(entry["workloads"]) == 11
    with open(os.path.join(hs.ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name and spec["reader"] == "span_stat"

    def engine_of():
        return JaxLLMEngine(EngineConfig(
            model=GPT2Config.tiny(vocab_size=384, max_seq=seq),
            max_batch_size=2, max_seq_len=seq))

    short = SamplingParams(max_tokens=6, stop_token=-1)
    alone = engine_of().generate(["a short one"], short)[0]["token_ids"]
    engine, done = engine_of(), {}

    def drain():
        while engine.has_unfinished():
            done.update((r["request_id"], r) for r in engine.step())

    tracing.start_profile(str(tmp_path))
    try:
        first = engine.add_request("a short one", short)
        drain()
        second = engine.add_request("a short one", short)
        engine.add_request("l" * 600, SamplingParams(
            max_tokens=3, stop_token=-1))
        drain()
    finally:
        tracing.stop_profile()
    assert done[first]["token_ids"] == alone
    assert done[second]["token_ids"] == alone
    [path] = tr.find_traces(str(tmp_path))
    trace = hs.from_planes(hs.load_host(path), {})
    ctx = types.SimpleNamespace(host_spans=[trace], trace=object(),
                                config={}, mix={}, stats={})
    reads = [s.stats["read_positions"]
             for s in trace.spans("engine.decode.dispatch")]
    assert set(reads) == {512, seq} and reads[0] == 512
    assert span_stat.read(ctx, **spec["args"]) == pytest.approx(
        100.0 * sum(reads) / (seq * len(reads)))
    for s in trace.spans("engine.decode.dispatch"):
        assert s.stats["read_positions"] == live_extent(
            s.stats["longest"], seq)
    parents = types.SimpleNamespace(spans=lambda name: [
        types.SimpleNamespace(stats={"active": 2})])
    ctx.host_spans = [parents]
    assert span_stat.read(ctx, **spec["args"]) is None


# ------------------------------------------- the programs' scope tables
# family -> (its benchmark configuration's file, the scopes' prefix, the
# parts its decode step must carry: what the module's docstring names)
FAMILIES = {
    "gpt2": (None, "gpt2", {"embed", "attn", "mlp", "head"}),
    "llama": ("mistral7b_l16", "llama", {"embed", "attn", "mlp", "head"}),
    "longcat": ("longcat_flash_l4_ep32", "longcat",
                {"embed", "mla", "moe", "ffn", "head"}),
    "nemotron_h": ("nemotron3_super_l11_ep4", "nemotron",
                   {"embed", "mamba", "attn", "moe", "head"}),
    "mimo_v2": ("mimo_v25_l7_ep16", "mimo",
                {"embed", "attn_full", "attn_window", "moe", "mlp", "head"}),
    "mistral4": ("mistral_small4_l9_ep8", "mistral4",
                 {"embed", "mla", "moe", "shared", "head"}),
    "laguna": ("laguna_s21_l9_ep16", "laguna",
               {"embed", "attn_full", "attn_window", "moe", "shared", "mlp",
                "head"}),
    "olmo_hybrid": ("olmo_hybrid7b_l12", "olmo",
                    {"embed", "delta", "attn", "mlp", "head"}),
    "granite_h": ("granite4h_micro", "granite",
                  {"embed", "mamba", "attn", "mlp", "head"}),
    # ``lib/scopes.py``'s PARTS (the benchmark's, a benchmark PR's to widen)
    # know no ``lightning`` and no ``select``: ``scope_of`` reads those
    # operations as unscoped, and the cell's own metric files find them by
    # pattern (``tests/test_bench_scopes.py``)
    "minicpm_sala": ("minicpm_sala_l12", "sala",
                     {"embed", "attn", "mlp", "head"}),
}


def tiny_model(family):
    """The family's model at the widths its benchmark cell rehearses at."""
    import importlib
    import json
    import os

    config_file = FAMILIES[family][0]
    if config_file is None:
        return GPT2Config.tiny(vocab_size=384)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs",
                           config_file + ".json")) as f:
        tiny = json.load(f)["tiny"]
    return importlib.import_module(
        "benchmarks.families." + family).config(tiny)


def read_programs(path):
    import json

    with open(path / "programs.jsonl") as f:
        return [json.loads(line) for line in f]


def parts_of(row, prefix):
    from benchmarks.lib import scopes

    found = {scopes.scope_of(op_name) for op_name in row["ops"].values()}
    return {s.split(".", 1)[1] for s in found if s.startswith(prefix + ".")}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_stop_profile_writes_every_programs_scope_table(family, tmp_path):
    """``programs.jsonl`` beside the trace: a row for the decode program
    and one for each prefill rung, every ``op_name`` text, and the decode
    row's scopes are the parts the family's docstring names, ``head`` and
    ``embed`` among them."""
    import importlib

    _file, prefix, parts = FAMILIES[family]
    engine = JaxLLMEngine(EngineConfig(
        model=tiny_model(family), max_batch_size=2, max_seq_len=32))
    tracing.start_profile(str(tmp_path))
    try:
        engine.add_request("ab", SamplingParams(max_tokens=3, stop_token=-1))
        by_hand(engine)
    finally:
        tracing.stop_profile()
    assert tr.find_traces(str(tmp_path))  # the trace is there too
    rows = read_programs(tmp_path)
    for row in rows:
        assert set(row) == {"module", "fingerprint", "ops"}
        assert isinstance(row["fingerprint"], str) and row["fingerprint"]
        assert all(isinstance(k, str) and isinstance(v, str)
                   for k, v in row["ops"].items())
        assert not any(name.startswith("%") for name in row["ops"])
    # Other engines of this process may be alive too: this family's rows.
    mine = [row for row in rows if parts_of(row, prefix)]
    decode = [row for row in mine if row["module"] == "jit__lambda"]
    rungs = [row for row in mine if row["module"] == "jit_prefill_one"]
    assert decode and len(rungs) >= len(engine._prefill_one) >= 1
    assert all(parts_of(row, prefix) == parts for row in decode)
    for row in rungs:
        assert parts_of(row, prefix) >= parts - {"embed"}  # a gather may fuse
    doc = importlib.import_module(
        "ray_tpu.models." + {"llama": "llama_decode"}.get(family, family)
    ).__doc__
    assert all(f"``{prefix}.{part}``" in doc for part in parts), family


def test_gpt2_loss_carries_its_scopes_through_the_backward_pass(tmp_path):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_init, gpt2_loss

    cfg = GPT2Config.tiny(vocab_size=384)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 17), jnp.int32)

    def step(p, t):
        return jax.value_and_grad(lambda q: gpt2_loss(q, t, cfg))(p)

    compiled = jax.jit(step).lower(params, tokens).compile()
    tracing.start_profile(str(tmp_path))
    try:
        jax.block_until_ready(compiled(params, tokens))
    finally:
        tracing.stop_profile()
    [row] = [r for r in read_programs(tmp_path) if r["module"] == "jit_step"
             and parts_of(r, "gpt2")]
    assert parts_of(row, "gpt2") == {"embed", "attn", "mlp", "head"}
    backward = [v for v in row["ops"].values() if "transpose(jvp(" in v]
    assert any("gpt2.attn" in v for v in backward)
    assert any("gpt2.head" in v for v in backward)


def test_one_unreadable_program_costs_its_row_and_nothing_else(
        tmp_path, monkeypatch, caplog):
    import jax
    import jax.numpy as jnp

    class Unreadable:
        fingerprint = b"x"

        def hlo_modules(self):
            raise RuntimeError("no text for this one")

    def f(x):
        with jax.named_scope("fam.mlp"):
            for _ in range(tracing.MIN_PROGRAM_OPS):
                x = jnp.sin(x) @ x
        return x

    compiled = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    real = tracing._live_executables
    monkeypatch.setattr(tracing, "_live_executables",
                        lambda: [Unreadable(), *real()])
    tracing.start_profile(str(tmp_path))
    jax.block_until_ready(compiled(jnp.ones((8, 8))))
    with caplog.at_level("WARNING", logger=tracing.__name__):
        tracing.stop_profile()  # returns
    assert "could not be read" in caplog.text
    assert tr.find_traces(str(tmp_path))
    assert any(r["module"] == "jit_f" for r in read_programs(tmp_path))


def test_a_session_someone_else_started_gets_no_table(tmp_path):
    """A process that never calls ``start_profile`` writes nothing: the
    path is remembered there and nowhere else."""
    import jax

    assert tracing._profile_path is None
    jax.profiler.start_trace(str(tmp_path))
    tracing.stop_profile()
    assert not (tmp_path / "programs.jsonl").exists()
    with pytest.raises(RuntimeError):
        tracing.stop_profile()  # no session: jax's own error, nothing written
    assert not (tmp_path / "programs.jsonl").exists()


CACHED_SCOPE = """
import sys, jax, jax.numpy as jnp
from ray_tpu.util import tracing
jax.config.update("jax_enable_compilation_cache", True)  # off under tests
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name) if name.endswith("cache_hits") else None)
def f(x):
    with jax.named_scope(sys.argv[1]):
        for _ in range(tracing.MIN_PROGRAM_OPS):
            x = jnp.sin(x) @ x
    return x
text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
print(len(hits), sorted({v.split("/")[1] for v in tracing.program_ops(text).values() if "/" in v}))
"""


def test_a_program_from_the_compile_cache_names_the_source_that_compiled_it(
        tmp_path):
    """What docs/observability.md warns of: the persistent cache's key leaves
    metadata out, so the second process, whose source says ``fam.mlp``, is
    handed the first one's executable and its ``fam.attn``.  If this fails
    jax has changed and the warning (and PERF.md section 3) can go."""
    def run(scope):
        out = subprocess.run(
            [sys.executable, "-c", CACHED_SCOPE, scope, str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1]

    assert run("fam.attn") == "0 ['fam.attn']"
    hits, scopes_seen = run("fam.mlp").split(" ", 1)
    assert int(hits) >= 1 and scopes_seen == "['fam.attn']"
