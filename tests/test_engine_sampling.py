"""One batched sampler over the decode step's logits, one host read a step.

``sample_logits_rows`` takes per-row parameters as arrays and must give each
row what ``sample_logits`` gives that row alone; the engine reads a step's
tokens from the device once, compiles nothing for a new ``SamplingParams``
value, and a greedy request does not feel a sampled neighbour.  Nothing here
times anything.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (GPT2Config, sample_logits, sample_logits_greedy,
                            sample_logits_rows)

B, V = 6, 384
KEYS = [jax.random.PRNGKey(i) for i in range(40)]


@pytest.fixture(scope="module")
def logits():
    return 3.0 * jax.random.normal(jax.random.PRNGKey(7), (B, V), jnp.float32)


def rows(logits, key, temperature, top_k, top_p):
    tokens, _key = jax.jit(sample_logits_rows)(
        logits, key, np.asarray(temperature, np.float32),
        np.asarray(top_k, np.int32), np.asarray(top_p, np.float32))
    return np.asarray(tokens)


def support(logits_row, temperature, top_k, top_p):
    """Ids ``sample_logits`` can draw for this row alone: what it does not
    mask to -1e30, worked out on the host from its own definition."""
    scaled = np.asarray(logits_row, np.float64) / temperature
    keep = np.ones(V, bool)
    if top_k > 0:
        keep &= scaled >= np.sort(scaled)[-top_k]
    if top_p < 1.0:
        masked = np.where(keep, scaled, -np.inf)
        order = np.argsort(-masked)
        probs = np.exp(masked[order] - masked[order][0])
        cum = np.cumsum(probs / probs.sum())
        keep &= masked >= masked[order][np.argmax(cum >= top_p)]
    return set(np.flatnonzero(keep))


def test_greedy_rows_are_argmax_and_tokens_are_int32(logits):
    tokens, key = jax.jit(sample_logits_rows)(
        logits, KEYS[0], np.zeros(B, np.float32), np.zeros(B, np.int32),
        np.ones(B, np.float32))
    assert tokens.dtype == jnp.int32 and tokens.shape == (B,)
    assert np.array_equal(tokens, np.argmax(np.asarray(logits), axis=-1))
    # The key that comes back is a new one: the split happened inside.
    assert not np.array_equal(np.asarray(key), np.asarray(KEYS[0]))


def test_greedy_program_agrees_with_the_general_one(logits):
    greedy = jax.jit(sample_logits_greedy)(logits)
    assert greedy.dtype == jnp.int32
    assert np.array_equal(greedy, rows(logits, KEYS[1], [0.0] * B, [5] * B,
                                       [0.5] * B))
    assert np.array_equal(greedy, np.asarray(sample_logits(logits, KEYS[1], 0.0)))


@pytest.mark.parametrize("temperature,top_k,top_p", [
    (1.0, 2, 1.0),      # top-k alone: its two best ids
    (2.0, 0, 0.5),      # top-p alone: its nucleus
    (2.5, 8, 0.7),      # both: the nucleus of the eight best
    (1.0, V + 9, 1.0),  # a top_k beyond the vocabulary masks nothing
])
def test_sampled_rows_draw_only_from_their_own_support(
        logits, temperature, top_k, top_p):
    """Row 2 carries the parameters under test among neighbours with others;
    over many keys it draws only ids ``sample_logits`` could draw for it
    alone, and more than one of them."""
    temp = [0.0, 1.0, temperature, 0.7, 0.0, 2.0]
    ks = [0, 0, top_k, 3, 1, 0]
    ps = [1.0, 0.9, top_p, 1.0, 0.2, 1.0]
    allowed = support(logits[2], temperature, min(top_k, V), top_p)
    drawn = {int(rows(logits, key, temp, ks, ps)[2]) for key in KEYS}
    assert drawn <= allowed and len(drawn) > 1
    if top_k == 2:
        assert allowed == set(np.argsort(-np.asarray(logits[2]))[:2])
    alone = {int(sample_logits(logits[2:3], key, temperature,
                               min(top_k, V), top_p)[0]) for key in KEYS}
    assert alone <= allowed


def test_a_row_is_distributed_as_sample_logits_gives_it_alone(logits):
    """Same parameters, 400 keys each: the two empirical distributions over a
    five-id support agree (total variation; two samples of 400 from ONE
    distribution over five ids differ by about 0.05)."""
    keys = jax.random.split(jax.random.PRNGKey(11), 400)
    temp, k, p = 1.3, 5, 0.95
    batched = jax.jit(jax.vmap(
        lambda key: sample_logits_rows(
            logits, key, jnp.full(B, temp), jnp.full(B, k, jnp.int32),
            jnp.full(B, p))[0][3]))(keys)
    alone = jax.jit(jax.vmap(
        lambda key: sample_logits(logits[3:4], key, temp, k, p)[0]))(keys)
    ids = sorted(support(logits[3], temp, k, p))
    assert set(np.asarray(batched)) <= set(ids) >= set(np.asarray(alone))
    freq = lambda got: np.array([(np.asarray(got) == i).mean() for i in ids])
    assert 0.5 * np.abs(freq(batched) - freq(alone)).sum() < 0.15


def test_a_greedy_row_does_not_depend_on_its_neighbours(logits):
    want = int(np.argmax(np.asarray(logits[4])))
    for key in KEYS[:8]:
        for temp, k, p in [(1.0, 0, 1.0), (3.0, 2, 0.5), (0.0, 0, 1.0)]:
            temps = [temp] * B
            temps[4] = 0.0
            assert rows(logits, key, temps, [k] * B, [p] * B)[4] == want


# ------------------------------------------------------------------ engine
SLOTS, SEQ = 4, 64


def make_engine():
    return JaxLLMEngine(EngineConfig(
        model=GPT2Config.tiny(vocab_size=V), max_batch_size=SLOTS,
        max_seq_len=SEQ))


def drain(engine):
    """Step by hand until nothing is unfinished; the results by request."""
    done = {}
    while engine.has_unfinished():
        done.update((r["request_id"], r) for r in engine.step())
    return done


def test_one_host_read_a_decode_step_and_one_an_admission():
    engine = make_engine()
    sampled = SamplingParams(max_tokens=6, temperature=0.9, top_k=7,
                             stop_token=-1)
    for i, prompt in enumerate(["a", "bc", "def", "ghij", "klmno", "pq"]):
        engine.add_request(prompt, sampled if i % 2 else SamplingParams(
            max_tokens=3 + i, stop_token=-1))
    drain(engine)
    stats = engine.stats()
    assert stats["admitted"] == 6 and stats["decode_steps"] > 6
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    engine.step()  # nothing to admit, nothing to decode: nothing to read
    assert engine.stats()["host_syncs"] == stats["host_syncs"]
    # Mid-flight the last decode step's vector is unread: a step ahead.
    engine.add_request("again", SamplingParams(max_tokens=5, stop_token=-1))
    engine.step()
    engine.step()
    mid = engine.stats()
    assert mid["host_syncs"] == mid["decode_steps"] - 1 + mid["admitted"]
    drain(engine)
    end = engine.stats()
    assert end["host_syncs"] == end["decode_steps"] + end["admitted"]


def test_a_new_sampling_params_value_compiles_nothing():
    """Counted as ``benchmarks/lib/bench_server.py`` counts compilations in
    the measured window, and by the samplers' own caches."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    engine = make_engine()
    two = ["first prompt", "second"]
    engine.generate(two, SamplingParams(max_tokens=4, stop_token=-1))
    engine.generate(two, SamplingParams(
        max_tokens=4, temperature=0.7, top_k=5, stop_token=-1))
    assert compiles  # the listener hears this backend
    warm = len(compiles)
    # jit keys its cache by the function, so engines share it: compare sizes.
    sizes = (engine._sample_rows._cache_size(),
             engine._sample_greedy._cache_size())
    for params in [
            SamplingParams(max_tokens=5, temperature=1.1, top_p=0.9),
            SamplingParams(max_tokens=3, temperature=0.3, top_k=2, top_p=0.5),
            SamplingParams(max_tokens=4, stop_token=-1)]:
        out = engine.generate(two, params)
        assert all(1 <= o["num_generated"] <= params.max_tokens for o in out)
    assert len(compiles) == warm
    assert sizes == (engine._sample_rows._cache_size(),
                     engine._sample_greedy._cache_size())


def test_greedy_together_equals_alone_beside_a_sampled_neighbour():
    greedy = SamplingParams(max_tokens=10, stop_token=-1)
    alone = make_engine().generate(["the same prompt"], greedy)[0]["token_ids"]
    engine = make_engine()
    mine = engine.add_request("the same prompt", greedy)
    for prompt in ["neighbour one", "two", "and three"]:
        engine.add_request(prompt, SamplingParams(
            max_tokens=14, temperature=1.2, top_k=40, top_p=0.9,
            stop_token=-1))
    assert drain(engine)[mine]["token_ids"] == alone
    assert engine._sample_rows._cache_size() > 0  # the general program ran


def test_sampled_requests_keep_their_own_top_k_in_a_mixed_batch():
    """``top_k=1`` at a high temperature is greedy by another road: in one
    batch with a free-running sampled request it must reproduce the greedy
    ids, which it cannot if rows share parameters."""
    greedy = make_engine().generate(
        ["a prompt"], SamplingParams(max_tokens=8, stop_token=-1))[0]
    engine = make_engine()
    one = engine.add_request("a prompt", SamplingParams(
        max_tokens=8, temperature=5.0, top_k=1, stop_token=-1))
    engine.add_request("other", SamplingParams(
        max_tokens=8, temperature=5.0, stop_token=-1))
    assert drain(engine)[one]["token_ids"] == greedy["token_ids"]


def test_host_syncs_per_step_metric_reads_the_counts_span(tmp_path):
    """``host_syncs_per_step.serve`` as ``BENCHMARK.json`` and its metric file
    define it, on a traced run of this engine: the mean of the attribute; and
    nothing (not an error) from spans that lack it, as the parent's do."""
    import json
    import os
    import types

    from benchmarks.lib import host_spans as hs
    from benchmarks.lib import trace_reduce as tr
    from benchmarks.readers import span_stat
    from ray_tpu.util import tracing

    name = "host_syncs_per_step.serve"
    with open(os.path.join(hs.ROOT, "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["moves"] == "serve_tokens_per_s" and entry["layer"] == "engine"
    with open(os.path.join(hs.ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name and spec["reader"] == "span_stat"

    engine = make_engine()
    engine.generate(["warm"], SamplingParams(max_tokens=2, stop_token=-1))
    engine.shutdown()  # the loop that generate() started: by hand from here
    before = engine.stats()
    tracing.start_profile(str(tmp_path))
    try:
        for prompt in ["a", "bc", "def"]:
            engine.add_request(prompt, SamplingParams(max_tokens=5,
                                                      stop_token=-1))
        drain(engine)
    finally:
        tracing.stop_profile()
    now = engine.stats()
    [path] = tr.find_traces(str(tmp_path))
    trace = hs.from_planes(hs.load_host(path), {})
    ctx = types.SimpleNamespace(host_spans=[trace], trace=object(),
                                config={}, mix={}, stats={})
    got = span_stat.read(ctx, **spec["args"])
    steps = now["steps"] - before["steps"]
    assert got == (now["host_syncs"] - before["host_syncs"]) / steps
    # Every step but the first reads what the one before it sampled (the
    # last only reads); three admissions.
    assert got == (steps - 1 + 3) / steps
    for span in trace.spans("engine.counts"):
        del span.stats["host_syncs"]
    assert span_stat.read(ctx, **spec["args"]) is None


# ------------------------------------------------- one step ahead of the host
# A step's sampled tokens feed the next decode on the device and are read a
# step later; the stops the host knows by count free the row at once, a stop
# by value is seen a step late and the row rides one step for nothing.
GREEDY = dict(temperature=0.0)


def alone(prompt, params):
    """The request's result from an engine that serves nothing else."""
    return make_engine().generate([prompt], params)[0]


def value_stop(prompt, at_least=2):
    """(stop token, ids before it): a token of the request's own greedy
    stream that first shows at index >= ``at_least``, to stop it mid-way."""
    ids = alone(prompt, SamplingParams(max_tokens=24, stop_token=-1,
                                       **GREEDY))["token_ids"]
    k = next(k for k in range(at_least, len(ids)) if ids[k] not in ids[:k])
    return ids[k], ids[:k]


def test_a_mixed_batch_gives_each_request_what_it_gets_alone():
    """Admissions mid-flight, stops by ``max_tokens``, one by ``stop_token``
    in the middle of its stream, one cut at ``max_seq_len - 1``: ids, text
    and ``num_generated`` as alone; the over-run token is nowhere."""
    stop, before_stop = value_stop("stops by value")
    plan = [
        ("a", SamplingParams(max_tokens=7, stop_token=-1, **GREEDY)),
        ("stops by value", SamplingParams(max_tokens=24, stop_token=stop,
                                          **GREEDY)),
        ("x" * 50, SamplingParams(max_tokens=100, stop_token=-1, **GREEDY)),
        ("bc", SamplingParams(max_tokens=1, stop_token=-1, **GREEDY)),
        ("joins later", SamplingParams(max_tokens=9, stop_token=-1, **GREEDY)),
        ("and later still", SamplingParams(max_tokens=5, stop_token=-1,
                                           **GREEDY)),
        ("the last one in", SamplingParams(max_tokens=3, stop_token=-1,
                                           **GREEDY)),
    ]
    want = [alone(prompt, params) for prompt, params in plan]
    assert want[1]["token_ids"] == before_stop  # the stop token cut off
    assert want[1]["num_generated"] == len(before_stop) + 1
    prompt_len = len(make_engine().tokenizer.encode("x" * 50))
    assert want[2]["num_generated"] == SEQ - 1 - prompt_len  # the extent

    engine = make_engine()
    ids, done = [], {}
    for i, (prompt, params) in enumerate(plan):  # staggered over the steps
        ids.append(engine.add_request(prompt, params))
        if i >= 2:
            for _ in range(2):
                done.update((r["request_id"], r) for r in engine.step())
    done.update(drain(engine))
    for rid, expected in zip(ids, want):
        got = done[rid]
        assert got["token_ids"] == expected["token_ids"]
        assert got["text"] == expected["text"]
        assert got["num_generated"] == expected["num_generated"]
    stats = engine.stats()
    assert stats["generated_tokens"] == sum(w["num_generated"] for w in want)
    # Only the value stop rode a step too many: once.
    assert stats["overrun_row_steps"] == 1
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["retired"] == len(plan) and not engine.has_unfinished()


def test_streams_that_end_by_count_over_run_nothing():
    engine = make_engine()
    out = engine.generate(
        ["a", "bc", "def", "ghij", "klmno"],
        SamplingParams(max_tokens=5, stop_token=-1, **GREEDY))
    assert [o["num_generated"] for o in out] == [5] * 5
    assert engine.stats()["overrun_row_steps"] == 0
    engine.shutdown()


def test_a_step_dispatches_its_decode_before_it_reads_the_last_steps_tokens():
    engine = make_engine()
    calls = []
    decode, absorb = engine._decode, engine._absorb

    def spy_decode(*args):
        calls.append("decode")
        return decode(*args)

    def spy_absorb(tokens, rows):
        calls.append(("read", tokens.shape[0], [s.request_id for _, s in rows]))
        return absorb(tokens, rows)

    engine._decode, engine._absorb = spy_decode, spy_absorb
    rid = engine.add_request("abc", SamplingParams(max_tokens=3,
                                                   stop_token=-1, **GREEDY))
    want = alone("abc", SamplingParams(max_tokens=3, stop_token=-1, **GREEDY))
    # The step that prefills: the decode is dispatched behind the prefill,
    # THEN the first token is read, and it is in the mailbox when the step
    # returns (not a step later).
    assert engine.step() == [] and calls == ["decode", ("read", 1, [rid])]
    assert engine._mailboxes[rid].get_nowait() == want["token_ids"][:1]
    # A later step: its own decode first, then the vector of the step before.
    del calls[:]
    assert engine.step() == [] and calls == ["decode", ("read", SLOTS, [rid])]
    assert engine._mailboxes[rid].get_nowait() == want["token_ids"][1:2]
    # Out of tokens by count: no decode to ride, the row is free, and the
    # request is unfinished until its last token is on the host.
    assert engine.occupied() == 0 and engine.has_unfinished()
    del calls[:]
    [result] = engine.step()
    assert calls == [("read", SLOTS, [rid])]
    assert result["token_ids"] == want["token_ids"]
    assert not engine.has_unfinished()


def test_the_decode_step_compiles_once():
    """The decode program is ONE executable, compiled when the engine is
    built: admissions, decodes, an adopted-KV request and a sampled
    neighbour all run it (an operand of another kind would raise, not
    compile again).  The program that puts a first token into its operand
    compiles for the kinds of operand a first round brings and for nothing
    after."""
    from ray_tpu.collective.device_objects import device_object_store
    from ray_tpu.llm.disagg import PrefillEngine

    engine = make_engine()
    greedy = SamplingParams(max_tokens=6, stop_token=-1, **GREEDY)
    prefiller = PrefillEngine(engine.cfg)
    want = alone("adopted", greedy)["token_ids"]

    def a_round():
        engine.add_request("first", greedy)
        engine.step()
        engine.add_request("joins a running batch", SamplingParams(
            max_tokens=5, temperature=0.8, top_k=5, stop_token=-1))
        drain(engine)
        meta = prefiller.prefill("adopted", greedy)
        store = device_object_store()
        row = {"k": store.fetch(meta["k_ref"]),
               "v": store.fetch(meta["v_ref"])}
        rid = engine.add_request_from_kv(meta, row)
        assert drain(engine)[rid]["token_ids"] == want

    a_round()
    assert isinstance(engine._decode, jax.stages.Compiled)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    # jit keys its cache by the function, so engines share it: compare sizes.
    size = engine._put_first_token._cache_size()
    a_round()
    assert not compiles
    assert engine._put_first_token._cache_size() == size


def test_cancel_with_a_step_in_flight_drops_its_token_and_frees_the_row():
    greedy = SamplingParams(max_tokens=8, stop_token=-1, **GREEDY)
    engine = make_engine()
    gone = engine.add_request("cancelled mid-flight", greedy)
    stays = engine.add_request("keeps going", greedy)
    engine.step()
    engine.step()
    engine.cancel_request(gone)  # a decode that holds its row is in flight
    assert engine.occupied() == 1 and gone not in engine._mailboxes
    late = engine.add_request("takes the freed row", greedy)
    done = drain(engine)
    assert gone not in done
    assert done[stays]["token_ids"] == alone("keeps going",
                                             greedy)["token_ids"]
    assert done[late]["token_ids"] == alone("takes the freed row",
                                            greedy)["token_ids"]
    stats = engine.stats()
    assert stats["cancelled"] == 1 and stats["overrun_row_steps"] == 1
    # A request out of its row by count, its last token unread: cancelled
    # there, it never retires.
    short = engine.add_request("short", SamplingParams(
        max_tokens=2, stop_token=-1, **GREEDY))
    engine.step()
    assert engine.occupied() == 0 and engine.has_unfinished()
    engine.cancel_request(short)
    assert drain(engine) == {} and engine.stats()["cancelled"] == 2


def test_shutdown_with_a_step_in_flight_fails_the_callers_and_leaves_a_sound_engine():
    import threading

    long = SamplingParams(max_tokens=40, stop_token=-1, **GREEDY)
    engine = make_engine()
    failed = []

    def caller():
        try:
            for _delta in engine.generate_stream("shut down under me", long):
                engine.shutdown()  # from a consumer: a step is in flight
        except RuntimeError as e:
            failed.append(e)

    t = threading.Thread(target=caller)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive() and len(failed) == 1
    assert not engine.has_unfinished() and engine._unread is None
    # By hand it still serves, whatever the dropped step left in the feed.
    short = SamplingParams(max_tokens=6, stop_token=-1, **GREEDY)
    rid = engine.add_request("after the shutdown", short)
    assert drain(engine)[rid]["token_ids"] == alone(
        "after the shutdown", short)["token_ids"]
