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
    assert got == (steps + 3) / steps  # every step decodes; three admissions
    for span in trace.spans("engine.counts"):
        del span.stats["host_syncs"]
    assert span_stat.read(ctx, **spec["args"]) is None
