"""The engine prefills at the prompt's length: a ladder of prefill programs.

``prefill_ladder(max_seq_len)`` gives the padded lengths; a prompt runs at
the smallest rung that holds it, every rung is compiled when the engine is
built, and a shorter program computes what the full-length one computes
(right-padded causal attention never lets a real position see a pad; decode
reads nothing at or beyond a slot's ``pos``).  Small float32 models on the
CPU; nothing here times anything.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from ray_tpu.llm.engine import (EngineConfig, JaxLLMEngine, SamplingParams,
                                prefill_ladder, prefill_rung)
from ray_tpu.models import GPT2Config, LlamaConfig, LongcatConfig
from ray_tpu.util import tracing

SEQ = 1024  # rungs 256, 512, 1024
SLOTS = 2
FAMILIES = {
    "gpt2": lambda: GPT2Config.tiny(
        vocab_size=384, max_seq=SEQ, dtype="float32"),
    "llama": lambda: LlamaConfig.tiny(
        vocab_size=384, max_seq=SEQ, dtype="float32"),
    "longcat": lambda: LongcatConfig.tiny(vocab_size=384, dtype="float32"),
}
LADDERS = {
    64: [64], 128: [128], 256: [256], 257: [256, 257],
    1000: [256, 512, 1000], 2048: [256, 512, 1024, 2048],
    4096: [256, 512, 1024, 2048, 4096],
}


def prompt_of(n_tokens: int, salt: int = 0) -> str:
    """A prompt the byte tokenizer turns into ``n_tokens`` ids (BOS + one a
    character), the same for the same arguments."""
    rng = np.random.default_rng(n_tokens * 7919 + salt)
    return "".join(chr(c) for c in rng.integers(97, 123, n_tokens - 1))


def make_engine(family: str = "gpt2", slots: int = SLOTS) -> JaxLLMEngine:
    return JaxLLMEngine(EngineConfig(
        model=FAMILIES[family](), max_batch_size=slots, max_seq_len=SEQ))


def greedy(n: int) -> SamplingParams:
    return SamplingParams(max_tokens=n, stop_token=-1)  # never stops early


# ------------------------------------------------------------- (a) the ladder
@pytest.mark.parametrize("max_seq_len", sorted(LADDERS))
def test_ladder_is_a_function_of_max_seq_len(max_seq_len):
    assert prefill_ladder(max_seq_len) == LADDERS[max_seq_len]


@pytest.mark.parametrize("max_seq_len", sorted(LADDERS))
def test_a_prompt_runs_at_the_smallest_rung_that_holds_it(max_seq_len):
    rungs = prefill_ladder(max_seq_len)
    # each rung's edges, and the longest prompt encode_prompt lets through
    edges = {1, max_seq_len - 1}
    edges.update(n for r in rungs for n in (r, r + 1) if n < max_seq_len)
    for n in sorted(edges):
        rung = prefill_rung(rungs, n)
        assert rung >= n and rung in rungs
        assert not any(n <= smaller < rung for smaller in rungs)
    assert all(prefill_rung(rungs, r) == r for r in rungs)  # n == rung
    assert prefill_rung(rungs, max_seq_len - 1) in rungs[-2:]


# ------------------------------------------ (b) a rung computes what 1024 does
@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family_engine(request):
    """An engine of the family, and the family's own ``prefill`` (at the
    cache's full length, one row) and ``decode_step``, jitted once."""
    engine = make_engine(request.param)
    fam, mcfg = engine.family, engine.cfg.model
    prefill = jax.jit(lambda p, t, n, c: fam.prefill(p, t, n, c, mcfg))
    decode = jax.jit(lambda p, t, pos, c: fam.decode_step(p, t, pos, c, mcfg))
    return engine, prefill, decode


def full_length(family_engine, token_ids, n_new):
    """Greedy through the family's functions at full length: (first logits
    [V], ids)."""
    engine, prefill, decode = family_engine
    n = len(token_ids)
    tokens = np.zeros((1, SEQ), np.int32)
    tokens[0, :n] = token_ids
    logits, cache = prefill(
        engine.params, jnp.asarray(tokens), jnp.asarray([n], jnp.int32),
        engine.family.init_cache(engine.cfg.model, 1, SEQ))
    first = np.asarray(logits[0])
    ids = [int(first.argmax())]
    for i in range(n_new - 1):
        logits, cache = decode(
            engine.params, jnp.asarray(ids[-1:], jnp.int32),
            jnp.asarray([n + i], jnp.int32), cache)
        ids.append(int(np.asarray(logits[0]).argmax()))
    return first, ids


@pytest.mark.parametrize("n_tokens,rung", [
    (40, 256), (256, 256), (257, 512), (512, 512), (700, 1024)])
def test_a_rung_agrees_with_the_full_length_prefill(family_engine, n_tokens,
                                                    rung):
    engine = family_engine[0]
    prompt = prompt_of(n_tokens)
    token_ids = engine.tokenizer.encode(prompt)
    assert len(token_ids) == n_tokens
    assert prefill_rung(engine._prefill_rungs, n_tokens) == rung
    want_logits, want_ids = full_length(family_engine, token_ids, 9)

    tokens = np.zeros(rung, np.int32)
    tokens[:n_tokens] = token_ids
    logits, engine.cache, _counts = engine._prefill_one[rung](
        engine.params, engine.cache, jnp.asarray(tokens),
        np.int32(n_tokens), np.int32(1))
    # float32: the masked tail of a softmax row adds exact zeros, so only
    # the order of sums may differ.
    err = np.abs(np.asarray(logits[0]) - want_logits).max()
    assert err <= 1e-4 * want_logits.std()

    [out] = engine.generate([prompt], greedy(9))
    assert out["token_ids"] == want_ids  # the first id and the next eight


# ------------------------------------------------- (c) a slot's stale tail
def test_a_short_prompt_after_a_long_one_in_the_same_slot():
    """The 40-token request's row covers positions [0, 256) of the slot; the
    700-token tenant's keys beyond stay there, and are never read: the ids
    are those the engine gave while it was fresh."""
    engine = make_engine(slots=1)
    short = prompt_of(40, salt=1)
    fresh = engine.generate([short], greedy(24))[0]["token_ids"]
    engine.generate([prompt_of(700)], greedy(6))
    assert engine.generate([short], greedy(24))[0]["token_ids"] == fresh


# --------------------------------- (d) nothing compiles after construction
@pytest.fixture(scope="module")
def compiles():
    """Every backend compilation of this process from here on, by name."""
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_kw: seen.append(name)
        if name.endswith("backend_compile_duration") else None)
    return seen


@pytest.mark.parametrize("warm_with", [40, 700])
def test_no_rung_compiles_after_construction(compiles, warm_with):
    """The first request compiles the decode step and the samplers (not
    this file's matter); a request on every OTHER rung after it meets no
    compiler, whichever rung came first."""
    engine = make_engine()
    engine.generate([prompt_of(warm_with)], greedy(3))
    before = len(compiles)
    for n_tokens in (40, 300, 700, SEQ - 1):
        engine.generate([prompt_of(n_tokens)], greedy(3))
    assert len(compiles) == before


# ------------------------------------ (e) padded_len is the rung, everywhere
def test_admit_span_and_stats_count_the_rung(tmp_path):
    engine = make_engine()
    lengths = [40, 256, 257, 700, 1023]
    rungs = [256, 256, 512, 1024, 1024]
    tracing.start_profile(str(tmp_path))
    try:
        engine.generate([prompt_of(n) for n in lengths], greedy(2))
    finally:
        tracing.stop_profile()
    [path] = tr.find_traces(str(tmp_path))
    admits = hs.from_planes(hs.load_host(path), {}).spans("engine.admit")
    assert sorted((int(s.stats["prompt_len"]), int(s.stats["padded_len"]))
                  for s in admits) == list(zip(lengths, rungs))
    stats = engine.stats()
    assert stats["prompt_tokens"] == sum(lengths)
    assert stats["padded_prompt_tokens"] == sum(rungs)


def test_an_engine_of_256_or_less_has_one_rung():
    """Every tiny engine: today's behaviour, the one program at
    ``max_seq_len``."""
    engine = JaxLLMEngine(EngineConfig(
        model=GPT2Config.tiny(vocab_size=384), max_batch_size=2,
        max_seq_len=128))
    assert list(engine._prefill_one) == [128]
    engine.generate(["hello"], greedy(2))
    assert engine.stats()["padded_prompt_tokens"] == 128
