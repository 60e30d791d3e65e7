"""LongCat family (``ray_tpu/models/longcat*.py``) against its plain float32
reference (``benchmarks/reference/longcat_ref.py``), at tiny widths on the
CPU with seeded weights: 2 double layers, 8 routed + 4 identity experts, 3 a
token.  Each tolerance says what it allows for.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import longcat as bench_family
from benchmarks.reference.longcat_ref import longcat_ref_logits
from benchmarks.reference.longcat_ref import moe as ref_moe
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import LongcatConfig, longcat_init, model_family
from ray_tpu.models import longcat
from ray_tpu.models.expert_share import chunk_rows

# float32 against float32: the two differ by the order of their sums only
# (absorbed against expanded attention, experts added in another order);
# logits are ~0.5 wide, so this is a few units in the last place.
F32_TOL = 2e-5


def tiny(**kw):
    return LongcatConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def ref_logits(params, tokens, cfg, **kw):
    return longcat_ref_logits(params, jnp.asarray(tokens), sizes_of(cfg),
                              cfg.n_layer, cfg.expert_offset, **kw)


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, longcat_init(jax.random.PRNGKey(0), cfg)


def layer_of(params, layer):
    return (jax.tree.map(lambda a: a[layer], params["blocks"]),
            jax.tree.map(lambda a: a[layer], params["experts"]))


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def test_family_resolves_and_full_forward_matches_the_reference(weights):
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "longcat" and fam.prefill_counted is not None
    toks = tokens_of(cfg, 3, 20)
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, toks)
    want = ref_logits(params, toks, cfg)
    assert got.shape == (3, 20, cfg.vocab_size)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    loss = fam.loss(params, tokens_of(cfg, 2, 9), cfg)
    assert np.isfinite(float(loss)) and float(loss) > np.log(cfg.vocab_size) - 1


def through_the_latent_cache(cfg, params, toks, lengths, steps):
    """Ragged batch: prefill each row's first ``lengths[b]`` tokens, then
    ``steps`` decode steps at each row's own position.  Returns the logits
    that predict positions ``lengths[b] + i`` and the routing counts."""
    fam = model_family(cfg)
    cache = fam.init_cache(cfg, len(lengths), toks.shape[1] + 1)
    lengths = np.asarray(lengths, np.int32)
    padded = np.where(np.arange(toks.shape[1])[None] < lengths[:, None],
                      toks, 0)
    logits, cache, counts = jax.jit(
        lambda p, t, n, c: fam.prefill_counted(p, t, n, c, cfg)
    )(params, padded, lengths, cache)
    out, all_counts = [np.asarray(logits)], [counts]
    decode = jax.jit(
        lambda p, t, pos, c: fam.decode_step_counted(p, t, pos, c, cfg))
    rows = np.arange(len(lengths))
    for i in range(steps):
        pos = lengths + i
        logits, cache, counts = decode(params, toks[rows, pos], pos, cache)
        out.append(np.asarray(logits))
        all_counts.append(counts)
    return np.stack(out, 1), all_counts  # [B, steps + 1, V]


def recount(chosen, cfg):
    """Routing counts of some tokens from the reference's choices
    ``[L, N, k]`` (N tokens that one program ran together)."""
    local = np.asarray(chosen) - cfg.expert_offset
    held = (local >= 0) & (local < cfg.experts_held)
    touched = sum(len(np.unique(layer[mask])) for layer, mask
                  in zip(local, held))
    return {"routed_total": int(np.asarray(chosen).size),
            "routed_zero": int((np.asarray(chosen)
                                >= cfg.n_routed_experts).sum()),
            "routed_held": int(held.sum()), "experts_touched": int(touched)}


def as_ints(counts):
    """The routing counts (the loop's own, ``expert_share.loop_counts``, are
    counted by hand in ``tests/test_expert_share.py``)."""
    return {k: int(v) for k, v in counts.items()
            if k not in longcat.LOOP_COUNT_NAMES}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_full_forward(dtype):
    # Half the routed experts are held, so absent, held and identity
    # experts are all chosen.
    cfg = tiny(dtype=dtype, experts_held=4, expert_offset=2)
    params = longcat_init(jax.random.PRNGKey(1), cfg)
    lengths, steps = [5, 9, 12], 3
    toks = tokens_of(cfg, 3, 16, seed=1)
    got, counts = through_the_latent_cache(cfg, params, toks, lengths, steps)
    ref, chosen = ref_logits(params, toks, cfg, with_routing=True)
    want = np.stack([np.asarray(ref[b, n - 1:n + steps])
                     for b, n in enumerate(lengths)])
    rms = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    chosen = np.asarray(chosen)  # [L, B, S, k]
    prompt = np.concatenate(
        [chosen[:, b, :n] for b, n in enumerate(lengths)], 1)
    want_counts = recount(prompt, cfg)
    if dtype == "float32":
        assert np.abs(got - want).max() < F32_TOL
        assert as_ints(counts[0]) == want_counts
        for i in range(steps):
            step = np.stack([chosen[:, b, n + i]
                             for b, n in enumerate(lengths)], 1)
            assert as_ints(counts[1 + i]) == recount(step, cfg)
    else:
        # The benchmark's measure (root mean square over the vocabulary as
        # a share of the logits' spread) and its limit, 3 %: bf16 rounds to
        # 2^-9 after each of some thirty operations of two double layers;
        # 0.3-0.8 % here.  A near-tie among a token's choices may fall the
        # other way in bf16: a flip shows as a count that differs from the
        # reference's, and costs that token the least of its three weights.
        # Of 26 prompt tokens x 2 layers x 3 choices a few may flip; more
        # than 6 would mean the router does not run in float32.
        assert rms.max() < 0.03, rms
        got_counts = as_ints(counts[0])
        flips = sum(abs(got_counts[k] - want_counts[k])
                    for k in ("routed_zero", "routed_held"))
        assert got_counts["routed_total"] == want_counts["routed_total"]
        assert flips <= 6, (got_counts, want_counts)


def test_four_shares_and_the_identity_part_once_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2: what each share's held experts give, plus
    what every chip computes alike (the identity experts) counted once,
    equals the uncut reference's MoE(u)."""
    cfg = tiny()
    params = longcat_init(jax.random.PRNGKey(2), cfg)
    blk, experts = layer_of(params, 1)
    u = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    live = jnp.ones(40, bool)

    def share(offset, held):
        c = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
        mine = jax.tree.map(lambda a: a[:, offset:offset + held],
                            params["experts"])
        return longcat.moe(u, live, blk["router"], blk["router_bias"],
                           mine, 1, c)

    identity, none = share(0, 0)
    assert int(none["routed_held"]) == 0 and int(none["experts_touched"]) == 0
    total, held_choices = identity, 0
    for offset in (0, 2, 4, 6):
        part, counts = share(offset, 2)
        total = total + (part - identity)
        held_choices += int(counts["routed_held"])
    want, chosen = ref_moe(u[None], blk, experts, sizes_of(cfg), 0)
    assert float(jnp.abs(total - want[0]).max()) < F32_TOL
    # Every choice fell on exactly one share or on an identity expert.
    assert held_choices + int(none["routed_zero"]) == chosen.size


def test_dropless_and_batch_independent_under_adversarial_routing(weights):
    """Every token of 8 rows is forced onto held expert 0 (a capacity would
    drop most of them; 320 prompt tokens are three chunks of that expert's
    loop): a row's logits among the 8 equal its logits alone, up to float32
    rounding (the CPU's matrix product may block 8 rows and 1 row
    differently; on the chip the programs have one shape)."""
    cfg, params = weights
    bias = params["blocks"]["router_bias"].at[:, 0].set(10.0)
    params = dict(params, blocks=dict(params["blocks"], router_bias=bias))
    toks = tokens_of(cfg, 8, 44, seed=4)
    lengths = [40] * 8
    assert sum(lengths) > 2 * longcat.EXPERT_CHUNK
    together, counts = through_the_latent_cache(cfg, params, toks, lengths, 2)
    assert int(counts[0]["routed_held"]) >= sum(lengths) * cfg.n_layer
    for row in (0, 5):
        alone, _ = through_the_latent_cache(
            cfg, params, toks[row:row + 1], lengths[:1], 2)
        assert np.abs(alone[0] - together[row]).max() < 1e-6
    ref = ref_logits(params, toks, cfg)
    assert np.abs(together[:, 0] - np.asarray(ref[:, 39])).max() < F32_TOL


def test_the_cache_holds_576_values_a_token_an_attention():
    cfg = LongcatConfig(n_layer=4, experts_held=16, vocab_size=16384)
    fam = model_family(cfg)
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, 32, 2048))
    assert cfg.latent_dim == 576
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (8, 32, 2048, 576)}  # 2 attentions a double layer
    nbytes = sum(np.prod(v.shape) * v.dtype.itemsize for v in cache.values())
    assert nbytes == 32 * 2048 * 8 * 576 * 2 == 603_979_776
    # Expanded keys and values would be 64 heads x (192 + 128): 36 x more.
    assert 64 * (192 + 128) / cfg.latent_dim > 35
    small = jax.eval_shape(lambda: fam.init_cache(tiny(), 1, 68))
    assert small["latent"].shape == (4, 1, 68, 24)


def test_identity_experts_cost_one_multiply_add_and_touch_no_weight():
    """A token whose choices are all identity experts gets ``sum w_i u``
    exactly, whatever the expert weights hold (here: NaN)."""
    cfg = tiny()
    params = longcat_init(jax.random.PRNGKey(5), cfg)
    blk, _ = layer_of(params, 0)
    bias = blk["router_bias"].at[cfg.n_routed_experts:].set(10.0)
    poisoned = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan),
                            params["experts"])
    u = jax.random.normal(jax.random.PRNGKey(6), (7, cfg.d_model))
    y, counts = jax.jit(
        lambda u: longcat.moe(u, jnp.ones(7, bool), blk["router"], bias,
                              poisoned, 0, cfg))(u)
    sel, w = longcat.route(u, blk["router"], bias, cfg)
    assert bool((sel >= cfg.n_routed_experts).all())
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(w.sum(-1, keepdims=True) * u))
    assert as_ints(counts) == {
        "routed_total": 7 * cfg.top_k, "routed_zero": 7 * cfg.top_k,
        "routed_held": 0, "experts_touched": 0}


PROMPTS = ["the first prompt", "second", "a third, somewhat longer prompt"]


def make_engine(slots=4):
    cfg = tiny(experts_held=4, expert_offset=2)
    return JaxLLMEngine(EngineConfig(model=cfg, max_batch_size=slots,
                                     max_seq_len=64, seed=7))


def test_engine_serves_the_family_and_counts_its_routing():
    engine = make_engine()
    cfg = engine.cfg.model
    params = SamplingParams(max_tokens=6, stop_token=-1)
    zero = engine.stats()
    assert all(zero[k] == zero["prefill_" + k] == 0
               for k in longcat.COUNT_NAMES)
    # Alone, one after the other: the counts can be recounted exactly.
    alone = [engine.generate([p], params)[0]["token_ids"] for p in PROMPTS]
    stats = engine.stats()
    # Drained: every decode step's vector has been read, a step after it.
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0
    want = {k: 0 for k in zero if "routed" in k or "touched" in k}
    for prompt, generated in zip(PROMPTS, alone):
        ids = engine.tokenizer.encode(prompt)
        # The last generated token is never fed to a decode step.
        fed = np.asarray([ids + generated[:-1]], np.int32)
        _, chosen = ref_logits(engine.params, fed, cfg, with_routing=True)
        chosen = np.asarray(chosen)[:, 0]  # [L, S, k]
        for k, v in recount(chosen[:, :len(ids)], cfg).items():
            want["prefill_" + k] += v
        for pos in range(len(ids), fed.shape[1]):  # one token a step
            for k, v in recount(chosen[:, pos:pos + 1], cfg).items():
                want[k] += v
    assert {k: stats[k] for k in want} == want
    assert stats["routed_zero"] > 0 and stats["routed_held"] > 0
    # Together, streamed and unary give the same ids as alone.
    together = engine.generate(PROMPTS, params)
    assert [r["token_ids"] for r in together] == alone
    streamed = "".join(engine.generate_stream(PROMPTS[2], params))
    assert streamed == together[2]["text"]
    after = engine.stats()
    assert after["host_syncs"] == after["decode_steps"] + after["admitted"]
    assert after["routed_total"] > stats["routed_total"]


def stepped(engine, prompt, params):
    """One request through an engine stepped by hand; its result."""
    rid = engine.add_request(prompt, params)
    while True:
        for result in engine.step():
            if result["request_id"] == rid:
                return result


def test_a_row_that_over_ran_gives_its_next_tenant_what_a_fresh_engine_gives():
    """A stop by value reaches the host a step late, so the row rides one
    decode more, which writes a latent beyond the stream's end.  The next
    tenant's prefill replaces the row: its ids are a fresh engine's."""
    free = SamplingParams(max_tokens=16, stop_token=-1)
    ids = stepped(make_engine(slots=1), PROMPTS[2], free)["token_ids"]
    k = next(k for k in range(2, len(ids)) if ids[k] not in ids[:k])
    engine = make_engine(slots=1)
    first = stepped(engine, PROMPTS[2],
                    SamplingParams(max_tokens=16, stop_token=ids[k]))
    assert first["token_ids"] == ids[:k] and first["num_generated"] == k + 1
    assert engine.has_unfinished()  # the step it rode for nothing, unread
    second = stepped(engine, PROMPTS[0], free)
    assert second["token_ids"] == stepped(
        make_engine(slots=1), PROMPTS[0], free)["token_ids"]
    stats = engine.stats()
    assert stats["overrun_row_steps"] == 1
    assert stats["generated_tokens"] == k + 1 + 16
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]


def test_engine_counts_span_carries_the_routing_one_step_late(tmp_path):
    from ray_tpu.util import tracing

    engine = make_engine(slots=2)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    engine.generate(["warm"], params)
    before = engine.stats()
    seen = []
    real = tracing.host_span

    def spy(name, **attrs):
        if name == "engine.counts":
            seen.append(attrs)
        return real(name, **attrs)

    import ray_tpu.llm.engine as engine_module
    engine_module.host_span = spy
    try:
        engine.generate(PROMPTS[:2], params)
    finally:
        engine_module.host_span = real
    after = engine.stats()
    assert seen and all(set(longcat.COUNT_NAMES) <= set(a) for a in seen[1:])
    for name in longcat.COUNT_NAMES:
        written = sum(a.get(name, 0) for a in seen)
        grown = after[name] - before[name]
        # One step late: the last decode step's counts are not written yet.
        assert 0 < written <= grown
        assert grown - written <= 2 * cfg_choices(engine)
    # The vector of the step before, and a first token an admission.
    assert all(a["host_syncs"] <= 1 + a["admitted"] for a in seen)
    assert all(a["overrun"] == 0 for a in seen)  # streams end by count


def test_engine_counts_span_carries_a_prefills_counts_under_stats_names(
        monkeypatch):
    """What a prefill counted is written on ``engine.counts`` with
    ``prefill_`` before the family's names, as ``stats()`` has them: every
    prefill of the run is on some span by its end (a step folds the runs
    dispatched before it), spans that folded none carry none, and the rows
    the loop's chunks ran hold the choices they were run for
    (``prefill_chunk_fill_pct.serve`` reads the two)."""
    import ray_tpu.llm.engine as engine_module

    engine = make_engine(slots=2)
    params = SamplingParams(max_tokens=6, stop_token=-1)
    engine.generate(["warm"], params)
    before = engine.stats()
    seen = []
    real = engine_module.host_span

    def spy(name, **attrs):
        if name == "engine.counts":
            seen.append(attrs)
        return real(name, **attrs)

    monkeypatch.setattr(engine_module, "host_span", spy)
    engine.generate(PROMPTS[:3], params)
    after = engine.stats()
    names = ["prefill_" + name for name in longcat.COUNT_NAMES]
    folded = [a for a in seen if names[0] in a]
    assert 0 < len(folded) <= 3 < len(seen)
    assert all(set(names) <= set(a) for a in folded)
    assert not any(set(names) & set(a) for a in seen if a not in folded)
    for name in names:
        assert sum(a[name] for a in folded) == after[name] - before[name] > 0
    held = sum(a["prefill_routed_held"] for a in folded)
    rows = sum(a["prefill_held_chunk_rows"] for a in folded)
    assert 0 < held <= rows
    assert rows == chunk_rows(64) * sum(  # max_seq_len 64: its one rung
        a["prefill_held_chunks"] for a in folded)


def cfg_choices(engine):
    cfg = engine.cfg.model
    return cfg.top_k * cfg.n_layer * engine.cfg.max_batch_size


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16", experts_held=4))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(
        lambda: longcat_init(jax.random.PRNGKey(0), LongcatConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    router = np.asarray(params["blocks"]["router"])
    assert abs(router.std() - bench_family.ROUTER_SCALE) < 0.005


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_a_latent_cache_by_name(kind):
    """Both ends of the hand-over refuse when they are BUILT: nothing has
    been prefilled, fetched or freed yet."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "longcat" in str(err.value) and "latent" in str(err.value)
