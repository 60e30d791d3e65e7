"""Mistral-4 family (``ray_tpu/models/mistral4*.py``) against its plain
float32 reference (``benchmarks/reference/mistral4_ref.py``: per-head keys
and values, dense ``[S, S]`` scores, YaRN and both scales written out, dense
routing, no cache, no absorption, no blocks), at tiny widths on the CPU with
seeded weights: 16 trained positions scaled by 8, so that 64-100 positions
cross ``floor(pos / 16)`` several times and turn the slowed rotary pairs by
radians; 16 routed experts, 4 a token, a shared expert.  Logits, not tokens.
Each tolerance says what it allows for.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mistral4 as bench_family
from benchmarks.reference import mistral4_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (Mistral4Config, layers, mistral4, mistral4_init,
                            model_family)
from ray_tpu.models.expert_share import (chunk_rows, runs_every_held_expert,
                                         softmax_route)
from ray_tpu.models.mla import mla_absorbed, mla_expanded

# float32 against float32: the two differ by the order of their sums only
# (blocks under an online softmax against one dense row, the absorbed
# products against the expanded ones, experts added in another order);
# logits are ~1 wide and pass through two blocks, so this is some tens of
# units in the last place (1e-6 measured; the limit leaves ten times that).
F32_TOL = 2e-5
# bfloat16 products (2^-9 a rounding, some twenty of them through two blocks
# and the head) against float32, as a share of the logits' spread: the
# benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).
BF16_TOL = 0.03


def tiny(**kw):
    return Mistral4Config.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): scale the embedding to RMS 1 and
    the matrices by 5, so that every layer moves the logits and a fault in
    one shows."""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a * 50
        return a * 5 if a.ndim >= 3 or name == "lm_head" else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(mistral4_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, **switches):
    return np.asarray(ref.mistral4_ref_logits(
        params, jnp.asarray(tokens), dict(bench_family.sizes_of(cfg),
                                          **switches),
        cfg.n_layer, cfg.expert_offset))


def rel_rms(got, want):
    """The benchmark's statistic: RMS of the difference over the vocabulary
    as a share of the reference logits' spread, worst position."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


# ------------------------------------------------------------ full forward
def test_family_resolves_and_full_forward_matches_the_reference(weights):
    """``apply`` (blocked scores, the held-experts loop or batched products,
    YaRN, both scales) is the reference's dense forward over 100 positions,
    six periods of the tiny ``rope_original_max``."""
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "mistral4" and fam.prefill_counted is not None
    toks = tokens_of(cfg, 2, 100)
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, jnp.asarray(toks))
    np.testing.assert_allclose(got, ref_logits(params, toks, cfg),
                               atol=F32_TOL)
    loss = float(fam.loss(params, jnp.asarray(toks), cfg))
    assert np.isfinite(loss) and loss > 0
    with pytest.raises(NotImplementedError):
        fam.apply(params, jnp.asarray(toks), cfg, mesh=object())


def test_the_yarn_table_and_both_scales_at_the_published_sizes():
    """Numbers, no model: the closed form of ISSUE 48 at the published
    ``rope_parameters``.  Pairs 0-12 keep their frequency, 25-31 turn 128
    times slower, a linear ramp between; ``m`` = 0.1 ln 128 + 1; ``a`` is 1
    below 8192 positions and 1 + 0.1 ln 2 from there to 16383."""
    cfg = Mistral4Config()
    assert layers.yarn_correction_range(64, 1e4, 8192, 32.0, 1.0) == (12, 25)
    table = layers.yarn_inv_freq(*mistral4.yarn_numbers(cfg))
    f = 1e4 ** (-2 * np.arange(32) / 64)
    r = np.clip((np.arange(32) - 12) / 13, 0, 1)
    np.testing.assert_allclose(table,
                               (1 - r) * f + r * f / 128, rtol=1e-6)
    np.testing.assert_allclose(table[:13], f[:13], rtol=1e-6)
    np.testing.assert_allclose(table[25:], f[25:] / 128, rtol=1e-6)
    assert mistral4.yarn_mscale(cfg) == pytest.approx(1.48520, abs=1e-5)
    a = np.asarray(mistral4.query_factor(
        jnp.asarray([0, 8191, 8192, 16383, 16384]), cfg)) / 1.48520 ** 2
    np.testing.assert_allclose(
        a, [1, 1, 1.06931, 1.06931, 1 + 0.1 * math.log(3)], rtol=1e-5)
    # the reference writes the same table from the same keys, on its own
    np.testing.assert_allclose(
        ref.inv_freq(bench_family.sizes_of(cfg)), table, rtol=1e-6)
    assert cfg.latent_dim == 320
    with pytest.raises(ValueError, match="mscale"):
        Mistral4Config(rope_mscale=0.5)
    with pytest.raises(ValueError, match="routed experts"):
        Mistral4Config(experts_held=16, expert_offset=120)


# ------------------------------------------------------- through the cache
def through_the_cache(cfg, params, toks, lengths, steps, padded_to=None):
    """``prefill`` of each row's first ``lengths[b]`` tokens (right-padded to
    ``padded_to``), then ``steps`` x ``decode_step``: logits ``[B, steps + 1,
    V]`` that predict positions ``length .. length + steps``, and the cache
    after prefill."""
    fam = model_family(cfg)
    lengths = np.asarray(lengths)
    s = padded_to or int(lengths.max())
    padded = np.zeros((len(lengths), s), np.int32)
    for b, n in enumerate(lengths):
        padded[b, :n] = toks[b, :n]
    cache = fam.init_cache(cfg, len(lengths), s + steps + 1)
    logits, cache = jax.jit(lambda p, t, n, c: fam.prefill(p, t, n, c, cfg))(
        params, jnp.asarray(padded), jnp.asarray(lengths), cache)
    after_prefill = cache
    out = [np.asarray(logits)]
    decode = jax.jit(lambda p, t, pos, c: fam.decode_step(p, t, pos, c, cfg))
    rows = np.arange(len(lengths))
    for i in range(steps):
        pos = lengths + i
        logits, cache = decode(params, jnp.asarray(toks[rows, pos]),
                               jnp.asarray(pos), cache)
        out.append(np.asarray(logits))
    return np.stack(out, 1), after_prefill


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_full_forward(dtype):
    """Prompts of 41 and 70 tokens (beyond two and four multiples of the 16
    trained positions) then 24 steps through the latent cache (across two
    more), against ONE full forward of the reference: the absorbed decode
    with the query's scale folded into the query, the deferred write, rows
    at different positions in one batch.  float32 to rounding; bfloat16
    inside the benchmark's 3 %."""
    cfg = tiny(dtype=dtype)
    params = weights_of(cfg)
    lengths, steps = [41, 70], 24
    toks = tokens_of(cfg, 2, 70 + steps + 1, seed=1)
    got, _ = through_the_cache(cfg, params, toks, lengths, steps)
    want = ref_logits(jax.tree.map(lambda a: a.astype(jnp.float32), params),
                      toks, tiny())
    for b, n in enumerate(lengths):
        rows = want[b, n - 1:n + steps]
        if dtype == "float32":
            np.testing.assert_allclose(got[b], rows, atol=F32_TOL)
        else:
            assert rel_rms(got[b], rows) < BF16_TOL


@pytest.mark.parametrize("n,rung", [(19, 32), (41, 64), (70, 128)])
def test_a_padded_prefill_gives_the_logits_and_latents_of_the_true_length(
    weights, n, rung
):
    """A prompt of ``n`` tokens prefilled at a longer rung gives the same
    logits and the same latents ``[0, n)`` as at ``n``: padded rows choose
    nothing, and the query blocks beyond the prompt's are not computed.
    Counts are of the positions ``< n``, summed over the layers."""
    cfg, params = weights
    fam = model_family(cfg)
    toks = tokens_of(cfg, 1, n, seed=n)
    padded = np.zeros((1, rung), np.int32)
    padded[0, :n] = toks[0]

    def prefill(t):
        return jax.jit(lambda p, t, c: fam.prefill_counted(
            p, t, jnp.asarray([n]), c, cfg))(
                params, jnp.asarray(t), fam.init_cache(cfg, 1, t.shape[1]))

    exact, exact_cache, exact_counts = prefill(toks)
    got, cache, counts = prefill(padded)
    np.testing.assert_allclose(got, exact, atol=F32_TOL)
    np.testing.assert_allclose(cache["latent"][:, :, :n],
                               exact_cache["latent"], atol=F32_TOL)
    routing = lambda c: {k: int(v) for k, v in c.items()  # noqa: E731
                         if k not in mistral4.LOOP_COUNT_NAMES}  # the rung's
    assert routing(counts) == routing(exact_counts)
    assert int(counts["routed_total"]) == n * cfg.top_k * cfg.n_layer


# ----------------------------------------------------------------- prefill
def attention_operands(s, seed=0, h=4, d=16, dv=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (2 * jax.random.normal(k[0], (2, s, h, d)),
            jax.random.normal(k[1], (2, s, h, d)),
            jax.random.normal(k[2], (2, s, h, dv)))


def dense_attention(q, k, v):
    s = q.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / q.shape[-1] ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("s,query_block,key_block", [
    (5, 8, 8), (29, 8, 8), (64, 16, 8), (70, 8, 16), (100, 32, 32),
    (29, 1024, 1024)])
def test_the_blocked_prefill_equals_the_dense_one(s, query_block, key_block):
    """Several blocks and a ragged last one, query blocks longer and shorter
    than key blocks, and one block that holds the whole sequence: the online
    softmax over the key blocks up to a query block's last row is the dense
    causal softmax."""
    q, k, v = attention_operands(s, seed=s)
    got = jax.jit(lambda q, k, v: layers.blocked_attention(
        q, k, v, query_block=query_block, key_block=key_block))(q, k, v)
    np.testing.assert_allclose(got, dense_attention(q, k, v), atol=F32_TOL)


def test_query_blocks_beyond_the_longest_prompt_are_not_computed():
    """``longest`` bounds the loop over query blocks: the rows of the blocks
    it reaches are the dense rows, those of the blocks wholly beyond it come
    out zero (nothing reads them)."""
    q, k, v = attention_operands(64)
    got = jax.jit(lambda q, k, v, n: layers.blocked_attention(
        q, k, v, n, query_block=8, key_block=8))(q, k, v, 19)
    np.testing.assert_allclose(got[:, :24], dense_attention(q, k, v)[:, :24],
                               atol=F32_TOL)
    assert float(jnp.abs(got[:, 24:]).max()) == 0.0


def test_the_blocked_latent_attention_equals_longcats_dense_expanded_form(
    weights
):
    """``mla_blocked`` (keys ``[kn_h | kr]`` in one product, blocks of 8)
    against ``mla.mla_expanded`` (two products, dense ``[S, S]``) on the
    same queries and latents: what was shared with LongCat's latent attention
    and what was split compute the same thing."""
    cfg, params = weights
    att = {k: params["blocks"][k][1] for k in mistral4.ATTENTION}
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 70, cfg.d_model))
    q, latent = mistral4.project(y, att, jnp.arange(70), cfg)
    got = mistral4.mla_blocked(q, latent, att, cfg, query_block=8,
                               key_block=8)
    whole = dict(att, wkv_b=jnp.concatenate([att["wk_b"], att["wv_b"]], -1))
    np.testing.assert_allclose(got, mla_expanded(q, latent, whole, cfg),
                               atol=F32_TOL)


@pytest.mark.parametrize("t", [64, 1024])
def test_the_absorbed_decode_equals_the_expanded_form_at_the_same_position(
    weights, t
):
    """One query a row against its slot's latents, absorbed (a cache of one
    extent, and one read in blocks of 512), equals the last row of the
    expanded form over the same positions: rows at 17, 40 and 63 cached
    positions, beyond one, two and three multiples of the trained 16."""
    cfg, params = weights
    att = {k: params["blocks"][k][0] for k in mistral4.ATTENTION}
    pos = np.asarray([17, 40, 63])
    y = jax.random.normal(jax.random.PRNGKey(6), (3, 64, cfg.d_model))
    q, latent = mistral4.project(y, att, jnp.arange(64), cfg)
    cache = jnp.zeros((2, 3, t, cfg.latent_dim)).at[0, :, :64].set(latent)
    rows = np.arange(3)
    got = jax.jit(lambda q, own, c, p: mla_absorbed(q, own, c, p, att, cfg))(
        q[rows, pos], latent[rows, pos], cache, jnp.asarray(pos))
    for b, n in enumerate(pos):
        want = mistral4.mla_blocked(q[b:b + 1, :n + 1], latent[b:b + 1, :n + 1],
                                    att, cfg)[0, -1]
        np.testing.assert_allclose(got[b], want, atol=F32_TOL)


# ------------------------------------------------------------------ experts
@pytest.mark.parametrize("rows", [4, 13, 150])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(weights, rows):
    """The ways the held experts run: 13 rows (a decode step whose rows, each
    on its own, would touch nearly every held expert: all of them run on
    every row in batched products), 4 rows (a decode step with ONE choice an
    expert, 4 x 4 / 16 as the cell's 32 x 4 / 128: the loop of
    ``expert_share.held_experts`` in its one-chunk form, a turn a touched
    expert on the whole batch) and 150 rows (a prefill's: the gather and the
    chunk loop).  The deployment's cut: each of
    ``n_routed_experts / experts_held`` = four chips holds a quarter of the
    experts, routes over all sixteen, sums ITS experts' part and adds the
    shared expert.  The four held parts + the shared expert COUNTED ONCE are
    the uncut reference's layer; the counts are the reference's choices
    recounted; the combine weights of a token sum to 1."""
    cfg, params = weights
    i = 1
    assert runs_every_held_expert(
        rows, cfg.top_k, cfg.n_routed_experts) == (rows == 13)
    sizes = bench_family.sizes_of(cfg)
    w = jax.tree.map(lambda a: a[i], params["blocks"])
    experts = jax.tree.map(lambda a: a[i], params["experts"])
    u = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.d_model))
    pad = min(4, rows - 2)
    live = jnp.arange(rows) != pad  # a padded row chooses no held expert
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.moe(u[None], w, experts, sizes, 0)
        shared, _ = ref.moe(u[None], w, jax.tree.map(lambda a: a[:0], experts),
                            sizes, 0)
    total, held_sum = -3 * shared[0], 0  # four shares add it four times
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = dict(params, experts=jax.tree.map(
            lambda a: a[:, offset:offset + 4], params["experts"]))
        y, counts = jax.jit(lambda u, part=part, share=share: mistral4.moe(
            u, live, part, i, share))(u)
        local = np.asarray(chosen)[0][np.asarray(live)] - offset
        held = (local >= 0) & (local < 4)
        # the loop's turns and the rows they ran, counted by hand (4 rows
        # are one chunk: a turn is a touched expert)
        turns = sum(-(-int((local[held] == e).sum()) // chunk_rows(rows))
                    for e in range(4)) if rows != 13 else 0
        if rows == 4:
            assert turns == len(np.unique(local[held]))
        assert {k: int(v) for k, v in counts.items()} == {
            "routed_total": (rows - 1) * cfg.top_k,
            "routed_held": int(held.sum()),
            "experts_touched": len(np.unique(local[held])),
            "held_chunks": turns,
            "held_chunk_rows": turns * chunk_rows(rows)}
        total, held_sum = total + y, held_sum + int(held.sum())
    assert held_sum == (rows - 1) * cfg.top_k  # every choice is somebody's
    np.testing.assert_allclose(
        np.asarray(total)[np.asarray(live)],
        np.asarray(want[0])[np.asarray(live)], atol=F32_TOL)
    # the padded row: the shared expert alone (four times less three)
    np.testing.assert_allclose(total[pad], shared[0, pad], atol=F32_TOL)
    combine = softmax_route(u, w["router"], cfg.top_k)[1]
    np.testing.assert_allclose(combine.sum(-1), 1.0, rtol=1e-5)


# ----------------------------------------------------------------- controls
# Each must FAIL the float32 tolerance, at a length where the mechanism acts
# (100 positions: the query scale is 1.07-1.19 from position 16 on, the
# slowed pairs differ from plain rotary by radians).
CONTROLS = {
    "YaRN left out": dict(yarn=False),
    "m^2 left out": dict(rope_mscale_all_dim=0),
    "the query scale left out": dict(query_scale_beta=0.0),
    "the shared expert left out": dict(shared=False),
    "the router's weights not renormalised": dict(renormalise=False),
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_a_fault_in_the_mathematics_is_outside_the_tolerance(weights, fault):
    """The program against the reference with one mechanism left out: a
    comparison that passes the program (2e-5) reads a thousand times that
    for every one of them (0.05-0.7 in the logits), and over the benchmark's
    3 % of the logits' spread for all but the query scale (0.4 % at 100
    tiny positions: the float32 tolerance is what sees it here, and the
    chip's all-layers comparison at 9,000 positions beside it)."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 100)
    got = np.asarray(model_family(cfg).apply(params, jnp.asarray(toks), cfg))
    want = ref_logits(params, toks, cfg)
    assert np.abs(got - want).max() < F32_TOL
    faulty = ref_logits(params, toks, cfg, **CONTROLS[fault])
    assert np.abs(got - faulty).max() > 1000 * F32_TOL
    # at 12 positions, below the trained 16, the query scale does nothing:
    # the control can fail only where the mechanism acts
    if fault == "the query scale left out":
        short = tokens_of(cfg, 2, 12)
        np.testing.assert_allclose(
            ref_logits(params, short, cfg, **CONTROLS[fault]),
            ref_logits(params, short, cfg), atol=F32_TOL)


def test_float8_weights_are_outside_the_tolerance_and_bfloat16_inside():
    """The lower-precision control, in the served type: bfloat16 weights
    against the float32 reference pass the benchmark's 3 %; the same
    matrices at float8's three bits of mantissa (``reduce_precision``: a
    cast pair is folded by the compiler, PERF.md section 6) fail it."""
    cfg = tiny(dtype="bfloat16", experts_held=8, expert_offset=4)
    params = weights_of(cfg, seed=2)
    toks = tokens_of(cfg, 2, 100, seed=2)
    want = ref_logits(jax.tree.map(lambda a: a.astype(jnp.float32), params),
                      toks, cfg)
    fam = model_family(cfg)
    assert rel_rms(fam.apply(params, jnp.asarray(toks), cfg), want) < BF16_TOL
    float8 = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3) if a.ndim >= 3 else a,
        params)
    assert rel_rms(fam.apply(float8, jnp.asarray(toks), cfg), want) > BF16_TOL


# ------------------------------------------------------------------ engine
PROMPTS = ["the first prompt, well beyond the trained sixteen positions",
           "second", "a third, somewhat longer prompt than the second", "four"]


def make_engine(slots=4, max_seq_len=128):
    cfg = tiny(experts_held=8, expert_offset=4)
    return JaxLLMEngine(EngineConfig(
        model=cfg, max_batch_size=slots, max_seq_len=max_seq_len, seed=7,
        param_loader=lambda: weights_of(cfg, seed=7)))


def by_hand(engine, prompts, params):
    """Step the engine by hand until the requests are done; ids in order."""
    ids = [engine.add_request(p, params) for p in prompts]
    done = {}
    while len(done) < len(ids):
        for result in engine.step():
            done[result["request_id"]] = result["token_ids"]
    return [done[i] for i in ids]


def test_engine_serves_the_latent_cache_with_no_edit_for_the_family():
    """What ``llm/engine.py`` needed for this family: nothing (`grep -in
    mistral4 ray_tpu/llm ray_tpu/serve` is empty).  A slot's second tenant
    gives the ids it gives alone (the first tenant was longer: its latents
    beyond the second's positions stay in the slot and must not be read); a
    request among full slots gives the ids it gives alone; streamed equals
    unary; the family's counts reach ``stats()``."""
    params = SamplingParams(max_tokens=24, stop_token=-1)
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    one = make_engine(slots=1)
    assert one.cache["latent"].shape == (2, 1, 128, 24)
    order = [0, 3, 2, 1]
    assert by_hand(one, [PROMPTS[i] for i in order], params) == [
        alone[i] for i in order]
    full = make_engine()
    assert by_hand(full, PROMPTS, params) == alone
    assert all(s is None for s in full.slots)
    assert [r["token_ids"] for r in full.generate(PROMPTS, params)] == alone
    for i in (0, 3):
        rid = full.add_request(PROMPTS[i], params)
        streamed = "".join(full.stream_request(rid))
        assert streamed == full.tokenizer.decode(alone[i])
    stats = full.stats()
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["routed_held"] > 0 and stats["prefill_routed_held"] > 0
    assert stats["experts_touched"] <= stats["routed_held"] < (
        stats["routed_total"])
    full.shutdown()


def test_no_engine_or_serve_module_names_the_family():
    """ROADMAP's test of the family interface, as a test."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    named = [str(p) for d in ("llm", "serve") for p in (root / d).rglob("*.py")
             if "mistral4" in p.read_text().lower()]
    assert not named


def test_the_harness_two_layer_cut_and_the_cells_draw(monkeypatch):
    """``bench_server.check_reference``'s shape for a family: ``n_layer=2``,
    ``a[:2]`` on every leaf of ``params["blocks"]`` (the held experts a
    subtree of their own, not copied), ``init_cache`` at any length, an
    UNPADDED prefill of 64 tokens and 3 decode steps.  And the draw of the
    cell's weights (``families/mistral4.py``, here at tiny widths with its
    scales): the routers read channels that no layer writes, so every
    layer's choices are those of the token's embedding alone."""
    from benchmarks.lib.bench_server import through_the_cache as harness_way

    monkeypatch.setitem(bench_family.SCALES, "router_share", 4)
    model = dict(dataclasses.asdict(tiny(
        n_layer=3, dtype="bfloat16", experts_held=8, expert_offset=4)))
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 11)
    want = jax.eval_shape(lambda: mistral4_init(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    cut = dataclasses.replace(cfg, n_layer=2)
    two = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 67, seed=3)
    got = harness_way(model_family(cut), two, cut, toks, 64, 3)
    full = np.asarray(bench_family.reference_logits(two, jnp.asarray(toks),
                                                    cut))[0]
    assert rel_rms(np.stack(got), full[63:67]) < BF16_TOL
    _, chosen = ref.mistral4_ref_logits(
        jax.tree.map(lambda a: a.astype(jnp.float32), params),
        jnp.asarray(toks), bench_family.sizes_of(cfg), 3, cfg.expert_offset,
        with_routing=True)
    routed = np.arange(cfg.d_model) < cfg.d_model // 4
    embedding = jnp.where(routed, params["wte"][toks].astype(jnp.float32), 0)
    for layer in range(3):
        logits = embedding @ params["blocks"]["router"][layer]
        assert (np.sort(np.asarray(jax.lax.top_k(logits, cfg.top_k)[1]), -1)
                == np.sort(np.asarray(chosen[layer]), -1)).all()
