"""Laguna family (``ray_tpu/models/laguna*.py``) against its plain float32
reference (``benchmarks/reference/laguna_ref.py``: dense ``[S, S]`` scores
with the window as a mask, keys and values repeated to the query heads, both
rotary tables and the gate written out, dense routing, no cache, no ring, no
tiles), at tiny widths on the CPU with seeded weights: 12 query heads in a
full layer and 18 in a window layer over 2 key-value heads (the published
GROUPS of 6 and 9), a window of 8 and sixteen trained positions scaled by 8,
so that 64-100 positions wrap the ring a dozen times and turn the slowed
rotary pairs by radians; 16 routed experts, 4 a token, a shared expert.
Logits, not tokens.  Each tolerance says what it allows for.
"""

import dataclasses
import json
import pathlib
import unittest.mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import laguna as bench_family
from benchmarks.reference import laguna_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (LagunaConfig, laguna, laguna_init, layers,
                            model_family)
from ray_tpu.models.expert_share import (chunk_rows, runs_every_held_expert,
                                         sigmoid_route)

# float32 against float32: the two differ by the order of their sums only
# (tiles under an online softmax against one dense row, a ring's one softmax
# against the masked row, experts added in another order); logits are ~1
# wide and pass through five blocks, so this is some tens of units in the
# last place (1e-6 measured; the limit leaves ten times that).
F32_TOL = 2e-5
# bfloat16 products (2^-9 a rounding, some fifty of them through five blocks
# and the head) against float32, as a share of the logits' spread: the
# benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 3072).
BF16_TOL = 0.03
CONFIG = json.loads((pathlib.Path(__file__).parent.parent / "benchmarks"
                     / "configs" / "laguna_s21_l9_ep16.json").read_text())


def tiny(**kw):
    return LagunaConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): scale the embedding to RMS 1 and
    the matrices by 5 (the gate's by 25: pre-activations of spread 2), so
    that every layer moves the logits and a fault in one shows."""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a * 50
        if name == "wg":
            return a * 25
        return a * 5 if a.ndim >= 3 or name == "lm_head" else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(laguna_init(jax.random.PRNGKey(seed), cfg))


def cell_draw(seed, **kw):
    """The cell's draw (``families/laguna.py``: the routers read channels no
    layer writes, so no near-tied choice flips on rounding; with the
    family's init a flipped fourth choice moves a position's logits by 5 %)
    at tiny widths in the served type, d 256 for its sixteenth of router
    channels."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16", **kw)),
                 d_model=256)
    return bench_family.config(model), bench_family.load_params(model, seed)


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, **switches):
    return np.asarray(ref.laguna_ref_logits(
        params, jnp.asarray(tokens),
        dict(bench_family.sizes_of(cfg), **switches), cfg.attn_kinds,
        cfg.mlp_kinds, cfg.expert_offset))


def rel_rms(got, want):
    """The benchmark's statistic: RMS of the difference over the vocabulary
    as a share of the reference logits' spread, worst position."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


# ------------------------------------------------------------ full forward
def test_family_resolves_and_full_forward_matches_the_reference(weights):
    """``apply`` (tiled scores over grouped heads, the band, both rotary
    tables, the gate, the held-experts loop, the shared expert) is the
    reference's dense forward over 100 positions: a dozen windows, six
    periods of the tiny ``rope_original_max``."""
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "laguna" and fam.prefill_counted is not None
    toks = tokens_of(cfg, 2, 100)
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, jnp.asarray(toks))
    want = ref_logits(params, toks, cfg)
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    loss = float(fam.loss(params, jnp.asarray(toks), cfg))
    assert np.isfinite(loss) and loss > 0
    with pytest.raises(NotImplementedError):
        fam.apply(params, jnp.asarray(toks), cfg, mesh=object())
    axes, shapes = fam.param_axes(), jax.eval_shape(lambda: params)
    assert jax.tree.structure(axes) == jax.tree.structure(shapes)
    assert all(len(a) == len(s.shape) for a, s in zip(
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)), jax.tree.leaves(shapes)))


def test_the_published_patterns_and_head_counts_are_the_configs():
    """``layer_types``, ``mlp_layer_types`` and
    ``num_attention_heads_per_layer`` of the catalog's row, as the family's
    letters and its two head counts; the cell's ``model`` is the published
    widths with nine layers, sixteen held experts and an eighth of the
    vocabulary."""
    pub = CONFIG["published"]
    letters = {"full_attention": "F", "sliding_attention": "W",
               "dense": "D", "sparse": "E"}
    assert "".join(letters[t] for t in pub["layer_types"]) == (
        laguna.PUBLISHED_ATTN)
    assert "".join(letters[t] for t in pub["mlp_layer_types"]) == (
        laguna.PUBLISHED_MLP)
    cfg = LagunaConfig()
    assert [cfg.heads(k) for k in cfg.attn_kinds] == (
        pub["num_attention_heads_per_layer"])
    assert (cfg.d_model, cfg.n_kv_head, cfg.head_dim, cfg.window, cfg.d_ff,
            cfg.d_expert, cfg.n_routed_experts, cfg.top_k, cfg.vocab_size) == (
        pub["hidden_size"], pub["num_key_value_heads"], pub["head_dim"],
        pub["sliding_window"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["vocab_size"])
    cell = bench_family.config(CONFIG["model"])
    assert dataclasses.replace(
        cell, n_layer=48, experts_held=256, vocab_size=100352,
        attn_pattern=laguna.PUBLISHED_ATTN,
        mlp_pattern=laguna.PUBLISHED_MLP) == cfg
    assert laguna.PUBLISHED_ATTN.startswith(cell.attn_pattern)
    assert laguna.PUBLISHED_MLP.startswith(cell.mlp_pattern)
    assert (cell.attn_kinds, cell.mlp_kinds) == ("FWWWFWWWF", "DEEEEEEEE")
    assert [r[0] + (r[3],) for r in laguna.layer_runs(cell)] == [
        ("F", "D", 1), ("W", "E", 3), ("F", "E", 1), ("W", "E", 3),
        ("F", "E", 1)]
    assert [r[1:3] for r in laguna.layer_runs(cell)] == [
        (0, 0), (0, 0), (1, 3), (3, 4), (2, 7)]
    with pytest.raises(ValueError):
        LagunaConfig(attn_pattern="FW", mlp_pattern="DEE", n_layer=2)
    with pytest.raises(ValueError, match="routed experts"):
        LagunaConfig(experts_held=16, expert_offset=250)
    with pytest.raises(ValueError, match="whole groups"):
        LagunaConfig(n_head_window=70)


def test_the_yarn_table_at_the_published_sizes():
    """Numbers, no model: the closed form of ISSUE 52 at the published
    ``rope_parameters.full_attention`` over the 32 pairs of the 64 rotated
    dimensions.  Pairs 0-9 keep their frequency, 18-31 turn 128 times
    slower, a linear ramp between; the attention factor is ``0.1 ln 128 +
    1``; the sliding layers' table is the plain one."""
    cfg = LagunaConfig()
    numbers = (cfg.rotary_dim, cfg.rope_theta, cfg.rope_original_max,
               cfg.rope_beta_fast, cfg.rope_beta_slow)
    assert numbers == (64, 5e5, 8192, 32.0, 1.0)
    assert layers.yarn_correction_range(*numbers) == (9, 18)
    table = layers.yarn_inv_freq(64, 5e5, 128.0, 8192, 32.0, 1.0)
    f = 5e5 ** (-2 * np.arange(32) / 64)
    r = np.clip((np.arange(32) - 9) / 9, 0, 1)
    np.testing.assert_allclose(table, (1 - r) * f + r * f / 128, rtol=1e-6)
    np.testing.assert_allclose(table[:10], f[:10], rtol=1e-6)
    np.testing.assert_allclose(table[18:], f[18:] / 128, rtol=1e-6)
    assert cfg.rope_attention_factor == pytest.approx(
        0.1 * np.log(128) + 1, abs=1e-12)
    assert cfg.rope_attention_factor == pytest.approx(1.4852030, abs=1e-7)
    # the reference writes the same tables from the same keys, on its own
    sizes = bench_family.sizes_of(cfg)
    np.testing.assert_allclose(ref.full_inv_freq(sizes), table, rtol=1e-6)
    inv, factor, on_scores = ref.rotary_table("W", sizes)
    np.testing.assert_allclose(inv, 1e4 ** (-2 * np.arange(64) / 128),
                               rtol=1e-6)
    assert (factor, on_scores) == (1.0, 1.0)
    # and the program's rotary is the reference's, kind by kind
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 100, 3, 128))
    for kind in "FW":
        inv, factor, _ = ref.rotary_table(kind, sizes)
        np.testing.assert_allclose(
            laguna.rotary(x, jnp.arange(100), kind, cfg),
            ref._rope(x, inv, factor), atol=1e-5)


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
def test_prefill_then_decode_through_a_wrapped_ring_matches_full_forward(
    dtype
):
    """Prompts of 5 (inside the window), 41 and 70 tokens then 24 steps
    through the cache (the rings wrap three times more), against ONE full
    forward of the reference: a ring's one softmax with the current token
    beside it, the full layers' read, groups of 6 and 9, the gate on one
    token, the deferred write at ``pos mod 8``, rows at different positions
    in one batch.  float32 to rounding; bfloat16 (the cell's draw) inside
    the benchmark's 3 % (0.6 % measured)."""
    cfg, params = (tiny(), weights_of(tiny())) if dtype == "float32" else (
        cell_draw(3000000023))
    lengths, steps = [5, 41, 70], 24
    toks = tokens_of(cfg, 3, 70 + steps + 1, seed=1)
    got, cache = through_the_cache(cfg, params, toks, lengths, steps)
    assert cache["k"].shape == (2, 3, 2, 95, 16)
    assert cache["v_win"].shape == (3, 3, 2, 8, 16)
    want = ref_logits(params, toks, cfg)
    for b, n in enumerate(lengths):
        rows = want[b, n - 1:n + steps]
        if dtype == "float32":
            np.testing.assert_allclose(got[b], rows, atol=F32_TOL)
        else:
            assert rel_rms(got[b], rows) < BF16_TOL


@pytest.mark.parametrize("n_layer", [9, 7, 13])
def test_a_group_of_runs_that_repeats_is_one_loop_and_keeps_the_layers_order(
    n_layer
):
    """The cell's nine layers (``FWWWFWWWF``: ``[FD]`` then ``[WE x 3, FE] x
    2``, a scan of three in a scan of two), seven (the second period cut
    short: nothing repeats) and thirteen (three periods): the forward is the
    reference's, and the keys and values it leaves are in the LAYERS' order
    of each kind, because six decode steps through them give the
    reference's logits too."""
    cfg = tiny(attn_pattern="FWWW" * 4, mlp_pattern="D" + "E" * 15,
               n_layer=n_layer)
    plan = [([kinds + (layers,) for kinds, _, _, layers in group], repeats)
            for group, repeats in laguna.layer_plan(cfg)]
    assert plan == {
        9: [([("F", "D", 1)], 1), ([("W", "E", 3), ("F", "E", 1)], 2)],
        7: [([("F", "D", 1)], 1), ([("W", "E", 3)], 1), ([("F", "E", 1)], 1),
            ([("W", "E", 2)], 1)],
        13: [([("F", "D", 1)], 1), ([("W", "E", 3), ("F", "E", 1)], 3)],
    }[n_layer]
    params = weights_of(cfg)
    toks = tokens_of(cfg, 2, 36, seed=n_layer)
    got, cache = through_the_cache(cfg, params, toks, [19, 30], 6)
    assert cache["k"].shape[0] == cfg.attn_kinds.count("F")
    assert cache["k_win"].shape[0] == cfg.attn_kinds.count("W")
    want = ref_logits(params, toks, cfg)
    for b, n in enumerate([19, 30]):
        np.testing.assert_allclose(got[b], want[b, n - 1:n + 6],
                                   atol=2 * F32_TOL)  # up to 13 blocks deep


@pytest.mark.parametrize("n", [3, 8, 9, 19, 41])
def test_a_padded_prefill_gives_the_logits_keys_and_rings_of_the_true_length(
    weights, n
):
    """A prompt of ``n`` tokens (inside the window, at its edge, one past it,
    two and five wraps on) prefilled at a rung of 64 gives the same logits,
    the same keys and values ``[0, n)`` and the same RINGS as at ``n``: the
    last 8 TRUE positions at ``p mod 8``, not the rung's tail; padded rows
    choose nothing, and the query tiles beyond the prompt's are not
    computed.  Counts are of the positions ``< n``, over the expert
    layers."""
    cfg, params = weights
    fam = model_family(cfg)
    toks = tokens_of(cfg, 1, 64, seed=n)  # the padding is not zeros

    def prefill(t):
        return jax.jit(lambda p, t, c: fam.prefill_counted(
            p, t, jnp.asarray([n]), c, cfg))(
                params, jnp.asarray(t), fam.init_cache(cfg, 1, t.shape[1]))

    exact, exact_cache, exact_counts = prefill(toks[:, :n])
    got, cache, counts = prefill(toks)
    np.testing.assert_allclose(got, exact, atol=F32_TOL)
    for leaf in ("k_win", "v_win"):
        assert cache[leaf].shape == exact_cache[leaf].shape
        np.testing.assert_allclose(cache[leaf], exact_cache[leaf],
                                   atol=F32_TOL)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(cache[leaf][:, :, :, :n],
                                   exact_cache[leaf], atol=F32_TOL)
    routing = lambda c: {k: int(v) for k, v in c.items()  # noqa: E731
                         if k not in laguna.LOOP_COUNT_NAMES}  # the rung's
    assert routing(counts) == routing(exact_counts)
    assert int(counts["routed_total"]) == n * cfg.top_k * 4
    # slot r holds position p = r mod 8, the newest below n; none: zeros
    ring = np.asarray(exact_cache["k_win"][0, 0])  # [Hkv, 8, D]
    for r in range(8):
        if not any(p % 8 == r for p in range(n)):
            assert not ring[:, r].any()
        else:
            assert ring[:, r].any()


# ----------------------------------------------------------------- prefill
def attention_operands(s, seed=0, h=12, hkv=2, d=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (2 * jax.random.normal(k[0], (2, s, h, d)),
            jax.random.normal(k[1], (2, s, hkv, d)),
            jax.random.normal(k[2], (2, s, hkv, d)))


def dense_attention(q, k, v, window=None):
    s, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / q.shape[-1] ** 0.5
    behind = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    seen = (behind >= 0) if window is None else (
        (behind >= 0) & (behind < window))
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("s,query_block,key_block", [
    (5, 8, 8), (29, 8, 8), (64, 16, 8), (70, 8, 16), (100, 32, 32),
    (70, 4, 4), (29, 1024, 1024)])
def test_the_tiled_prefill_over_grouped_heads_equals_the_dense_one(
    s, query_block, key_block, window
):
    """Groups of 6 (12 query heads over 2 key-value heads, which are never
    repeated), several tiles and a ragged last one, query tiles longer and
    shorter than key tiles and than the window, one tile that holds the
    whole sequence: the online softmax over the key tiles a query tile sees
    is the dense softmax, causal or BANDED (``window`` 8: the key tiles have
    a lower bound too, and a row whose first tile is wholly masked takes its
    softmax from the next)."""
    q, k, v = attention_operands(s, seed=s)
    got = jax.jit(lambda q, k, v: layers.blocked_attention(
        q, k, v, query_block=query_block, key_block=key_block,
        window=window))(q, k, v)
    np.testing.assert_allclose(got, dense_attention(q, k, v, window),
                               atol=F32_TOL)


def test_the_band_meets_two_key_tiles_a_query_tile_whatever_the_length():
    """The band's cost: at tiles of the window's length a query tile runs
    TWO key tiles (one the first), where the causal loop runs up to the
    diagonal: counted by the scores' products in the traced program, and
    the rows of query tiles beyond ``longest`` come out zero."""
    q, k, v = attention_operands(64, h=18)  # groups of 9
    got = jax.jit(lambda q, k, v, n: layers.blocked_attention(
        q, k, v, n, query_block=8, key_block=8, window=8))(q, k, v, 19)
    np.testing.assert_allclose(got[:, :24],
                               dense_attention(q, k, v, 8)[:, :24],
                               atol=F32_TOL)
    assert float(jnp.abs(got[:, 24:]).max()) == 0.0
    trips = []

    def spy(block, n_blocks, step, shape, columns=()):
        trips.append(int(n_blocks))
        return jnp.zeros(shape, jnp.float32)

    with jax.disable_jit(), unittest.mock.patch.object(
            layers, "attend_blocks", spy):
        layers.blocked_attention(q, k, v, query_block=8, key_block=8,
                                 window=8)
        assert trips == [1] + [2] * 7
        trips.clear()
        layers.blocked_attention(q, k, v, query_block=8, key_block=8)
        assert trips == list(range(1, 9))


# ------------------------------------------------------------------ experts
@pytest.mark.parametrize("rows", [4, 13, 150])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(weights, rows):
    """The ways the held experts run: 13 rows (a decode step whose rows, each
    on its own, would touch nearly every held expert: all of them run on
    every row in batched products), 4 rows (a decode step whose rows would
    touch two in three, as the cell's 32 x 10 / 256: the loop of
    ``expert_share.held_experts`` in its one-chunk form, a turn a touched
    expert on the whole batch) and 150 rows (a prefill's: the gather and the
    chunk loop).  The deployment's cut: each of
    ``n_routed_experts / experts_held`` = four chips holds a quarter of the
    experts, routes over all sixteen, sums ITS experts' part x 2.5 and adds
    the shared expert.  The four held parts + the shared expert COUNTED ONCE
    are the uncut reference's layer; the counts are the reference's choices
    recounted; the combine weights of a token sum to 2.5."""
    cfg, params = weights
    i = 2
    assert runs_every_held_expert(
        rows, cfg.top_k, cfg.n_routed_experts) == (rows == 13)
    sizes = bench_family.sizes_of(cfg)
    w = jax.tree.map(lambda a: a[i], params["blocks"]["moe"])
    experts = jax.tree.map(lambda a: a[i], params["experts"])
    u = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.d_model))
    pad = min(4, rows - 2)
    live = jnp.arange(rows) != pad  # a padded row chooses no held expert
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.experts_layer(u[None], w, experts, sizes, 0)
        shared, _ = ref.experts_layer(
            u[None], w, jax.tree.map(lambda a: a[:0], experts), sizes, 0)
    total, held_sum = -3 * shared[0], 0  # four shares add it four times
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = dict(params, experts=jax.tree.map(
            lambda a: a[:, offset:offset + 4], params["experts"]))
        y, counts = jax.jit(lambda u, part=part, share=share: laguna.moe(
            u, live, part, i, share))(u)
        local = np.asarray(chosen)[0][np.asarray(live)] - offset
        held = (local >= 0) & (local < 4)
        # the loop's turns and the rows they ran, counted by hand (4 rows
        # are one chunk: a turn is a touched expert)
        turns = sum(-(-int((local[held] == e).sum()) // chunk_rows(rows))
                    for e in range(4)) if rows != 13 else 0
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
    combine = sigmoid_route(u, w["router"], w["router_bias"], cfg.top_k,
                            cfg.routed_scaling_factor)[1]
    np.testing.assert_allclose(combine.sum(-1), 2.5, rtol=1e-5)


# ----------------------------------------------------------------- controls
# Each must FAIL the float32 tolerance, at a length where the mechanism acts
# (100 positions: a dozen windows of 8, slowed pairs that differ from plain
# rotary by radians).
CONTROLS = {
    "the gate left out": dict(gate=False),
    "a window layer reads the first 12 of its 18 heads' weights":
        dict(window_heads=12),
    "the window one position wide": dict(window=9),
    "the window one position narrow": dict(window=7),
    "YaRN left out of the full layers": dict(yarn=False),
    "m^2 on the whole score": dict(factor_on_scores=True),
    "the full layers' table in the window layers": dict(window_table="full"),
    "the shared expert left out": dict(shared=False),
    "the routed scale 1 for 2.5": dict(routed_scaling_factor=1.0),
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_a_fault_in_the_mathematics_is_outside_the_tolerance(weights, fault):
    """The program against the reference with one mechanism left out or
    wrong: a comparison that passes the program (2e-5) reads a thousand
    times that for every one of them, and over the benchmark's 3 % of the
    logits' spread."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 100)
    got = np.asarray(model_family(cfg).apply(params, jnp.asarray(toks), cfg))
    want = ref_logits(params, toks, cfg)
    assert np.abs(got - want).max() < F32_TOL
    faulty = ref_logits(params, toks, cfg, **CONTROLS[fault])
    assert np.abs(got - faulty).max() > 1000 * F32_TOL
    assert rel_rms(got, faulty) > BF16_TOL
    # inside the window (8 positions) its edge does nothing: the control can
    # fail only where the mechanism acts
    if fault == "the window one position wide":
        short = tokens_of(cfg, 2, 8)
        np.testing.assert_allclose(
            ref_logits(params, short, cfg, **CONTROLS[fault]),
            ref_logits(params, short, cfg), atol=F32_TOL)


def test_float8_weights_are_outside_the_tolerance_and_bfloat16_inside():
    """The lower-precision control, in the served type: bfloat16 weights
    against the float32 reference pass the benchmark's 3 %; the same
    matrices at float8's three bits of mantissa (``reduce_precision``: a
    cast pair is folded by the compiler, PERF.md section 6) fail it."""
    cfg, params = cell_draw(3000000029, experts_held=8, expert_offset=4)
    toks = tokens_of(cfg, 2, 100, seed=2)
    want = ref_logits(params, toks, cfg)
    fam = model_family(cfg)
    assert rel_rms(fam.apply(params, jnp.asarray(toks), cfg), want) < BF16_TOL
    float8 = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3) if a.ndim >= 3 else a,
        params)
    assert rel_rms(fam.apply(float8, jnp.asarray(toks), cfg), want) > BF16_TOL


# ------------------------------------------------------------------ engine
PROMPTS = ["the first prompt, five windows of eight positions long",
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


def ascii_of(text):
    return [c for c in text if c.isascii()]


def test_engine_serves_stacks_by_kind_with_no_edit_for_the_family():
    """What ``llm/engine.py`` needed for this family: nothing.  A slot's
    second tenant gives the ids it gives alone: its first tenant was LONGER
    than the window (54 tokens, then 24 more: the ring wrapped nine times)
    and the second is shorter than it (6, 4 tokens), so what the ring still
    holds of the first sits in slots the second has not reached and must
    not be read; a request among full slots gives the ids it gives alone;
    streamed equals unary; the family's counts reach ``stats()``."""
    params = SamplingParams(max_tokens=24, stop_token=-1)
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    one = make_engine(slots=1)
    assert one.cache["k_win"].shape == (3, 1, 2, 8, 16)
    assert one.cache["k"].shape == (2, 1, 2, 128, 16)
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
        # random ids are no valid UTF-8: two bytes that decode as one
        # character in the whole answer are two replacement marks when a
        # delta's end falls between them; the ASCII bytes are in both
        assert ascii_of(streamed) == ascii_of(full.tokenizer.decode(alone[i]))
        assert len(ascii_of(streamed)) >= 4
    stats = full.stats()
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["routed_held"] > 0 and stats["prefill_routed_held"] > 0
    assert stats["experts_touched"] <= stats["routed_held"] < (
        stats["routed_total"])
    full.shutdown()


def test_no_engine_or_serve_module_names_the_family():
    """ROADMAP's test of the family interface, as a test."""
    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    named = [str(p) for d in ("llm", "serve") for p in (root / d).rglob("*.py")
             if "laguna" in p.read_text().lower()]
    assert not named


def test_the_harness_two_layer_cut_and_the_cells_draw(monkeypatch):
    """``bench_server.check_reference``'s shape for a family: ``n_layer=2``,
    ``a[:2]`` on every leaf of ``params["blocks"]`` (the held experts a
    subtree of their own, not copied), a cache of 68 positions whose rings
    keep the window's extent, an UNPADDED prefill of 64 tokens and 3 decode
    steps: layer 0 (full, dense) and layer 1 (window, experts), in the
    served type under the benchmark's own limit.  And the draw of the
    cell's weights (``families/laguna.py``, here at tiny widths with its
    scales): the program's tree; the routers read channels that no layer
    writes, so the bfloat16 program and the float32 reference make the SAME
    choices at every token of every expert layer."""
    from benchmarks.lib import bench_server

    cfg, params = cell_draw(3000000019, experts_held=8, expert_offset=4)
    want = jax.eval_shape(lambda: laguna_init(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    blocks, own = params["blocks"], cfg.d_model // 16
    router = np.asarray(blocks["moe"]["router"])
    assert abs(router[:, :own].std() - bench_family.SCALES["router"]) < 0.02
    assert not router[:, own:].any()
    for out in (blocks["full"]["wo"], blocks["window"]["wo"],
                blocks["dense"]["w_down"], blocks["moe"]["w_down"],
                params["experts"]["w_down"]):
        out = np.asarray(out, np.float32)
        assert not out[..., :own].any() and out[..., own:].all()
    cut = dataclasses.replace(cfg, n_layer=2)
    assert (cut.attn_kinds, cut.mlp_kinds) == ("FW", "DE")
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 64 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 64, 3)
    full = ref_logits(part, toks, cut)[0]
    errs = bench_server.logit_errors(got, [full[63 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 68)
    assert cache["k"].shape[:4] == (1, 1, 2, 68)
    assert cache["k_win"].shape[:4] == (1, 1, 2, 8)
    # at the published window the check's 67 positions stay inside it
    wide = model_family(cut).init_cache(
        dataclasses.replace(cut, window=512), 1, 68)
    assert wide["k_win"].shape[3] == 512 and wide["k"].shape[3] == 68
    # the same choices, program and reference, and by the token alone
    toks = tokens_of(cfg, 2, 40, seed=9)
    toks[:, 30] = toks[:, 3]  # one token at two places
    chosen, top_k = [], jax.lax.top_k

    def spy(scores, k):
        values, sel = top_k(scores, k)
        chosen.append(np.sort(np.asarray(sel).reshape(2, 40, k), -1))
        return values, sel

    monkeypatch.setattr(jax.lax, "top_k", spy)
    with jax.disable_jit():  # the runs of one kind are scanned
        laguna.laguna_apply(params, jnp.asarray(toks), cfg)
    ref_logits(params, toks, cfg)
    layers = cfg.mlp_kinds.count("E")
    assert len(chosen) == 2 * layers
    for program, reference in zip(chosen[:layers], chosen[layers:]):
        np.testing.assert_array_equal(program, reference)
        np.testing.assert_array_equal(program[:, 30], program[:, 3])
    assert (chosen[0] != chosen[1]).any()  # every layer its own choice
