"""MiMo-V2 family (``ray_tpu/models/mimo_v2*.py``) against its plain float32
reference (``benchmarks/reference/mimo_v2_ref.py``: dense scores, the window
as a mask, the sink as a column, dense routing), at tiny widths on the CPU
with seeded weights: pattern ``FWWWWWF`` / ``DEEEEEE``, a window of 8, 16
routed experts, 4 a token.  Logits, not tokens.  Each tolerance says what it
allows for.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mimo_v2 as bench_family
from benchmarks.reference import mimo_v2_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (MimoV2Config, mimo_v2, mimo_v2_init,
                            model_family)
from ray_tpu.models.expert_share import (chunk_rows, runs_every_held_expert,
                                         sigmoid_route)
from ray_tpu.ops.decode_attention import (NEG_INF, decode_attention,
                                          ring_positions)

# float32 against float32: the two differ by the order of their sums only
# (a band of two blocks against a masked row, query blocks against one
# product, experts added in another order); logits are ~1 wide and pass
# through seven blocks, so this is some tens of units in the last place
# (1-2e-6 measured; the limit leaves ten times that).
F32_TOL = 2e-5
# bfloat16 products (2^-9 a rounding, some forty of them through seven
# blocks and the head) against float32, as a share of the logits' spread:
# the benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).
BF16_TOL = 0.03


def tiny(**kw):
    return MimoV2Config.tiny(dtype=kw.pop("dtype", "float32"), **kw)


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
    return lively(mimo_v2_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, **kw):
    return np.asarray(ref.mimo_v2_ref_logits(
        params, jnp.asarray(tokens), bench_family.sizes_of(cfg),
        cfg.attn_kinds, cfg.mlp_kinds, cfg.expert_offset, **kw))


def rel_rms(got, want):
    """RMS of the difference over the vocabulary as a share of the
    reference logits' spread, the worst position."""
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


def test_family_resolves_and_full_forward_matches_the_reference(weights):
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "mimo_v2" and fam.decode_step_counted is not None
    assert (cfg.attn_kinds, cfg.mlp_kinds) == ("FWWWWWF", "DEEEEEE")
    toks = tokens_of(cfg, 3, 27)  # three whole windows and a part
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, toks)
    want = ref_logits(params, toks, cfg)
    assert got.shape == (3, 27, cfg.vocab_size) and want.std() > 0.5
    assert float(np.abs(got - want).max()) < F32_TOL
    loss = fam.loss(params, tokens_of(cfg, 2, 9), cfg)
    assert np.isfinite(float(loss)) and float(loss) > np.log(cfg.vocab_size) - 1
    axes, shapes = fam.param_axes(), jax.eval_shape(lambda: params)
    assert jax.tree.structure(axes) == jax.tree.structure(shapes)
    assert all(len(a) == s.ndim for a, s in zip(
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)), jax.tree.leaves(shapes)))


def test_the_published_patterns_are_the_configs():
    """``hybrid_layer_pattern`` (0 = full) and ``moe_layer_freq`` (0 =
    dense) of the catalog's row, as letters."""
    full = [i for i, kind in enumerate(mimo_v2.PUBLISHED_ATTN) if kind == "F"]
    assert full == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert mimo_v2.PUBLISHED_MLP == "D" + "E" * 47
    cfg = MimoV2Config()
    assert (cfg.attn_kinds.count("W"), cfg.attn_kinds.count("F")) == (39, 9)
    # the cell's layers: layer 0 and layers 6-11
    assert (mimo_v2.PUBLISHED_ATTN[0] + mimo_v2.PUBLISHED_ATTN[6:12]
            == "FWWWWWF")
    with pytest.raises(ValueError):
        MimoV2Config(attn_pattern="FW", mlp_pattern="DEE", n_layer=2)


def through_the_cache(cfg, params, toks, lengths, steps, padded_to=None):
    """Ragged batch: prefill each row's first ``lengths[b]`` tokens (padded
    to ``padded_to``), then ``steps`` decode steps at each row's own
    position.  Returns the logits that predict positions ``lengths[b] + i``,
    the cache after prefill and the counts of every program run."""
    fam = model_family(cfg)
    lengths = np.asarray(lengths, np.int32)
    width = padded_to or toks.shape[1]
    cache = fam.init_cache(cfg, len(lengths), max(width, toks.shape[1] + 1))
    padded = np.zeros((len(lengths), width), np.int32)
    for b, n in enumerate(lengths):
        padded[b, :n] = toks[b, :n]
    logits, cache, counts = jax.jit(
        lambda p, t, n, c: fam.prefill_counted(p, t, n, c, cfg)
    )(params, padded, lengths, cache)
    after_prefill = cache
    out, all_counts = [np.asarray(logits)], [counts]
    decode = jax.jit(
        lambda p, t, pos, c: fam.decode_step_counted(p, t, pos, c, cfg))
    rows = np.arange(len(lengths))
    for i in range(steps):
        pos = lengths + i
        logits, cache, counts = decode(params, toks[rows, pos], pos, cache)
        out.append(np.asarray(logits))
        all_counts.append(counts)
    return np.stack(out, 1), after_prefill, all_counts  # [B, steps + 1, V]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_a_wrapped_ring_matches_full_forward(
        dtype):
    """Prompts shorter than, equal to and longer than the window (8), then
    24 decode steps: every row's ring wraps three times, and the row that
    started inside the window crosses its edge while decoding.  Half the
    experts are held, so absent and held ones are both chosen."""
    cfg = tiny(dtype=dtype, experts_held=8, expert_offset=4)
    params = weights_of(cfg, seed=1)
    lengths, steps = [5, 8, 19], 24
    toks = tokens_of(cfg, 3, 19 + steps, seed=1)
    got, cache, counts = through_the_cache(cfg, params, toks, lengths, steps)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, n - 1:n + steps] for b, n in enumerate(lengths)])
    if dtype == "float32":
        assert float(np.abs(got - want).max()) < F32_TOL
    else:
        assert rel_rms(got, want) < BF16_TOL
    n_moe = cfg.mlp_kinds.count("E")
    assert int(counts[0]["routed_total"]) == sum(lengths) * cfg.top_k * n_moe
    for step in counts[1:]:
        assert int(step["routed_total"]) == 3 * cfg.top_k * n_moe
        assert 0 < int(step["experts_touched"]) <= int(step["routed_held"])
        assert int(step["routed_held"]) < int(step["routed_total"])
    # The two extents: every position served on the full layers' leaves, a
    # ring of the window's on the window layers', whatever is served.
    assert cache["k"].shape == (2, 3, cfg.n_kv_head, 44, cfg.head_dim)
    assert cache["v"].shape == (2, 3, cfg.n_kv_head, 44, cfg.v_head_dim)
    assert cache["k_win"].shape == (5, 3, cfg.n_kv_head_window, 8,
                                    cfg.head_dim)
    assert cache["v_win"].shape == (5, 3, cfg.n_kv_head_window, 8,
                                    cfg.v_head_dim)


@pytest.mark.parametrize("n", [3, 8, 9, 19])
def test_a_padded_prefill_leaves_the_ring_of_the_true_length(weights, n):
    """The engine pads a prompt to a rung; the ring spliced into the slot
    must hold the last 8 TRUE positions (``p < n``, at ``p mod 8``), not the
    rung's tail: ``n`` inside the window, at its edge, one past it and two
    wraps on, padded to 32, against the same prompt prefilled at exactly
    ``n``.  The padding is not zeros: whatever the rung holds beyond ``n``
    must not matter."""
    cfg, params = weights
    fam = model_family(cfg)
    toks = tokens_of(cfg, 1, 32, seed=n)
    run = jax.jit(lambda p, t, c: fam.prefill(p, t, jnp.asarray([n]), c, cfg))
    exact_logits, exact = run(params, toks[:, :n], fam.init_cache(cfg, 1, n))
    padded_logits, padded = run(params, toks, fam.init_cache(cfg, 1, 32))
    # float32 sums in another order (blocks of the padded length)
    assert float(jnp.abs(padded_logits - exact_logits).max()) < F32_TOL
    for leaf in ("k_win", "v_win"):
        assert padded[leaf].shape == exact[leaf].shape
        np.testing.assert_allclose(padded[leaf], exact[leaf], atol=F32_TOL)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(padded[leaf][:, :, :, :n], exact[leaf],
                                   atol=F32_TOL)
    # slot r holds position p = r mod 8, the newest below n; none: zeros
    ring = np.asarray(exact["k_win"][0, 0])  # [Hkv, 8, D]
    keys = first_window_layers_keys(params, toks[:, :n], cfg)  # [n, Hkv, D]
    for r in range(8):
        held = [p for p in range(n) if p % 8 == r]
        if held:
            np.testing.assert_allclose(ring[:, r], keys[held[-1]],
                                       atol=F32_TOL)
        else:
            assert not ring[:, r].any()


def first_window_layers_keys(params, toks, cfg):
    """The roped keys of layer 1 (the first window layer), position by
    position, from the reference's pieces."""
    sizes, eps = bench_family.sizes_of(cfg), cfg.rms_eps
    blocks = params["blocks"]
    take = lambda kind, i: {k: v[i] for k, v in blocks[kind].items()}
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["wte"][toks], jnp.float32)
        w = take("full", 0)
        x = x + ref.attention(ref._rms(x, w["rms"], eps), w, sizes,
                              cfg.rope_theta)
        w = take("dense", 0)
        x = x + ref._swiglu(ref._rms(x, w["rms"], eps), w["w_gate"],
                            w["w_up"], w["w_down"])
        w = take("window", 0)
        k = jnp.einsum("bse,ekd->bskd", ref._rms(x, w["rms"], eps), w["wk"])
        return np.asarray(ref._rope(k, cfg.rope_theta_window,
                                    cfg.rotary_dim)[0])


def dense_attention(q, k, v, window=None, sink=None):
    """Plain float64 attention with an ``[S, S]`` mask."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    g = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    s = q.shape[1]
    scores = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(q.shape[-1])
    behind = np.arange(s)[:, None] - np.arange(s)[None]
    seen = behind >= 0 if window is None else (behind >= 0) & (behind < window)
    scores = np.where(seen, scores, -np.inf)
    top = scores.max(-1, keepdims=True)
    e = np.exp(scores - top)
    norm = e.sum(-1, keepdims=True)
    if sink is not None:
        norm = norm + np.exp(np.asarray(sink, np.float64)[None, :, None, None]
                             - top)
    return np.einsum("bhst,bthd->bshd", e / norm, v)


@pytest.mark.parametrize("s", [5, 16, 29])
def test_the_banded_prefill_equals_the_masked_dense_one(s):
    """``window_attention`` computes queries of a block against the keys of
    that block and the one before: the same as dense scores under the
    window's mask with the sink's column, at a length inside one block, of
    whole blocks, and with a part block."""
    rng = np.random.default_rng(s)
    q = rng.normal(size=(2, s, 8, 24)).astype(np.float32)
    k = rng.normal(size=(2, s, 4, 24)).astype(np.float32)
    v = rng.normal(size=(2, s, 4, 16)).astype(np.float32)
    sink = rng.normal(size=8).astype(np.float32)
    got = jax.jit(lambda *a: mimo_v2.window_attention(*a, 8))(q, k, v, sink)
    np.testing.assert_allclose(got, dense_attention(q, k, v, 8, sink),
                               atol=F32_TOL)


@pytest.mark.parametrize("s,block", [(5, 8), (16, 8), (29, 8), (29, 512)])
def test_the_query_blocked_full_prefill_equals_the_dense_one(s, block):
    rng = np.random.default_rng(s)
    q = rng.normal(size=(2, s, 8, 24)).astype(np.float32)
    k = rng.normal(size=(2, s, 2, 24)).astype(np.float32)
    v = rng.normal(size=(2, s, 2, 16)).astype(np.float32)
    got = jax.jit(lambda *a: mimo_v2.full_attention(*a, block=block))(q, k, v)
    np.testing.assert_allclose(got, dense_attention(q, k, v), atol=F32_TOL)


# ------------------------------------------------- ops/decode_attention.py
def decode_attention_before(q, k_cache, v_cache, pos, layer, k_self=None,
                            v_self=None):
    """``decode_attention``'s program as it was before it learned of a ring,
    a sink and a value width of its own (PR 44's), verbatim."""
    k = k_cache[layer]
    v = v_cache[layer]
    b, hkv, t, d = k.shape
    h = q.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = d ** -0.5
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k).astype(jnp.float32) * scale
    limit = pos[:, None, None, None]
    idx = jnp.arange(t)[None, None, None, :]
    if k_self is None:
        mask = idx <= limit
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v.dtype), v)
        return out.reshape(b, h, d)
    mask = idx < limit
    scores = jnp.where(mask, scores, NEG_INF)
    s_self = (
        jnp.einsum("bkgd,bkd->bkg", qg, k_self).astype(jnp.float32) * scale
    )[..., None]
    full = jnp.concatenate([scores, s_self], axis=-1)
    probs = jax.nn.softmax(full, axis=-1)
    out = jnp.einsum(
        "bkgt,bktd->bkgd", probs[..., :-1].astype(v.dtype), v
    ) + probs[..., -1:].astype(v.dtype) * v_self[:, :, None, :]
    return out.reshape(b, h, d)


def decode_operands(b, h, hkv, t, d, dv, dtype, seed=0, layers=2):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (draw(b, h, d), draw(layers, b, hkv, t, d),
            draw(layers, b, hkv, t, dv), draw(b, hkv, d), draw(b, hkv, dv))


# (B, H, Hkv, T, D): LlamaConfig.tiny (Mistral's family) and
# NemotronHConfig.tiny, in the types the cells and the tests run them in
OLDER_FAMILIES = [(3, 4, 2, 32, 16, "bfloat16"), (3, 4, 2, 32, 16, "float32"),
                  (4, 4, 2, 64, 16, "bfloat16"), (2, 8, 8, 24, 8, "float32")]


@pytest.mark.parametrize("b,h,hkv,t,d,dtype", OLDER_FAMILIES)
@pytest.mark.parametrize("with_self", [True, False])
def test_decode_attention_without_the_new_arguments_is_bit_for_bit_what_it_was(
        b, h, hkv, t, d, dtype, with_self):
    """The older families' path: no ``window``, no ``sink``, values as wide
    as the keys.  Same bits out, jitted as the decode steps call it, and the
    same program: the optimised HLO's operations, counted."""
    q, kc, vc, ks, vs = decode_operands(b, h, hkv, t, d, d, dtype, seed=t)
    pos = jnp.asarray(np.random.default_rng(1).integers(0, t, b), jnp.int32)
    own = dict(k_self=ks, v_self=vs) if with_self else {}
    now = decode_attention(q, kc, vc, pos, 1, **own)
    before = jax.jit(decode_attention_before, static_argnums=4)(
        q, kc, vc, pos, 1, *own.values())
    assert now.dtype == before.dtype
    np.testing.assert_array_equal(np.asarray(now, np.float32),
                                  np.asarray(before, np.float32))

    def operations(fn):
        text = jax.jit(fn).lower(q, kc, vc, pos).compile().as_text()
        return sorted(line.split(" = ")[1].split("(")[0].split()[-1]
                      for line in text.splitlines() if " = " in line)

    assert operations(lambda q, kc, vc, pos: decode_attention(
        q, kc, vc, pos, 1, **own)) == operations(
            lambda q, kc, vc, pos: decode_attention_before(
                q, kc, vc, pos, 1, *own.values()))


@pytest.mark.parametrize("pos", [[0, 1, 5], [7, 8, 9], [16, 23, 100]])
@pytest.mark.parametrize("with_self", [True, False])
def test_decode_attention_over_a_ring_with_a_sink_and_narrower_values(
        pos, with_self):
    """Against the plain formula in float64: the ring holds position ``p`` at
    ``p mod 8``; the token at ``pos`` attends the last 8 positions, itself
    included, plus its head's sink logit, whose probability is dropped.
    Slots no true position has reached hold GARBAGE here (the last tenant's
    keys): they are masked by position, not by content.  Values are 16 wide
    beside keys of 24."""
    b, h, hkv, w, d, dv = 3, 8, 4, 8, 24, 16
    q, kc, vc, ks, vs = decode_operands(b, h, hkv, w, d, dv, "float32", 5)
    sink = jnp.asarray(np.random.default_rng(2).normal(size=h), jnp.float32)
    pos = np.asarray(pos, np.int32)
    got = decode_attention(
        q, kc, vc, jnp.asarray(pos), 1, window=w, sink=sink,
        **(dict(k_self=ks, v_self=vs) if with_self else {}))
    assert got.shape == (b, h, dv)
    g = h // hkv
    for row in range(b):
        # the positions attended, oldest first, and where each lies
        seen = [p for p in range(pos[row] - w + 1, pos[row] + 1) if p >= 0]
        for head in range(h):
            kv = head // g
            keys = [ks[row, kv] if with_self and p == pos[row]
                    else kc[1, row, kv, p % w] for p in seen]
            vals = [vs[row, kv] if with_self and p == pos[row]
                    else vc[1, row, kv, p % w] for p in seen]
            scores = np.array([np.dot(np.asarray(q[row, head], np.float64),
                                      np.asarray(key, np.float64))
                               for key in keys]) / np.sqrt(d)
            e = np.exp(scores - scores.max())
            p = e / (e.sum() + np.exp(float(sink[head]) - scores.max()))
            want = (p[:, None] * np.asarray(vals, np.float64)).sum(0)
            np.testing.assert_allclose(got[row, head], want, atol=2e-6)
    mask = ring_positions(jnp.asarray(pos)[:, None, None, None],
                          jnp.asarray(pos)[:, None, None, None] - 1, w)
    assert [int(m.sum()) for m in mask] == [min(p, w - 1) for p in pos]
    with pytest.raises(ValueError, match="ring"):
        decode_attention(q, jnp.tile(kc, (1, 1, 1, 2, 1)),
                         jnp.tile(vc, (1, 1, 1, 2, 1)), jnp.asarray(pos), 1,
                         window=w)


# ------------------------------------------------------------------ experts
@pytest.mark.parametrize("rows", [13, 150])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(weights, rows):
    """Both ways the held experts run: 13 rows (a decode step's: one chunk
    and a choice or more an expert, so every held expert runs on every row
    in batched products) and 150 rows (a prefill's: the gather and the chunk
    loop of ``expert_share.held_experts``).  The deployment's cut: each of
    ``n_routed_experts / experts_held`` = four chips holds a quarter of the
    experts, routes over all sixteen and sums ITS experts' part.  The four
    parts are the uncut reference's layer (there is no shared expert to
    count once); the counts are the reference's choices recounted."""
    cfg, params = weights
    layer = 2
    assert runs_every_held_expert(
        rows, cfg.top_k, cfg.n_routed_experts) == (rows == 13)
    w = jax.tree.map(lambda a: a[layer], params["blocks"]["moe"])
    experts = jax.tree.map(lambda a: a[layer], params["experts"])
    u = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.d_model))
    live = jnp.arange(rows) != 4  # a padded row chooses nothing
    with jax.default_matmul_precision("highest"):
        want, chosen = ref.experts_layer(
            u[None], w, experts, bench_family.sizes_of(cfg), 0)
    total, held_sum = 0, 0
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = dict(params, experts=jax.tree.map(
            lambda a: a[:, offset:offset + 4], params["experts"]))
        y, counts = jax.jit(lambda u, part=part, share=share: mimo_v2.moe(
            u, live, part, layer, share))(u)
        local = np.asarray(chosen)[0][np.asarray(live)] - offset
        held = (local >= 0) & (local < 4)
        # the loop's turns and the rows they ran, counted by hand
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
    assert float(jnp.abs(total[4]).max()) == 0.0
    combine = sigmoid_route(u, w["router"], w["router_bias"], cfg.top_k)[1]
    np.testing.assert_allclose(combine.sum(-1), 1.0, rtol=1e-5)


# ----------------------------------------------------------------- controls
def control_errors(cfg, params, program_cfg=None, program_params=None, **kw):
    """The program (``program_cfg`` / ``program_params``: the fault) through
    the cache against the TRUE reference: a prompt of 19 and 12 decode
    steps, the worst position's error both ways it is measured."""
    toks = tokens_of(cfg, 2, 19 + 12, seed=4)
    got, _, _ = through_the_cache(program_cfg or cfg, program_params or params,
                                  toks, [19, 19], 12)
    want = ref_logits(params, toks, cfg, **kw)
    want = np.stack([want[b, 18:19 + 12] for b in range(2)])
    return float(np.abs(got - want).max()), rel_rms(got, want)


CONTROLS = {
    "the window one position wide": lambda cfg: dataclasses.replace(
        cfg, window=cfg.window + 1),
    "the window one position narrow": lambda cfg: dataclasses.replace(
        cfg, window=cfg.window - 1),
    "the values' 0.707 left out": lambda cfg: dataclasses.replace(
        cfg, value_scale=1.0),
    "the window layers' rotary base swapped for the full layers'":
        lambda cfg: dataclasses.replace(cfg, rope_theta_window=cfg.rope_theta),
}


@pytest.mark.parametrize("fault", sorted(CONTROLS) + ["the sink left out"])
def test_a_fault_in_the_mathematics_is_outside_the_tolerance(weights, fault):
    """Each is a program that differs from the reference in ONE term of the
    equations; every one must fail the float32 tolerance by a hundred times
    (they read 0.04-0.25 where the program reads 1e-6) and the bfloat16 one
    (3 % of the logits' spread, the benchmark's) too: 4.8-9.6 %.  All but
    the rotary base, which at these sizes turns one of four frequencies by
    at most 0.6 rad over a window of 8 positions and reads 1.3 %: a float32
    comparison sees it two thousand times over, the 3 % would not, and it
    is held to a third of that here.  (A window of 7 or 9 is a ring of
    another extent: the program is built with the wrong window throughout.)
    The sink cannot be left out of the program, which has no switch for it:
    it is left out of the reference."""
    cfg, params = weights
    good = control_errors(cfg, params)
    assert good[0] < F32_TOL
    if fault == "the sink left out":
        worst, rel = control_errors(cfg, params, with_sink=False)
    else:
        worst, rel = control_errors(cfg, params, CONTROLS[fault](cfg))
    assert worst > 100 * F32_TOL, (fault, worst)
    assert rel > (BF16_TOL / 3 if "rotary" in fault else BF16_TOL), (fault, rel)


def test_float8_weights_are_outside_the_tolerance_and_bfloat16_inside():
    """The lower-precision control, in the served type: bfloat16 weights
    against the float32 reference pass the benchmark's 3 %; the same
    matrices at float8's three bits of mantissa (``reduce_precision``: a
    cast pair is folded by the compiler, PERF.md section 6) fail it."""
    cfg = tiny(dtype="bfloat16", experts_held=8, expert_offset=4)
    params = weights_of(cfg, seed=2)
    assert control_errors(cfg, params)[1] < BF16_TOL
    float8 = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3) if a.ndim >= 3 else a,
        params)
    assert control_errors(cfg, params, program_params=float8)[1] > BF16_TOL


# ------------------------------------------------------------------ engine
PROMPTS = ["the first prompt, well beyond the window", "second",
           "a third, somewhat longer prompt than the second",
           "four"]


def make_engine(slots=4, max_seq_len=64):
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


def test_engine_slots_hold_rings_beside_full_caches():
    """What ``llm/engine.py`` needed for leaves of two extents: nothing.
    ``init_cache(model, 1, rung)`` gives a ring of the window's extent
    whatever the rung and ``splice_row`` replaces the slot's whole.  A
    slot's second tenant gives the ids it gives alone: its first tenant was
    LONGER than the window (41 tokens, then 12 more: the ring wrapped six
    times) and the second is shorter (5, 7 tokens), so what the ring still
    holds of the first sits in slots the second has not reached and must
    not be read; a request among full slots gives the ids it gives alone;
    streamed equals unary; the family's counts reach ``stats()``."""
    params = SamplingParams(max_tokens=12, stop_token=-1)
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    # One slot: every request but the first is the slot's next tenant; the
    # longest prompt's ring is what the shortest finds there.
    one = make_engine(slots=1)
    assert one.cache["k_win"].shape == (5, 1, 4, 8, 24)
    assert one.cache["k"].shape == (2, 1, 2, 64, 24)
    order = [0, 3, 2, 1]
    assert by_hand(one, [PROMPTS[i] for i in order], params) == [
        alone[i] for i in order]
    # Four slots, all full, admitted in one step and decoded together.
    full = make_engine()
    assert by_hand(full, PROMPTS, params) == alone
    assert all(s is None for s in full.slots)
    # Through the loop: unary and streamed.
    assert [r["token_ids"] for r in full.generate(PROMPTS, params)] == alone
    for i in (0, 3):
        rid = full.add_request(PROMPTS[i], params)
        streamed = "".join(full.stream_request(rid))
        assert streamed == full.tokenizer.decode(alone[i])
    stats = full.stats()
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0  # every stream ended by count
    assert stats["routed_held"] > 0 and stats["prefill_routed_held"] > 0
    assert stats["experts_touched"] <= stats["routed_held"] < (
        stats["routed_total"])
    full.shutdown()


def test_the_engines_prefill_leaves_the_rings_of_the_true_length():
    """Through the engine's own ``jit_prefill_one``: a prompt of 41 tokens
    padded to the engine's one rung (64) leaves in the slot's rings what an
    unpadded prefill of 41 leaves, in a slot whose last tenant had filled
    them."""
    engine = make_engine(slots=2)
    params = SamplingParams(max_tokens=3, stop_token=-1)
    by_hand(engine, ["x" * 50, "y" * 50], params)  # both rings are full
    rid = engine.add_request(PROMPTS[0], params)
    engine.step()  # admitted: prefilled and spliced
    [slot] = [i for i, s in enumerate(engine.slots) if s is not None]
    cfg, fam = engine.cfg.model, engine.family
    ids = engine.tokenizer.encode(PROMPTS[0])
    n = len(ids)
    assert n > 4 * cfg.window
    _, exact = jax.jit(lambda p, t, c: fam.prefill(
        p, t, jnp.asarray([n]), c, cfg))(
            engine.params, jnp.asarray([ids]), fam.init_cache(cfg, 1, n))
    # (the step that admitted it has dispatched a decode too, which wrote
    # position n over the slot that held n - 8)
    rest = np.arange(cfg.window) != n % cfg.window
    for leaf in ("k_win", "v_win"):
        np.testing.assert_allclose(engine.cache[leaf][:, slot][:, :, rest],
                                   exact[leaf][:, 0][:, :, rest],
                                   atol=F32_TOL)
        assert not np.allclose(engine.cache[leaf][:, slot][:, :, ~rest],
                               exact[leaf][:, 0][:, :, ~rest], atol=F32_TOL)
    while engine.has_unfinished():
        engine.step()
    assert len(engine.wait([rid])[0]["token_ids"]) == 3


def test_idle_slots_and_wrapped_rings_through_two_hundred_steps():
    """Every slot is decoded every step, tenant or not; a long answer wraps
    its rings twenty-five times.  The next tenant of an idle slot is none
    the worse for what the idle steps wrote there."""
    engine = make_engine(slots=4, max_seq_len=256)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    by_hand(engine, PROMPTS, params)  # every slot has had a tenant
    long = SamplingParams(max_tokens=200, stop_token=-1)
    assert len(by_hand(engine, ["one long answer"], long)[0]) == 200
    assert engine.stats()["decode_steps"] >= 200
    for leaf in engine.cache.values():
        assert bool(jnp.isfinite(leaf).all())
    again = by_hand(engine, PROMPTS[:1], params)
    assert again == by_hand(make_engine(max_seq_len=256), PROMPTS[:1], params)


def test_the_harness_two_layer_cut_runs_both_attentions_and_both_mlps():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]`` (the experts are a
    subtree of their own and are not copied).  The patterns start ``FW`` /
    ``DE``: full attention + dense MLP, then window attention + experts,
    through a cache of 68 positions whose rings (window 8) wrap eight
    times, in the served type against the float32 reference, under the
    benchmark's own limit."""
    from benchmarks.lib import bench_server

    model = dict(dataclasses.asdict(tiny(dtype="bfloat16", experts_held=8,
                                         expert_offset=4)), d_model=256)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    cut = dataclasses.replace(cfg, n_layer=2)
    assert (cut.attn_kinds, cut.mlp_kinds) == ("FW", "DE")
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 64 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 64, 3)
    want = ref_logits(part, toks, cut)[0]
    errs = bench_server.logit_errors(got, [want[63 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 68)
    assert cache["k"].shape[0] == 1 and cache["k_win"].shape[:4] == (
        1, 1, 4, 8)
    # at the published window the check's 67 positions stay inside it
    wide = model_family(cut).init_cache(
        dataclasses.replace(cut, window=128), 1, 68)
    assert wide["k_win"].shape[3] == 128 and wide["k"].shape[3] == 68


def test_the_cells_draw_routes_by_the_token_alone(monkeypatch):
    """``families/mimo_v2.py`` keeps the first channels of the stream for
    the routers: no layer writes them, so they carry the token's embedding
    through every layer and rounding upstream reaches a router only as the
    norm's common factor.  The bfloat16 program and the float32 reference
    then make the SAME choices at every token of every expert layer, and a
    token chooses the same wherever it stands."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16", experts_held=8,
                                         expert_offset=4)), d_model=256)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000021)
    toks = tokens_of(cfg, 2, 40, seed=9)
    toks[:, 30] = toks[:, 3]  # one token at two places
    chosen, top_k = [], jax.lax.top_k

    def spy(scores, k):
        values, sel = top_k(scores, k)
        chosen.append(np.sort(np.asarray(sel).reshape(2, 40, k), -1))
        return values, sel

    monkeypatch.setattr(jax.lax, "top_k", spy)
    mimo_v2.mimo_v2_apply(params, jnp.asarray(toks), cfg)  # not jitted
    ref_logits(params, toks, cfg)
    layers = cfg.mlp_kinds.count("E")
    assert len(chosen) == 2 * layers
    for program, reference in zip(chosen[:layers], chosen[layers:]):
        np.testing.assert_array_equal(program, reference)
        np.testing.assert_array_equal(program[:, 30], program[:, 3])
        assert (program[:, 30] != program[:, 4]).any()
    assert (chosen[0] != chosen[1]).any()  # every layer its own choice


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16", experts_held=8))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: mimo_v2_init(
        jax.random.PRNGKey(0), MimoV2Config(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    # the routers' channels: read by the routers alone, written by no layer
    blocks, own = params["blocks"], model["d_model"] // 16
    router = np.asarray(blocks["moe"]["router"])
    assert abs(router[:, :own].std() - bench_family.SCALES["router"]) < 0.05
    assert not router[:, own:].any()
    for out in (blocks["full"]["wo"], blocks["window"]["wo"],
                blocks["dense"]["w_down"], params["experts"]["w_down"]):
        out = np.asarray(out, np.float32)
        assert not out[..., :own].any() and out[..., own:].all()
    assert blocks["window"]["sink"].dtype == jnp.float32
    # around 5: a quarter of a row's mass at a window of 128 positions
    assert 0.5 < float(blocks["window"]["sink"].std()) < 1.5
    assert 4.5 < float(blocks["window"]["sink"].mean()) < 5.5


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_rings_beside_keys_and_values(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache with rings beside them when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "mimo_v2" in str(err.value) and "k_win" in str(err.value)
