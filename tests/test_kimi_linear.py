"""Kimi-Linear family (``ray_tpu/models/kimi_linear*.py``) against its plain
float32 reference (``benchmarks/reference/kimi_linear_ref.py``: Kimi Delta
Attention by its RECURRENCE, latent attention with expanded keys and dense
scores, the experts a loop), at tiny widths on the CPU with seeded weights:
pattern ``kMKKMK`` (the first layer's FFN dense, the others' experts), 4 KDA
heads of 16, 4 latent-attention heads over a latent of 16, 16 experts of
which 4 a token, chunks of 8.  Logits, not tokens.  Each tolerance says what
it allows for.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_linear as bench_family
from benchmarks.reference import kimi_linear_ref as ref
from ray_tpu.models import (KimiLinearConfig, delta_rule, kimi_linear,
                            kimi_linear_decode, kimi_linear_init,
                            model_family)

# float32 against float32: the two differ by the order of their sums only
# (the chunked scan, its pairwise decays and its triangular solve against
# the recurrence; blocked against dense softmax); logits are ~1 wide and
# pass through six blocks: 1.5e-6 measured, the limit leaves ten times that.
F32_TOL = 2e-5
# bfloat16 products against float32, as a share of the logits' spread: the
# benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 2304).
BF16_TOL = 0.03


def tiny(**kw):
    return KimiLinearConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): scale the embedding to RMS 1 and
    the matrices by 5, so that every sub-block moves the logits and a fault
    in one shows."""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a * 50
        big = a.ndim >= 3 or name == "lm_head"
        return a * 5 if big and name != "conv_w" else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(kimi_linear_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, **switches):
    sizes = dict(dataclasses.asdict(cfg), **switches)
    return np.asarray(ref.kimi_linear_ref_logits(
        params, jnp.asarray(tokens), sizes, cfg.kinds, cfg.expert_offset))


def rel_rms(got, want):
    """RMS of the difference over the vocabulary as a share of the
    reference logits' spread, the worst position."""
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_family_resolves_and_full_forward_matches_the_reference(dtype):
    cfg = tiny(dtype=dtype)
    params = weights_of(cfg)
    if dtype == "bfloat16":  # the cell's draw, at a width where its scales
        # mean something: no router reads a channel that a layer rounds
        model = dict(dataclasses.asdict(cfg), d_model=256)
        cfg, params = bench_family.config(model), bench_family.load_params(
            model, 3000000019)
    fam = model_family(cfg)
    assert fam.name == "kimi_linear" and fam.decode_step_counted is not None
    assert cfg.kinds == "kMKKMK"
    assert cfg.stack_sizes() == {"kda": 4, "mla": 2, "dense": 1, "moe": 5}
    toks = tokens_of(cfg, 3, 27)  # three whole chunks and a part
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, toks)
    want = ref_logits(params, toks, cfg)
    assert got.shape == (3, 27, cfg.vocab_size) and want.std() > 0.25
    if dtype == "float32":
        assert float(np.abs(got - want).max()) < F32_TOL
    else:
        assert rel_rms(np.asarray(got, np.float32), want) < BF16_TOL
    loss = fam.loss(params, tokens_of(cfg, 2, 9), cfg)
    assert np.isfinite(float(loss)) and float(loss) > np.log(cfg.vocab_size) - 1
    axes, shapes = fam.param_axes(), jax.eval_shape(lambda: params)
    assert jax.tree.structure(axes) == jax.tree.structure(shapes)
    assert all(len(a) == s.ndim for a, s in zip(
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)), jax.tree.leaves(shapes)))


def test_the_published_model_and_what_a_config_refuses():
    """27 layers: latent attention at 4, 8, .. 24 and 27 (1-based), KDA at
    the other twenty; layer 1's FFN dense, 26 expert layers."""
    full = KimiLinearConfig()
    assert [i + 1 for i, c in enumerate(full.kinds) if c in "Mm"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert full.kinds[0] == "k" and full.kinds[1:].isupper()
    assert full.stack_sizes() == {"kda": 20, "mla": 7, "dense": 1, "moe": 26}
    assert (full.d_key, full.d_conv, full.latent_dim) == (4096, 12288, 576)
    cell = dataclasses.replace(full, layer_pattern="K" + "MKKK" * 5, n_layer=21)
    assert kimi_linear.stacks_in(cell.kinds) == {
        "kda": 16, "mla": 5, "dense": 1, "moe": 20}
    with pytest.raises(ValueError):
        KimiLinearConfig(layer_pattern="KKL")
    with pytest.raises(ValueError):
        KimiLinearConfig(layer_pattern="KM", n_layer=3)
    with pytest.raises(ValueError):
        KimiLinearConfig(experts_held=16, expert_offset=250)
    with pytest.raises(NotImplementedError):
        kimi_linear.kimi_linear_apply(None, None, full, mesh=object())


def random_rule_inputs(rng, bsz, s, h, dk, dv, strong: bool):
    """A vector gate: ``g [B, S, H, dk]``.  ``strong``: in every head
    channels at ``alpha = 1e-3`` (a whole chunk's decay is far past what
    float32 holds: ``1e-3 ** 16 = 1e-48``) beside channels at ``1 - 1e-6``,
    and everything between, drawn a channel."""
    q, k = rng.normal(size=(2, bsz, s, h, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(bsz, s, h, dv))
    if strong:
        channel = np.exp(rng.uniform(np.log(1e-6), np.log(-np.log(1e-3)),
                                     size=(1, 1, h, dk)))
        channel[..., 0], channel[..., 1] = -np.log(1e-3), 1e-6
        g = -channel * rng.uniform(0.9, 1.0, size=(bsz, s, h, dk))
        g[..., 0], g[..., 1] = np.log(1e-3), np.log1p(-1e-6)
    else:
        g = -rng.uniform(0.001, 0.4, size=(bsz, s, h, dk))
    beta = rng.uniform(0.0, 1.0, size=(bsz, s, h))
    return q, k, v, g, beta


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong_uneven"])
@pytest.mark.parametrize("chunk", [4, 8, 16, 29, 64])
def test_the_chunked_vector_gate_equals_the_recurrence(chunk, strong):
    """``delta_chunked`` with ``g [B, S, H, dk]`` against the recurrence
    itself, position by position, in numpy float64: 29 positions in chunks
    that divide them (29), that do not (4, 8, 16) and that hold them all
    (64); some positions with ``beta = g = 0`` in the middle, which must
    neither decay nor write the state.  At strong decay ``(K * Gamma)(K /
    Gamma)^T`` would overflow in every chunk of 16 or more; the pairwise
    form stays exact."""
    rng = np.random.default_rng(0)
    bsz, s, h, dk, dv = 2, 29, 3, 6, 10
    q, k, v, g, beta = random_rule_inputs(rng, bsz, s, h, dk, dv, strong)
    g[:, 11:14] = beta[:, 11:14] = 0.0
    g[1, 20:] = beta[1, 20:] = 0.0  # a row's padding
    if strong:
        assert np.exp(g).min() < 1.1e-3 and np.exp(g).max() > 1 - 2e-6
        assert g[0, :16].sum(0).min() < -88.8  # past float32 in a chunk
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, last = delta_rule.delta_chunked(
        f32(q), f32(k), f32(v), f32(g), f32(beta), chunk)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    state = np.zeros((bsz, h, dk, dv))
    for t in range(s):
        state = np.exp(g[:, t])[..., None] * state
        kv = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        state = state + np.einsum(
            "bhk,bhv->bhkv", k[:, t], beta[:, t, :, None] * (v[:, t] - kv))
        want = np.einsum("bhkv,bhk->bhv", state, q[:, t])
        np.testing.assert_allclose(o[:, t], want, atol=2e-5)
        if t == 19:
            at_20 = state[1].copy()
    np.testing.assert_allclose(last, state, atol=2e-5)
    np.testing.assert_allclose(last[1], at_20, atol=2e-5)


def test_a_vector_gate_whose_channels_agree_is_the_scalar_gates_rule():
    """Both branches of ``delta_chunked`` on the same decay: ``g [B, S, H]``
    and the same number on every channel."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta = random_rule_inputs(rng, 2, 21, 3, 6, 10, False)
    g = np.broadcast_to(g[..., :1], g.shape)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    for got, want in zip(
            delta_rule.delta_chunked(f32(q), f32(k), f32(v), f32(g),
                                     f32(beta), 8),
            delta_rule.delta_chunked(f32(q), f32(k), f32(v), f32(g[..., 0]),
                                     f32(beta), 8)):
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_references_recurrence_is_the_equations_in_numpy():
    """``kda_recurrence`` against a loop written from the equations: ``S' =
    Diag(alpha_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t
    = S_t^T q_t``, a head at a time, from zero and from a given state."""
    rng = np.random.default_rng(1)
    bsz, s, h, dk, dv = 2, 13, 3, 8, 12
    q, k, v, g, beta = random_rule_inputs(rng, bsz, s, h, dk, dv, True)
    start = rng.normal(size=(bsz, h, dk, dv))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    for first in (None, start):
        o, last = ref.kda_recurrence(
            f32(q), f32(k), f32(v), f32(g), f32(beta),
            None if first is None else f32(first))
        for b in range(bsz):
            for head in range(h):
                S = np.zeros((dk, dv)) if first is None else first[b, head]
                for t in range(s):
                    S = np.diag(np.exp(g[b, t, head])) @ S
                    S = S + beta[b, t, head] * np.outer(
                        k[b, t, head], v[b, t, head] - S.T @ k[b, t, head])
                    np.testing.assert_allclose(
                        o[b, t, head], S.T @ q[b, t, head], atol=2e-5)
                np.testing.assert_allclose(last[b, head], S, atol=2e-5)


# What the published model is NOT, each outside the served type's
# tolerance, which is held on the WORST position: the comparison tells the
# mechanisms apart.  (A rotated ``kr`` and a latent read one position short
# move the two latent layers alone and the median position by 1-2 %; their
# worst positions, early ones, by 9-11 %.)
@pytest.mark.parametrize("switch", [
    {"scalar_gate": True}, {"conv": False}, {"output_gate": "silu"},
    {"rotate_kr": True}, {"renormalise": False},
    {"routed_scaling_factor": 1.0}, {"latent_short": True},
    {"shared": False}], ids=lambda s: next(iter(s)))
def test_another_mechanism_is_outside_the_served_types_tolerance(
        weights, switch):
    cfg, params = weights
    toks = tokens_of(cfg, 2, 27, seed=4)
    got = np.asarray(jax.jit(
        lambda p, t: model_family(cfg).apply(p, t, cfg))(params, toks))
    assert rel_rms(got, ref_logits(params, toks, cfg)) < 1e-4
    other = ref_logits(params, toks, cfg, **switch)
    assert rel_rms(got, other) > 2 * BF16_TOL


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Expert parallelism's sum: the uncut reference's whole expert layer
    (all 16 experts held) equals the shared expert counted once plus the
    routed parts of the shares, each the program's ``moe`` with its own
    ``expert_offset`` over the same router; here four shares of four."""
    cfg = tiny()
    params = weights_of(cfg, seed=5)
    j = 2  # an expert layer
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(23, cfg.d_model)), jnp.float32)
    live = jnp.ones((23,), bool)
    w = {k: v[j] for k, v in params["blocks"]["moe"].items()}
    every = {k: v[j] for k, v in params["experts"].items()}
    sizes = dataclasses.asdict(cfg)
    with jax.default_matmul_precision("highest"):
        whole, sel = ref.moe(u[None], w, every, sizes, 0)
        shared = ref.moe(u[None], w, {k: v[:0] for k, v in every.items()},
                         sizes, 0)[0]
    total, touched, held = np.zeros_like(whole[0]), 0, 0
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = dict(params, experts={
            k: v[:, offset:offset + 4] for k, v in params["experts"].items()})
        y, counts = kimi_linear.moe(u, live, part, j, share)
        total += np.asarray(y) - np.asarray(shared[0])
        held += int(counts["routed_held"])
        touched += int(counts["experts_touched"])
        assert int(counts["routed_total"]) == 23 * cfg.top_k
    np.testing.assert_allclose(total + np.asarray(shared[0]), whole[0],
                               atol=2e-5)
    assert held == 23 * cfg.top_k  # every choice fell on exactly one share
    assert touched == len(np.unique(np.asarray(sel)))
    # renormalised and scaled: a token's routed weights sum to 2.446
    _, weight = kimi_linear.sigmoid_route(
        u, w["router"], w["router_bias"], cfg.top_k,
        cfg.routed_scaling_factor)
    np.testing.assert_allclose(weight.sum(-1), 2.446, rtol=1e-5)
    # the selection bias moves who is CHOSEN, not how the chosen weigh
    biased = w["router_bias"].at[3].set(10.0)
    sel_b, weight_b = kimi_linear.sigmoid_route(
        u, w["router"], biased, cfg.top_k, cfg.routed_scaling_factor)
    assert bool((sel_b == 3).any(-1).all())
    np.testing.assert_allclose(weight_b.sum(-1), 2.446, rtol=1e-5)


def test_the_forward_is_three_scanned_bodies_at_the_cells_pattern():
    """``k`` + ``MKKK`` x 5: the dense layer, then one period scanned five
    times whose run of three KDA layers is itself a scan: the lowered
    program holds each kind of layer's body once."""
    def lowered(periods):
        cfg = tiny(layer_pattern="K" + "MKKK" * periods, n_layer=1 + 4 * periods)
        params = jax.eval_shape(
            lambda: kimi_linear_init(jax.random.PRNGKey(0), cfg))
        return jax.jit(lambda p, t: kimi_linear.kimi_linear_apply(
            p, t, cfg)).lower(params, jax.ShapeDtypeStruct(
                (1, 32), jnp.int32)).as_text()

    two, five = lowered(2), lowered(5)
    assert len(five) < 1.02 * len(two)  # the same bodies, another count
    assert five.count("stablehlo.while") == two.count("stablehlo.while")


def test_importing_the_family_runs_no_jax_computation():
    for module in (kimi_linear, kimi_linear_decode, delta_rule):
        made = [name for name, value in vars(module).items()
                if isinstance(value, (jax.Array, np.ndarray))]
        assert not made, made
        source = inspect.getsource(module)
        assert "jax.devices" not in source and "device_put" not in source


def test_bench_family_builds_the_programs_tree_and_uneven_gates():
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16")), d_model=256)
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: kimi_linear_init(
        jax.random.PRNGKey(0), KimiLinearConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    kda = params["blocks"]["kda"]
    # a channel's decay before the token moves it: -log(alpha) in [1e-3, 0.3]
    decay = (jnp.exp(kda["a_log"])[..., None] * jax.nn.softplus(
        kda["dt_bias"]).reshape(*kda["a_log"].shape, -1))
    assert 1e-3 - 1e-6 <= float(decay.min()) and float(decay.max()) <= 0.3 + 1e-5
    # in EVERY head 1 - alpha differs by more than a factor of ten across
    # its channels: a scalar gate is a different model
    spread = (-jnp.expm1(-decay)).max(-1) / (-jnp.expm1(-decay)).min(-1)
    assert float(spread.min()) > 10.0
    # the routers' channels: read by routers alone, written by no layer
    own = 256 // bench_family.SCALES["router_share"]
    assert not np.asarray(params["blocks"]["moe"]["router"][:, own:]).any()
    for stack, name in (("kda", "w_o"), ("dense", "w_down"),
                        ("moe", "w_down")):
        assert not np.asarray(
            params["blocks"][stack][name][..., :own], np.float32).any()
    assert not np.asarray(params["blocks"]["mla"]["wo"][..., :own],
                          np.float32).any()
    assert not np.asarray(params["experts"]["w_down"][..., :own],
                          np.float32).any()
    # no greedy stream ends early: the stop id's logit is 0
    from ray_tpu.llm.tokenizer import ByteTokenizer
    assert not np.asarray(params["lm_head"][ByteTokenizer.EOS]).any()


def test_the_cells_draw_moves_the_decay_by_the_token_and_stays_uneven():
    """Under the cell's draw at a width where the scales mean something (d
    256): token by token ``alpha`` stays in (0.5, 1) with the slow channels
    above 0.99, ``beta`` spans (0, 1), and within a head the channels'
    ``1 - alpha`` still differ by more than ten at every token."""
    model = dict(dataclasses.asdict(tiny(dtype="float32")), d_model=256)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 11)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(1, 200, cfg.d_model)), jnp.float32)
    _, _, g, beta = kimi_linear.kda_project(u, params["blocks"]["kda"], 0, cfg)
    alpha, beta = np.exp(np.asarray(g)), np.asarray(beta)
    assert alpha.shape == (1, 200, 4, 16) and beta.shape == (1, 200, 4)
    assert alpha.min() > 0.4 and alpha.max() < 1 and np.median(alpha) > 0.9
    assert beta.min() > 0 and beta.max() < 1
    uneven = (1 - alpha).max(-1) / (1 - alpha).min(-1)
    assert uneven.min() > 10
