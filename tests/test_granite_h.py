"""Granite-4.0-H family (``ray_tpu/models/granite_h*.py``) against its plain
float32 reference (``benchmarks/reference/granite_h_ref.py``: Mamba-2's
RECURRENCE, dense softmax), and that reference against ``transformers``' own
torch model, at tiny widths on the CPU with seeded weights: pattern
``MM*MMM*M``, 8 heads of 16 with a state of 16 in ONE group, chunks of 8, an
attention scale that is not ``D^-1/2``.  Logits, not tokens.  Each tolerance
says what it allows for.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granite_h as bench_family
from benchmarks.lib import bench_server
from benchmarks.reference import granite_h_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (GraniteHConfig, granite_h, granite_h_decode,
                            granite_h_init, layers, mamba2, model_family,
                            nemotron_h, nemotron_h_decode)

# float32 against float32, the largest difference of a logit as a share of
# the logits' spread (``off``; the spread is ~0.02 here: a tied table at 0.02
# over ``logits_scaling`` 8): the two differ by the order of their sums only
# (the chunked scan against the recurrence, exp(a) exp(b) against exp(a +
# b), blocked softmax against dense) through sixteen branches, so this is
# some tens of units in the last place (5e-6 measured; the limit leaves
# ten times that).  States and cache rows, which are ~1, are held to it as
# an absolute difference.
F32_TOL = 5e-5
# bfloat16 products (2^-9 a rounding, some sixty of them through eight
# layers and the head) against float32, as a share of the logits' spread:
# the benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).
BF16_TOL = 0.03


def tiny(**kw):
    return GraniteHConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): the table stays at 0.02 (times 12
    the stream starts at 0.24, and what is left of a token's own embedding
    at the tied head is one logit among 512, not the winner), the matrices
    times 5 and the queries' and keys' times 5 again (scores of spread ~1 at
    a scale of 1/32), so that every mixer moves the logits THROUGH the
    multipliers and a fault in one shows; a convolution bias that is not
    zero."""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a
        if name == "conv_b":
            return a + 0.1 * jnp.cos(jnp.arange(a.size, dtype=a.dtype)
                                     ).reshape(a.shape)
        if name in ("wq", "wk"):
            return a * 25
        return a * 5 if a.ndim >= 3 and name != "conv_w" else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(granite_h_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, **sizes):
    return np.asarray(ref.granite_h_ref_logits(
        params, jnp.asarray(tokens), dict(dataclasses.asdict(cfg), **sizes),
        cfg.kinds))


def off(got, want):
    """The largest difference of a logit as a share of the reference
    logits' spread."""
    return float(np.abs(got - want).max() / want.std())


def rel_rms(got, want):
    """RMS of the difference over the vocabulary as a share of the
    reference logits' spread, the worst position."""
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


def test_family_resolves_and_full_forward_matches_the_reference(weights):
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "granite_h" and fam.decode_step_counted is not None
    assert cfg.kinds == "MM*MMM*M" and cfg.n_groups == 1
    assert "lm_head" not in params  # the table is the head
    toks = tokens_of(cfg, 3, 27)  # three whole chunks and a part
    got = jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, toks)
    want = ref_logits(params, toks, cfg)
    assert got.shape == (3, 27, cfg.vocab_size) and want.std() > 0.01
    assert off(got, want) < F32_TOL
    loss = fam.loss(params, tokens_of(cfg, 2, 9), cfg)
    assert np.isfinite(float(loss)) and float(loss) > 1.0
    axes, shapes = fam.param_axes(), jax.eval_shape(lambda: params)
    assert jax.tree.structure(axes) == jax.tree.structure(shapes)
    assert all(len(a) == s.ndim for a, s in zip(
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)), jax.tree.leaves(shapes)))
    # the published model: 36 Mamba-2 layers and 4 attentions, a period of
    # ten, five layer bodies in the sequence's program
    full = GraniteHConfig()
    assert full.kinds.count("M") == 36 and full.n_layer == 40
    assert [i for i, k in enumerate(full.kinds) if k == "*"] == [5, 15, 25, 35]
    assert (full.d_inner, full.d_conv, full.query_scale) == (4096, 4352, 0.125)
    assert layers.layer_plan(full.kinds) == [
        ([("M", 5)], 1), ([("*", 1), ("M", 9)], 3), ([("*", 1)], 1),
        ([("M", 4)], 1)]
    with pytest.raises(ValueError):
        GraniteHConfig(layer_pattern="MME")
    with pytest.raises(ValueError):
        GraniteHConfig(layer_pattern="M*", n_layer=3)


def test_the_mamba_mixer_is_nemotrons_not_a_copy():
    """One Mamba-2 in the tree: both families call ``mamba2``'s functions,
    this one on its own config, whose field names they read."""
    assert granite_h.mamba_sequence is nemotron_h.mamba_sequence
    assert granite_h.mamba_sequence is mamba2.mamba_sequence
    assert granite_h_decode.mamba_step is nemotron_h_decode.mamba_step
    assert granite_h_decode.mamba_step is mamba2.mamba_step
    for module in (granite_h, granite_h_decode):
        source = inspect.getsource(module)
        assert "def ssd_chunked" not in source
        assert "def mamba_" not in source


@pytest.mark.parametrize("pattern", ["MM*MMM*M", "*MMM*MMM*MMMM", "MMMMMM"])
def test_folded_layers_are_the_layers_in_order(pattern):
    """The sequence's program scans runs of one kind and groups that repeat
    (``layer_plan``): whatever the pattern folds to, layer ``i`` reads the
    ``i``-th MLP and the right mixer of its kind."""
    cfg = tiny(layer_pattern=pattern, n_layer=len(pattern))
    params = weights_of(cfg, seed=4)
    toks = tokens_of(cfg, 2, 11, seed=4)
    got = jax.jit(lambda p, t: granite_h.granite_h_apply(p, t, cfg))(
        params, toks)
    assert off(got, ref_logits(params, toks, cfg)) < F32_TOL
    folded = sum(n * repeats for group, repeats in layers.layer_plan(
        pattern) for _, n in group)
    assert folded == len(pattern)


def hf_model(cfg, params):
    """``transformers``' ``GraniteMoeHybridForCausalLM`` at ``cfg``'s sizes
    with ``params`` copied in (its plain torch path: no CUDA here)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    hf_cfg = tf.GraniteMoeHybridConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, shared_intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layer, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head,
        layer_types=["mamba" if k == "M" else "attention" for k in cfg.kinds],
        mamba_n_heads=cfg.mamba_num_heads, mamba_d_head=cfg.mamba_head_dim,
        mamba_d_state=cfg.ssm_state_size, mamba_n_groups=cfg.n_groups,
        mamba_d_conv=cfg.conv_kernel, mamba_chunk_size=cfg.chunk_size,
        mamba_expand=cfg.d_inner // cfg.d_model, mamba_conv_bias=True,
        mamba_proj_bias=False, num_local_experts=0, num_experts_per_tok=0,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, position_embedding_type="nope",
        tie_word_embeddings=True, rms_norm_eps=cfg.rms_eps,
        attention_dropout=0.0)
    assert hf_cfg.hidden_size // hf_cfg.num_attention_heads == cfg.head_dim
    model = tf.GraniteMoeHybridForCausalLM(hf_cfg).eval()
    assert model.lm_head.weight is model.model.embed_tokens.weight

    def put(param, value):
        value = torch.tensor(np.asarray(value, np.float32))
        assert param.shape == value.shape, (param.shape, value.shape)
        with torch.no_grad():
            param.copy_(value)

    put(model.model.embed_tokens.weight, params["wte"])
    put(model.model.norm.weight, params["rms_f"])
    for layer, (kind, w, w_mlp) in zip(
            model.model.layers, ref.layer_weights(params, cfg.kinds)):
        put(layer.input_layernorm.weight, w["rms"])
        put(layer.post_attention_layernorm.weight, w_mlp["rms"])
        put(layer.shared_mlp.input_linear.weight, jnp.concatenate(
            [w_mlp["w_gate"], w_mlp["w_up"]], axis=1).T)
        put(layer.shared_mlp.output_linear.weight, w_mlp["w_down"].T)
        if kind == "M":
            m = layer.mamba
            put(m.in_proj.weight, jnp.concatenate(
                [w["w_z"], w["w_xbc"], w["w_dt"]], axis=1).T)
            put(m.conv1d.weight, w["conv_w"].T[:, None, :])
            put(m.conv1d.bias, w["conv_b"])
            put(m.dt_bias, w["dt_bias"])
            put(m.A_log, w["a_log"])
            put(m.D, w["d_skip"])
            put(m.norm.weight, w["norm"])
            put(m.out_proj.weight, w["w_out"].T)
        else:
            a, d = layer.self_attn, cfg.d_model
            put(a.q_proj.weight, w["wq"].reshape(d, -1).T)
            put(a.k_proj.weight, w["wk"].reshape(d, -1).T)
            put(a.v_proj.weight, w["wv"].reshape(d, -1).T)
            put(a.o_proj.weight, w["wo"].reshape(-1, d).T)
    return model


def test_the_reference_is_transformers_granitemoehybrid(weights):
    """The reference against the published modelling code's torch path with
    the same weights copied in: all four multipliers, ``nope``, the tied
    head, the gate before the norm, the convolution's bias, ``in_proj``'s and
    ``input_linear``'s column order.  27 positions are three of its chunks
    of 8 and a part.  Float32 both, sums in another order: 1e-5 of logits ~1
    wide (2e-6 measured)."""
    torch = pytest.importorskip("torch")
    cfg, params = weights
    model = hf_model(cfg, params)
    toks = tokens_of(cfg, 2, 27, seed=3)
    with torch.no_grad():
        theirs = model(torch.tensor(toks.astype(np.int64))).logits.numpy()
    ours = ref_logits(params, toks, cfg)
    assert ours.std() > 0.01 and off(ours, theirs) < F32_TOL


FAULTS = {
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "logits_scaling": dict(logits_scaling=1.0),
    # D^-1/2 = 0.25 where the config says 1/32
    "attention_at_rsqrt_d": dict(attention_multiplier=16 ** -0.5),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_dropped_multiplier_is_outside_the_tolerance(weights, fault):
    """A reference that leaves one multiplier out (or scales the scores by
    ``D^-1/2``) is off the program by MORE than the served type's tolerance,
    not only the float32 one: each of the four is seen by the comparison the
    benchmark makes."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 21, seed=6)
    got = np.asarray(jax.jit(
        lambda p, t: granite_h.granite_h_apply(p, t, cfg))(params, toks))
    assert rel_rms(got, ref_logits(params, toks, cfg)) < 1e-4
    assert rel_rms(got, ref_logits(params, toks, cfg, **FAULTS[fault])
                   ) > BF16_TOL


def through_the_cache(cfg, params, toks, lengths, steps, padded_to=None,
                      state_dtype=None):
    """Ragged batch: prefill each row's first ``lengths[b]`` tokens (padded
    to ``padded_to``), then ``steps`` decode steps at each row's own
    position.  Returns the logits that predict positions ``lengths[b] + i``,
    the cache after prefill and the counts of every program run."""
    fam = model_family(cfg)
    lengths = np.asarray(lengths, np.int32)
    width = padded_to or toks.shape[1]
    cache = fam.init_cache(cfg, len(lengths), max(width, toks.shape[1] + 1))
    if state_dtype is not None:  # the lower-precision control
        cache["ssm"] = cache["ssm"].astype(state_dtype)
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
def test_prefill_then_decode_through_the_cache_matches_full_forward(dtype):
    cfg = tiny(dtype=dtype)
    params = weights_of(cfg, seed=1)
    lengths, steps = [5, 9, 14], 8
    toks = tokens_of(cfg, 3, 23, seed=1)
    got, cache, counts = through_the_cache(cfg, params, toks, lengths, steps,
                                           padded_to=24)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, n - 1:n + steps] for b, n in enumerate(lengths)])
    if dtype == "float32":
        assert off(got, want) < F32_TOL
    else:
        assert rel_rms(got, want) < BF16_TOL
    # a prefill scanned its true positions in three chunks of 8 a row
    assert int(counts[0]["ssm_positions"]) == sum(lengths)
    assert int(counts[0]["ssm_chunk_positions"]) == 3 * 24
    for step in counts[1:]:
        assert int(step["ssm_positions"]) == 3
        assert int(step["ssm_chunk_positions"]) == 3
    # The two kinds of leaf: positions on keys and values, none on state.
    assert cache["k"].shape == (2, 3, cfg.n_kv_head, 24, cfg.head_dim)
    assert cache["ssm"].shape == (6, 3, 8, 16, 16)
    assert cache["conv"].shape == (6, 3, 3 * cfg.d_conv)
    assert cache["ssm"].dtype == cache["conv"].dtype == jnp.float32


@pytest.mark.parametrize("n", [5, 8, 9, 19])
def test_a_padded_prefill_leaves_the_state_of_the_true_length(weights, n):
    """The engine pads a prompt to a rung; the state spliced into the slot
    must be the state after token ``n - 1``, not after the rung's last
    position: ``n`` on both sides of a chunk boundary (chunks of 8), padded
    to 32, against the same prompt prefilled at exactly ``n``.  The padding
    is not zeros: whatever the rung holds beyond ``n`` must not matter."""
    cfg, params = weights
    fam = model_family(cfg)
    toks = tokens_of(cfg, 1, 32, seed=n)
    run = jax.jit(lambda p, t, c: fam.prefill(p, t, jnp.asarray([n]), c, cfg))
    exact_logits, exact = run(params, toks[:, :n], fam.init_cache(cfg, 1, n))
    padded_logits, padded = run(params, toks, fam.init_cache(cfg, 1, 32))
    # float32 sums in another order (chunks of the padded length)
    assert off(np.asarray(padded_logits), np.asarray(exact_logits)) < F32_TOL
    for leaf in ("ssm", "conv"):
        assert padded[leaf].shape == exact[leaf].shape
        np.testing.assert_allclose(padded[leaf], exact[leaf], atol=F32_TOL)
    assert float(jnp.abs(padded["ssm"]).max()) > 1e-2  # there is a state
    for leaf in ("k", "v"):
        np.testing.assert_allclose(padded[leaf][:, :, :, :n], exact[leaf],
                                   atol=F32_TOL)
        assert float(jnp.abs(exact[leaf]).max()) > 1e-2
    # the convolution's state is layer 0's last three TRUE inputs, oldest
    # first (zeros before the prompt's start)
    w = {k: v[0] for k, v in params["blocks"]["mamba"].items()}
    x = ref.ref_embed(params, jnp.asarray(toks[:, :n]), dataclasses.asdict(cfg))
    xbc = np.asarray(ref._rms(x, w["rms"], cfg.rms_eps)[0] @ w["w_xbc"])
    want = np.concatenate([np.zeros((3, xbc.shape[1]), np.float32), xbc])[-3:]
    np.testing.assert_allclose(exact["conv"][0, 0].reshape(3, -1), want,
                               atol=F32_TOL)


@pytest.mark.parametrize("lengths", [[1, 2, 6], [5, 9, 14]], ids=str)
def test_each_decode_step_shifts_every_layers_window_by_its_token(lengths):
    """The ``conv`` leaf goes through the Mamba-2 layers whole and each
    shifts its own layer of it where it lies: after every step, in EVERY
    layer, the window is the last three inputs of the convolution, oldest
    first (zeros before a prompt's start: rows of 1 and 2 tokens), which is
    what a prefill of the same tokens leaves.  What it held moved one place
    to the bit, and the ``ssm`` leaf beside it is the prefill's too.  A
    layer shifted twice (a cloned update), a layer left stale or a window
    written into another layer's place fails here."""
    cfg = tiny()
    params = weights_of(cfg, seed=2)
    fam = model_family(cfg)
    lengths, steps = np.asarray(lengths, np.int32), 5
    width = int(lengths.max()) + steps
    toks = tokens_of(cfg, 3, width, seed=2)
    prefill = jax.jit(lambda n: fam.prefill(
        params, toks, n, fam.init_cache(cfg, 3, width), cfg)[1])
    decode = jax.jit(lambda t, pos, c: fam.decode_step(
        params, t, pos, c, cfg)[1])
    cache, rows, c = prefill(lengths), np.arange(3), cfg.d_conv
    for i in range(steps):
        pos, old = lengths + i, np.asarray(cache["conv"])
        cache = decode(toks[rows, pos], pos, cache)
        new, want = np.asarray(cache["conv"]), prefill(pos + 1)
        np.testing.assert_array_equal(new[..., :-c], old[..., c:])
        np.testing.assert_allclose(new, want["conv"], atol=F32_TOL)
        np.testing.assert_allclose(cache["ssm"], want["ssm"], atol=F32_TOL)
        # every layer's newest input is its own and none is a repeat
        newest = new[..., -c:]
        assert np.abs(newest - new[..., -2 * c:-c]).max(-1).min() > 1e-2
        assert np.abs(newest[1:] - newest[:-1]).max(-1).min() > 1e-2


@pytest.mark.parametrize("chunk", [4, 8, 16, 29, 64])
def test_the_chunked_scan_equals_the_recurrence_at_one_group(chunk):
    """``mamba2.ssd_chunked`` at ``G`` = 1 (every head reads the one
    ``B``, ``C``) against the recurrence itself, position by position, in
    numpy float64: 29 positions in chunks that divide them (29), that do not
    (4, 8, 16) and that hold them all (64); some positions with ``dt = 0``
    in the middle and a row's padding, which must neither decay nor feed the
    state."""
    rng = np.random.default_rng(0)
    bsz, s, h, p, n = 2, 29, 6, 5, 7
    x = rng.normal(size=(bsz, s, h, p))
    b, c = rng.normal(size=(2, bsz, s, 1, n))
    dt = rng.uniform(0.01, 0.5, size=(bsz, s, h))
    dt[:, 11:14] = 0.0
    dt[1, 20:] = 0.0  # a row's padding
    a, d_skip = -rng.uniform(0.5, 4.0, size=h), rng.normal(size=h)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, last = mamba2.ssd_chunked(
        f32(x), f32(dt), f32(a), f32(b), f32(c), f32(d_skip), chunk,
        jnp.float32)
    state = np.zeros((bsz, h, p, n))
    for t in range(s):
        state = (np.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None]
                 * b[:, t, 0][:, None, None, :])
        want = (state * c[:, t, 0][:, None, None, :]).sum(-1) + (
            d_skip[:, None] * x[:, t])
        np.testing.assert_allclose(y[:, t], want, atol=2e-5)
        if t == 19:
            at_20 = state[1].copy()
    np.testing.assert_allclose(last, state, atol=2e-5)
    np.testing.assert_allclose(last[1], at_20, atol=2e-5)


def test_the_harness_two_layer_cut_runs_two_mamba_layers_and_their_mlps():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]``.  The published
    pattern starts ``MM``: two Mamba-2 layers with the first two of the
    forty MLPs, through a cache of both kinds of leaf (the attention's
    empty), in the served type against the float32 reference, under the
    benchmark's own limit.  No attention layer is in its sight."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16")), d_model=256,
                 layer_pattern=granite_h.PUBLISHED_PATTERN, n_layer=40)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    assert params["blocks"]["mlp"]["w_down"].shape[0] == 40
    cut = dataclasses.replace(cfg, n_layer=2)
    assert cut.kinds == "MM"
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 24 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 24, 3)
    want = np.asarray(bench_family.reference_logits(
        part, jnp.asarray(toks), cut))[0]
    errs = bench_server.logit_errors(got, [want[23 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 32)
    assert cache["k"].shape[0] == 0 and cache["ssm"].shape[0] == 2


def test_state_kept_in_bfloat16_is_outside_the_tolerance(weights):
    """The lower-precision control: everything float32 but the Mamba-2
    state ``S``, which the cache keeps in bfloat16 (rounded after prefill and
    after every decode step).  That is off the reference by twenty to seventy
    times what the float32 program is (2-7 times the limit): the comparison sees one leaf's type.  The
    first logits, which prefill computes before the state is rounded, are
    untouched."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 28, seed=2)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, 18:19 + 8] for b in range(2)])
    good, _, _ = through_the_cache(cfg, params, toks, [19, 19], 8)
    bad, cache, _ = through_the_cache(cfg, params, toks, [19, 19], 8,
                                      state_dtype=jnp.bfloat16)
    assert cache["ssm"].dtype == jnp.bfloat16
    assert off(good, want) < F32_TOL
    assert off(bad[:, 0], want[:, 0]) < F32_TOL
    # every decode step of every row is outside it, the worst five times
    assert min(off(bad[b, i], want[b, i])
               for b in range(2) for i in range(1, 9)) > F32_TOL
    assert off(bad, want) > 5 * F32_TOL


def test_importing_the_family_runs_no_jax_computation():
    """Every worker imports ``ray_tpu.models`` (the training gang's too): the
    family's two modules define functions and constants and nothing else; no
    array is made at import."""
    for module in (granite_h, granite_h_decode):
        made = [name for name, value in vars(module).items()
                if isinstance(value, (jax.Array, np.ndarray))]
        assert not made, made
        source = inspect.getsource(module)
        assert "jax.devices" not in source and "device_put" not in source


# ------------------------------------------------------------------ engine
PROMPTS = ["the first prompt", "second", "a third, somewhat longer prompt",
           "and a fourth one to fill the last slot of the four"]


def make_engine(slots=4, max_seq_len=64):
    cfg = tiny()
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


def test_engine_slots_hold_mamba_state_beside_keys_and_values():
    """What ``llm/engine.py`` needed for a model that is mostly recurrent
    state, with a tied head and four multipliers: nothing.  A slot's second
    tenant gives the ids it gives alone (the state is replaced whole at
    admission, whatever the last tenant left); a request among full slots
    gives the ids it gives alone; streamed equals unary; the family's counts
    reach ``stats()``."""
    params = SamplingParams(max_tokens=12, stop_token=-1)
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    # One slot: every request but the first is the slot's next tenant, and
    # the longest prompt's state is what the shortest finds there.
    one = make_engine(slots=1)
    order = [2, 1, 3, 0]
    assert by_hand(one, [PROMPTS[i] for i in order], params) == [
        alone[i] for i in order]
    # Four slots, all full, admitted in one step and decoded together.
    full = make_engine()
    assert by_hand(full, PROMPTS, params) == alone
    assert all(s is None for s in full.slots)
    # Through the loop: unary and streamed.
    assert [r["token_ids"] for r in full.generate(PROMPTS, params)] == alone
    # (every id one visible character: bytes of half a UTF-8 sequence
    # would render by where a chunk ends)
    full.tokenizer = bench_server.VisibleTokenizer()
    rid = full.add_request(PROMPTS[2], params)
    streamed = "".join(full.stream_request(rid))
    assert bench_server.ids_of(streamed) == alone[2]
    stats = full.stats()
    # Drained: every decode step's vector has been read, a step after it.
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0  # every stream ended by count
    # a prompt of n characters is n + 1 tokens, scanned in chunks of 8 up
    # to the one rung of 64; a decode step serves its live rows of four
    prompt_tokens = sum(len(p) + 1 for p in PROMPTS)
    assert stats["prefill_ssm_positions"] == 2 * prompt_tokens + len(
        PROMPTS[2]) + 1
    assert stats["prefill_ssm_chunk_positions"] == stats["admitted"] * 64
    assert 0 < stats["ssm_positions"] <= stats["ssm_chunk_positions"]
    assert stats["ssm_chunk_positions"] == 4 * stats["decode_steps"]
    full.shutdown()


def test_idle_slots_stay_finite_through_two_hundred_steps():
    """Every slot is decoded every step, tenant or not: the state of the
    slots nobody occupies (token 0 at position 0, over and over, on whatever
    the last tenant left) must stay finite for a whole run: every step
    decays it by ``exp(dt A) < 1`` and adds a bounded term."""
    engine = make_engine(slots=4, max_seq_len=256)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    by_hand(engine, PROMPTS, params)  # every slot has had a tenant
    long = SamplingParams(max_tokens=200, stop_token=-1)
    assert len(by_hand(engine, ["one long answer"], long)[0]) == 200
    assert engine.stats()["decode_steps"] >= 200
    for leaf in ("ssm", "conv", "k", "v"):
        assert bool(jnp.isfinite(engine.cache[leaf]).all()), leaf
    assert float(jnp.abs(engine.cache["ssm"][:, 1:]).max()) < 1e3
    # and the next tenant of an idle slot is none the worse for it
    again = by_hand(engine, PROMPTS[:1], params)
    assert again == by_hand(make_engine(), PROMPTS[:1], params)


def test_the_engine_and_serve_know_nothing_of_the_family():
    """Five ``model_config`` PRs added a family with no edit of the engine
    or of ``serve/`` for it; so does this one."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    for path in [root / "llm" / "engine.py", *(root / "serve").rglob("*.py")]:
        text = path.read_text().lower()
        assert not any(word in text for word in (
            "granite", "ssm_positions", "ssm_chunk", "logits_scaling",
            "residual_multiplier")), path


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16"))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: granite_h_init(
        jax.random.PRNGKey(0), GraniteHConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    # a head's decay before the token moves it: exp(-delta) in 0.905-0.9999
    mamba = params["blocks"]["mamba"]
    decay = jnp.exp(mamba["a_log"]) * jax.nn.softplus(mamba["dt_bias"])
    assert 1e-4 * 0.999 <= float(decay.min())
    assert float(decay.max()) <= 0.1 * 1.001
    assert float(jnp.abs(mamba["conv_b"]).max()) > 0  # a bias to leave out
    # no greedy stream ends early: the stop id's row of the tied table is 0
    from ray_tpu.llm.tokenizer import ByteTokenizer
    assert not np.asarray(params["wte"][ByteTokenizer.EOS]).any()
    assert np.asarray(params["wte"][ByteTokenizer.EOS + 1]).any()


def test_the_cells_draw_keeps_the_state_old_and_the_scores_spread():
    """Under the cell's draw at a width where the scales mean something (d
    256, the published heads' sizes): ``exp(dt A)`` stays within 0.7-0.99999
    token by token with a median over 0.97, so a state written a hundred
    tokens ago is still read; a head's state adds to ``y`` about what its
    skip does, slow head or fast; the stream starts at RMS 0.5."""
    model = dict(dataclasses.asdict(GraniteHConfig(
        dtype="float32", vocab_size=512, d_model=256, mamba_num_heads=8,
        layer_pattern="MM*", n_layer=3, d_ff=256)))
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 11)
    m = params["blocks"]["mamba"]
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(1, 400, cfg.d_model)), jnp.float32)
    _, _, dt = mamba2.mamba_project(u, m, 0, cfg)
    alpha = np.exp(np.asarray(dt) * -np.exp(np.asarray(m["a_log"][0])))
    assert alpha.min() > 0.7 and alpha.max() < 1 and np.median(alpha) > 0.97
    assert (alpha.mean(axis=(0, 1)) > 0.999).any()  # a head that is slow
    toks = jnp.asarray(tokens_of(cfg, 1, 64))
    x = ref.ref_embed(params, toks, dataclasses.asdict(cfg))
    assert 0.4 < float(jnp.sqrt((x * x).mean())) < 0.6


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_mamba_state_beside_keys(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache with state beside them when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "granite_h" in str(err.value) and "ssm" in str(err.value)
