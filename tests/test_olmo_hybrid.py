"""Olmo-Hybrid family (``ray_tpu/models/olmo_hybrid*.py``) against its plain
float32 reference (``benchmarks/reference/olmo_hybrid_ref.py``: the gated
delta rule's RECURRENCE, dense softmax), at tiny widths on the CPU with seeded
weights: pattern ``FLLFLL``, 4 heads with keys of 8 and values of 64 (two
heads' states share a row of the cache), chunks of 8.  Logits, not tokens.
Each tolerance says what it allows for.
"""

import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import olmo_hybrid as bench_family
from benchmarks.lib import bench_server
from benchmarks.reference import olmo_hybrid_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (OlmoHybridConfig, model_family, olmo_hybrid,
                            olmo_hybrid_decode, olmo_hybrid_init)
from ray_tpu.ops import delta_update

# float32 against float32: the two differ by the order of their sums only
# (the chunked scan and its triangular solve against the recurrence,
# exp(a) exp(b) against exp(a + b), decode's ``alpha S^T q + (k . q) delta``
# against ``S_t^T q``); logits are ~1 wide and pass through six blocks, so
# this is some tens of units in the last place (1e-6 measured; the limit
# leaves ten times that).
F32_TOL = 2e-5
# bfloat16 products (2^-9 a rounding, some forty of them through six blocks
# and the head) against float32, as a share of the logits' spread: the
# benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).
BF16_TOL = 0.03


def tiny(**kw):
    return OlmoHybridConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): scale the embedding to RMS 1 and
    the matrices by 5, so that every mixer moves the logits and a fault in
    one shows.  (``beta = 2 sigmoid(u Wb)`` then spans 0.5-1.5.)"""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a * 50
        big = a.ndim >= 3 or name == "lm_head"
        return a * 5 if big and name not in ("conv_w", "q_norm", "k_norm") else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(olmo_hybrid_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg):
    return np.asarray(bench_family.reference_logits(
        params, jnp.asarray(tokens), cfg))


def test_family_resolves_and_full_forward_matches_the_reference(weights):
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "olmo_hybrid" and fam.decode_step_counted is not None
    assert cfg.kinds == "FLLFLL" and cfg.state_pack == 2
    toks = tokens_of(cfg, 3, 27)  # three whole chunks and a part
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
    # the published model: 24 linear and 8 full layers, the period LLLF
    full = OlmoHybridConfig()
    assert full.kinds.count("L") == 24 and full.kinds[3::4] == "F" * 8
    assert (full.d_key, full.d_value, full.d_conv) == (2880, 5760, 11520)
    with pytest.raises(ValueError):
        OlmoHybridConfig(layer_pattern="LLM")
    with pytest.raises(ValueError):
        OlmoHybridConfig(layer_pattern="LF", n_layer=3)


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
        cache["state"] = cache["state"].astype(state_dtype)
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


def rel_rms(got, want):
    """RMS of the difference over the vocabulary as a share of the
    reference logits' spread, the worst position."""
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


@pytest.fixture(params=["xla", "kernel"])
def state_update(request, monkeypatch):
    """The decode step's way through a linear layer's state: what the CPU
    runs unasked (``ops.delta_update``'s XLA formulation), then the Pallas
    kernel a TPU runs, forced here in interpret mode."""
    if request.param == "kernel":
        monkeypatch.setattr(
            olmo_hybrid_decode, "delta_update", functools.partial(
                delta_update.delta_update, force_pallas=True))
    return request.param


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_full_forward(
        dtype, state_update):
    cfg = tiny(dtype=dtype)
    params = weights_of(cfg, seed=1)
    lengths, steps = [5, 9, 14], 8
    toks = tokens_of(cfg, 3, 23, seed=1)
    got, cache, counts = through_the_cache(cfg, params, toks, lengths, steps,
                                           padded_to=24)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, n - 1:n + steps] for b, n in enumerate(lengths)])
    if dtype == "float32":
        assert float(np.abs(got - want).max()) < F32_TOL
    else:
        assert rel_rms(got, want) < BF16_TOL
    # a prefill scanned its true positions in three chunks of 8 a row
    assert int(counts[0]["delta_positions"]) == sum(lengths)
    assert int(counts[0]["delta_chunk_positions"]) == 3 * 24
    for step in counts[1:]:
        assert int(step["delta_positions"]) == 3
        assert int(step["delta_chunk_positions"]) == 3
    # The two kinds of leaf: positions on keys and values, none on state;
    # two heads' [8, 64] states side by side in a row of 128 lanes.
    assert cache["k"].shape == (2, 3, cfg.n_head, 24, cfg.head_dim)
    assert cache["state"].shape == (4, 3, 2, 8, 128)
    assert cache["conv"].shape == (4, 3, 3 * cfg.d_conv)
    assert cache["state"].dtype == cache["conv"].dtype == jnp.float32


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
    assert float(jnp.abs(padded_logits - exact_logits).max()) < F32_TOL
    for leaf in ("state", "conv"):
        assert padded[leaf].shape == exact[leaf].shape
        np.testing.assert_allclose(padded[leaf], exact[leaf], atol=F32_TOL)
    assert float(jnp.abs(padded["state"]).max()) > 1e-2  # there is a state
    for leaf in ("k", "v"):
        np.testing.assert_allclose(padded[leaf][:, :, :, :n], exact[leaf],
                                   atol=F32_TOL)
    # the convolution's state is its last three TRUE inputs, oldest first:
    # layer 1 is the first linear layer, and reads what layer 0 (full) left
    want = first_linear_layers_conv_inputs(params, toks[:, :n], cfg)
    np.testing.assert_allclose(exact["conv"][0, 0].reshape(3, -1), want,
                               atol=F32_TOL)
    # and the state is the recurrence's own at n, head by head
    state = olmo_hybrid.unpack_state(exact["state"][0], cfg.state_pack)
    np.testing.assert_allclose(
        state, first_linear_layers_state(params, toks[:, :n], cfg),
        atol=F32_TOL)


@pytest.mark.parametrize("lengths", [[1, 2, 6], [5, 9, 14]], ids=str)
def test_each_decode_step_shifts_every_layers_window_by_its_token(lengths):
    """The ``conv`` leaf goes through the linear layers whole and each
    shifts its own layer of it where it lies: after every step, in EVERY
    layer, the window is the last three inputs of the convolution, oldest
    first (zeros before a prompt's start: rows of 1 and 2 tokens), which is
    what a prefill of the same tokens leaves.  What it held moved one place
    to the bit, and the ``state`` leaf beside it is the prefill's too.  A
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
        np.testing.assert_allclose(cache["state"], want["state"],
                                   atol=F32_TOL)
        # every layer's newest input is its own and none is a repeat
        newest = new[..., -c:]
        assert np.abs(newest - new[..., -2 * c:-c]).max(-1).min() > 1e-2
        assert np.abs(newest[1:] - newest[:-1]).max(-1).min() > 1e-2


def first_linear_layer(params, toks, cfg):
    """(its weights, its normed input): the stream after layer 0 (full)."""
    sizes = dataclasses.asdict(cfg)
    x = jnp.asarray(params["wte"][toks], jnp.float32)
    (kind0, w0), (kind1, w1) = list(ref.layer_weights(params, cfg.kinds))[:2]
    assert kind0 + kind1 == "FL"
    x = ref.ref_layer(x, "F", w0, sizes)
    return w1, x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps)


def first_linear_layers_conv_inputs(params, toks, cfg):
    w, u = first_linear_layer(params, toks, cfg)
    qkv = np.asarray(u[0] @ w["w_qkv"])
    return np.concatenate([np.zeros((3, qkv.shape[1]), np.float32), qkv])[-3:]


def first_linear_layers_state(params, toks, cfg):
    """``[1, H, dk, dv]`` after the last token, by the reference's scan."""
    w, u = first_linear_layer(params, toks, cfg)
    h, dk = cfg.linear_num_heads, cfg.linear_key_head_dim
    s = toks.shape[1]
    qkv = u @ w["w_qkv"]
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(u @ w["w_a"] + w["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(u @ w["w_b"])
    padded = jnp.pad(qkv, ((0, 0), (3, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * w["conv_w"][j]
                          for j in range(4)))
    k = qkv[..., h * dk:2 * h * dk].reshape(1, s, h, dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = qkv[..., 2 * h * dk:].reshape(1, s, h, -1)
    return ref.gated_delta_rule(k, k, v, g, beta)[1]


def random_rule_inputs(rng, bsz, s, h, dk, dv):
    q, k = rng.normal(size=(2, bsz, s, h, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(bsz, s, h, dv))
    g = -rng.uniform(0.001, 0.4, size=(bsz, s, h))
    beta = rng.uniform(0.0, 2.0, size=(bsz, s, h))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [4, 8, 16, 29, 64])
def test_the_chunked_scan_equals_the_recurrence(chunk):
    """``delta_chunked`` against the recurrence itself, position by position,
    in numpy float64: 29 positions in chunks that divide them (29), that do
    not (4, 8, 16: whole chunks and a part) and that hold them all (64),
    ``beta`` over (0, 2) so that half the updates reflect past the key, some
    positions with ``beta = g = 0`` in the middle, which must neither decay
    nor write the state."""
    rng = np.random.default_rng(0)
    bsz, s, h, dk, dv = 2, 29, 3, 6, 10
    q, k, v, g, beta = random_rule_inputs(rng, bsz, s, h, dk, dv)
    g[:, 11:14] = beta[:, 11:14] = 0.0
    g[1, 20:] = beta[1, 20:] = 0.0  # a row's padding
    assert (beta > 1).mean() > 0.3
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, last = olmo_hybrid.delta_chunked(
        f32(q), f32(k), f32(v), f32(g), f32(beta), chunk)
    state = np.zeros((bsz, h, dk, dv))
    for t in range(s):
        state = np.exp(g[:, t])[..., None, None] * state
        kv = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        state = state + np.einsum(
            "bhk,bhv->bhkv", k[:, t], beta[:, t, :, None] * (v[:, t] - kv))
        want = np.einsum("bhkv,bhk->bhv", state, q[:, t])
        np.testing.assert_allclose(o[:, t], want, atol=2e-5)
        if t == 19:
            at_20 = state[1].copy()
    np.testing.assert_allclose(last, state, atol=2e-5)
    np.testing.assert_allclose(last[1], at_20, atol=2e-5)


def test_the_references_recurrence_is_transformers_gated_delta_rule():
    """``transformers`` is installed here with ``qwen3_next``, whose
    ``linear_*`` keys the config shares: its plain torch recurrence (and its
    chunked form) on random inputs, against the reference's scan, from zero
    and from a given state."""
    torch = pytest.importorskip("torch")
    qwen = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    rng = np.random.default_rng(1)
    bsz, s, h, dk, dv = 2, 21, 3, 8, 12
    q, k, v, g, beta = random_rule_inputs(rng, bsz, s, h, dk, dv)
    start = rng.normal(size=(bsz, h, dk, dv))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    t32 = lambda a: torch.tensor(a, dtype=torch.float32)
    # theirs scales q by dk^-1/2 itself: hand it q without the scale
    theirs = [t32(q * np.sqrt(dk)), t32(k), t32(v), t32(g), t32(beta)]
    for state in (None, start):
        o, last = ref.gated_delta_rule(
            f32(q), f32(k), f32(v), f32(g), f32(beta),
            None if state is None else f32(state))
        want_o, want_last = qwen.torch_recurrent_gated_delta_rule(
            *theirs, None if state is None else t32(state), True)
        np.testing.assert_allclose(o, want_o.numpy(), atol=2e-5)
        np.testing.assert_allclose(last, want_last.numpy(), atol=2e-5)
    chunk_o, chunk_last = qwen.torch_chunk_gated_delta_rule(
        *theirs, chunk_size=8, output_final_state=True)
    o0, last0 = ref.gated_delta_rule(f32(q), f32(k), f32(v), f32(g), f32(beta))
    np.testing.assert_allclose(o0, chunk_o.numpy(), atol=2e-5)
    np.testing.assert_allclose(last0, chunk_last.numpy(), atol=2e-5)


def test_the_packed_state_is_the_heads_states_side_by_side():
    cfg = tiny()
    rng = np.random.default_rng(2)
    state = jnp.asarray(rng.normal(size=(3, 4, 8, 64)), jnp.float32)
    packed = olmo_hybrid.pack_state(state, cfg.state_pack)
    assert packed.shape == (3, 2, 8, 128)
    np.testing.assert_array_equal(packed[:, 1, :, 64:], state[:, 3])
    np.testing.assert_array_equal(olmo_hybrid.unpack_state(packed, cfg.state_pack), state)
    per_head = jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32)
    lanes = delta_update.over_lanes(per_head, cfg.state_pack, 64)
    np.testing.assert_array_equal(
        olmo_hybrid.unpack_state(lanes, cfg.state_pack),
        np.broadcast_to(per_head[..., None], (3, 4, 8, 64)))
    # heads that do not pair (or values of a whole tile) lie one a row
    assert tiny(linear_num_heads=3).state_pack == 1
    assert tiny(linear_value_head_dim=128).state_pack == 1
    assert OlmoHybridConfig().state_pack == 2  # two of 192: three tiles


def test_the_harness_two_layer_cut_runs_one_layer_of_each_kind():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]``.  The cell's pattern
    starts ``FL``, so that is one full and one linear layer through a cache
    of both kinds of leaf, in the served type against the float32
    reference, under the benchmark's own limit."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16")), d_model=256,
                 layer_pattern="FLLLFLLLFLLL", n_layer=12)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    cut = dataclasses.replace(cfg, n_layer=2)
    assert cut.kinds == "FL"
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 24 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 24, 3)
    want = ref_logits(part, toks, cut)[0]
    errs = bench_server.logit_errors(got, [want[23 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 32)
    assert cache["k"].shape[0] == 1 and cache["state"].shape[0] == 1


def test_state_kept_in_bfloat16_is_outside_the_tolerance(weights):
    """The lower-precision control: everything float32 but the delta rule's
    state ``S``, which the cache keeps in bfloat16 (rounded after prefill and
    after every decode step).  That is off the reference by a hundred times
    what the float32 program is and many times the tolerance: the comparison
    sees one leaf's type.  The first logits, which prefill computes before
    the state is rounded, are untouched."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 28, seed=2)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, 18:19 + 8] for b in range(2)])
    good, _, _ = through_the_cache(cfg, params, toks, [19, 19], 8)
    bad, cache, _ = through_the_cache(cfg, params, toks, [19, 19], 8,
                                      state_dtype=jnp.bfloat16)
    assert cache["state"].dtype == jnp.bfloat16
    assert float(np.abs(good - want).max()) < F32_TOL
    assert float(np.abs(bad - want)[:, 0].max()) < F32_TOL
    # every decode step of every row is outside it, the worst five times
    assert float(np.abs(bad - want)[:, 1:].max(-1).min()) > F32_TOL
    assert float(np.abs(bad - want).max()) > 5 * F32_TOL


def test_importing_the_family_runs_no_jax_computation():
    """Every worker imports ``ray_tpu.models`` (the training gang's too): the
    family's two modules define functions and constants and nothing else; no
    array is made at import."""
    for module in (olmo_hybrid, olmo_hybrid_decode):
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


def test_engine_slots_hold_a_matrix_of_state_beside_keys_and_values():
    """What ``llm/engine.py`` needed for a matrix of state a head in its
    slots: nothing.  A slot's second tenant gives the ids it gives alone
    (the state is replaced whole at admission, whatever the last tenant
    left); a request among full slots gives the ids it gives alone;
    streamed equals unary; the family's counts reach ``stats()``."""
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
    assert stats["prefill_delta_positions"] == 2 * prompt_tokens + len(
        PROMPTS[2]) + 1
    assert stats["prefill_delta_chunk_positions"] == stats["admitted"] * 64
    assert 0 < stats["delta_positions"] <= stats["delta_chunk_positions"]
    assert stats["delta_chunk_positions"] == 4 * stats["decode_steps"]
    full.shutdown()


def test_idle_slots_stay_finite_through_two_hundred_steps(state_update):
    """Every slot is decoded every step, tenant or not: the state of the
    slots nobody occupies (token 0 at position 0, over and over, on whatever
    the last tenant left) must stay finite for a whole run: a step's map on
    ``S`` never expands (``|k| = 1``, ``0 < beta < 2``), whatever ``beta``."""
    engine = make_engine(slots=4, max_seq_len=256)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    by_hand(engine, PROMPTS, params)  # every slot has had a tenant
    long = SamplingParams(max_tokens=200, stop_token=-1)
    assert len(by_hand(engine, ["one long answer"], long)[0]) == 200
    assert engine.stats()["decode_steps"] >= 200
    for leaf in ("state", "conv", "k", "v"):
        assert bool(jnp.isfinite(engine.cache[leaf]).all()), leaf
    assert float(jnp.abs(engine.cache["state"][:, 1:]).max()) < 1e3
    # and the next tenant of an idle slot is none the worse for it
    again = by_hand(engine, PROMPTS[:1], params)
    assert again == by_hand(make_engine(), PROMPTS[:1], params)


def test_the_engine_and_serve_know_nothing_of_the_family():
    """Four ``model_config`` PRs added a family with no edit of the engine
    or of ``serve/`` for it; so does this one."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    for path in [root / "llm" / "engine.py", *(root / "serve").rglob("*.py")]:
        text = path.read_text().lower()
        assert not any(word in text for word in (
            "olmo", "delta_positions", "delta_chunk", "state_pack")), path


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16"))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: olmo_hybrid_init(
        jax.random.PRNGKey(0), OlmoHybridConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    linear = params["blocks"]["linear"]
    # a head's decay before the token moves it: alpha in [0.905, 0.999]
    decay = jnp.exp(linear["a_log"]) * jax.nn.softplus(linear["dt_bias"])
    assert 1e-3 - 1e-6 <= float(decay.min()) and float(decay.max()) <= 0.1 + 1e-6
    # no greedy stream ends early: the stop id's logit is 0 among ~100,000
    from ray_tpu.llm.tokenizer import ByteTokenizer
    assert not np.asarray(params["lm_head"][ByteTokenizer.EOS]).any()


def test_the_cells_draw_passes_one_and_keeps_the_state_old():
    """Under the cell's draw at a width where the scales mean something (d
    256): ``beta = 2 sigmoid(u Wb)`` spans (0, 2) with a good share past 1
    (the negative-eigenvalue branch is taken in every head), and ``alpha``
    stays within 0.8-0.9999 token by token, so a state written a hundred
    tokens ago is still read."""
    model = dict(dataclasses.asdict(tiny(dtype="float32")), d_model=256)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 11)
    m = params["blocks"]["linear"]
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(1, 400, cfg.d_model)), jnp.float32)
    _, _, g, beta = olmo_hybrid.delta_project(u, m, 0, cfg)
    beta, alpha = np.asarray(beta), np.exp(np.asarray(g))
    assert beta.min() > 0 and beta.max() < 2
    assert 0.2 < (beta > 1).mean() < 0.8
    assert ((beta > 1).mean(axis=(0, 1)) > 0.05).all()  # in every head
    assert alpha.min() > 0.8 and alpha.max() < 1 and np.median(alpha) > 0.97


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_a_matrix_of_state_beside_keys(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache with state beside them when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "olmo_hybrid" in str(err.value) and "state" in str(err.value)
