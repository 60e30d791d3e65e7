"""Kimi-Linear through its cache (``ray_tpu/models/kimi_linear_decode.py``) and
through ``JaxLLMEngine``, at tiny widths on the CPU with seeded weights:
prefill then decode equals the plain float32 reference's full forward
(``benchmarks/reference/kimi_linear_ref.py``), a padded prefill leaves
state, tail and latents of the TRUE length, the state's type is seen, and
the engine needed nothing for three leaves of two kinds in its slots.
Logits, not tokens.  Each tolerance says what it allows for.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_linear as bench_family
from benchmarks.lib import bench_server
from benchmarks.reference import kimi_linear_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import kimi_linear, kimi_linear_decode, model_family
from ray_tpu.ops import delta_update

# the family's tiny config, lively weights and tolerances, with their reasons
from test_kimi_linear import (BF16_TOL, F32_TOL, ref_logits,  # noqa: E402
                              rel_rms, tiny, tokens_of, weights,  # noqa: F401
                              weights_of)


def through_the_cache(cfg, params, toks, lengths, steps, padded_to=None,
                      state_dtype=None, shift=0):
    """Ragged batch: prefill each row's first ``lengths[b]`` tokens (padded
    to ``padded_to``), then ``steps`` decode steps at each row's own
    position (``shift``: what the step is TOLD the position is, less).
    Returns the logits that predict positions ``lengths[b] + i``, the cache
    after prefill and the counts of every program run."""
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
        logits, cache, counts = decode(params, toks[rows, pos], pos - shift,
                                       cache)
        out.append(np.asarray(logits))
        all_counts.append(counts)
    return np.stack(out, 1), after_prefill, all_counts  # [B, steps + 1, V]


@pytest.fixture(params=["xla", "kernel"])
def state_update(request, monkeypatch):
    """The decode step's way through a KDA layer's state: what the CPU runs
    unasked (``ops.delta_update``'s XLA formulation), then the Pallas kernel
    a TPU runs, forced here in interpret mode (the tiny heads of 16 are no
    whole tiles: ``linear_head_dim`` 128 for it)."""
    if request.param == "kernel":
        monkeypatch.setattr(
            kimi_linear_decode, "delta_update", functools.partial(
                delta_update.delta_update, force_pallas=True))
    return request.param


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_full_forward(
        dtype, state_update):
    cfg = tiny(dtype=dtype, linear_head_dim=(
        128 if state_update == "kernel" else 16))
    params = weights_of(cfg, seed=1)
    if dtype == "bfloat16":  # the cell's draw (``test_kimi_linear.py``)
        model = dict(dataclasses.asdict(cfg), d_model=256)
        cfg, params = bench_family.config(model), bench_family.load_params(
            model, 3000000019)
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
    # a prefill scanned its true positions in three chunks of 8 a row, and
    # routed them through five expert layers
    assert int(counts[0]["delta_positions"]) == sum(lengths)
    assert int(counts[0]["delta_chunk_positions"]) == 3 * 24
    assert int(counts[0]["routed_total"]) == sum(lengths) * cfg.top_k * 5
    for step in counts[1:]:
        assert int(step["delta_positions"]) == 3
        assert int(step["delta_chunk_positions"]) == 3
        assert int(step["routed_total"]) == 3 * cfg.top_k * 5
        assert int(step["routed_held"]) == int(step["routed_total"])
        assert 0 < int(step["experts_touched"]) <= 5 * 12
        assert int(step["held_chunks"]) == int(step["experts_touched"])
    # The three leaves, of two kinds: positions on the latents, none on
    # the state; a head's state a row of its own.
    dk = cfg.linear_head_dim
    assert cache["latent"].shape == (2, 3, 24, cfg.latent_dim)
    assert cache["state"].shape == (4, 3, 4, dk, dk)
    assert cache["conv"].shape == (4, 3, 3 * cfg.d_conv)
    assert cache["state"].dtype == cache["conv"].dtype == jnp.float32
    assert cache["latent"].dtype == jnp.dtype(cfg.dtype)
    assert all(leaf.shape[1] == 3 for leaf in jax.tree.leaves(cache))


@pytest.mark.parametrize("n", [5, 8, 9, 19])
def test_a_padded_prefill_leaves_state_tail_and_latents_of_the_true_length(
        weights, n):
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
    assert float(jnp.abs(padded_logits - exact_logits).max()) < F32_TOL
    for leaf in ("state", "conv"):
        assert padded[leaf].shape == exact[leaf].shape
        np.testing.assert_allclose(padded[leaf], exact[leaf], atol=F32_TOL)
    assert float(jnp.abs(padded["state"]).max()) > 1e-2  # there is a state
    np.testing.assert_allclose(padded["latent"][:, :, :n], exact["latent"],
                               atol=F32_TOL)
    # layer 0 is KDA: its window is the last three TRUE inputs of the
    # convolution, oldest first, and its state the recurrence's own at n
    m = {k: v[0] for k, v in params["blocks"]["kda"].items()}
    x = jnp.asarray(params["wte"][toks[:, :n]], jnp.float32)
    u = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps)
    qkv = np.asarray(u[0] @ m["w_qkv"])
    want = np.concatenate([np.zeros((3, qkv.shape[1]), np.float32), qkv])[-3:]
    np.testing.assert_allclose(exact["conv"][0, 0].reshape(3, -1), want,
                               atol=F32_TOL)
    np.testing.assert_allclose(
        exact["state"][0], first_layers_state(m, u, cfg), atol=F32_TOL)


def first_layers_state(m, u, cfg):
    """``[1, H, dk, dv]`` after the last token, by the reference's scan."""
    h, dk, s = cfg.linear_num_heads, cfg.linear_head_dim, u.shape[1]
    qkv = u @ m["w_qkv"]
    f = (u @ m["w_fa"]) @ m["w_fb"] + m["dt_bias"]
    g = -jnp.exp(m["a_log"])[:, None] * jax.nn.softplus(
        f.reshape(1, s, h, dk))
    beta = jax.nn.sigmoid(u @ m["w_b"])
    padded = jnp.pad(qkv, ((0, 0), (3, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * m["conv_w"][j]
                          for j in range(4)))
    k = qkv[..., h * dk:2 * h * dk].reshape(1, s, h, dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = qkv[..., 2 * h * dk:].reshape(1, s, h, dk)
    return ref.kda_recurrence(k, k, v, g, beta)[1]


@pytest.mark.parametrize("lengths", [[1, 2, 6], [5, 9, 14]], ids=str)
def test_each_decode_step_leaves_what_a_prefill_of_the_same_tokens_leaves(
        lengths):
    """After every step, in EVERY layer: the window is the convolution's
    last three inputs (what it held moved one place to the bit), the state
    is the prefill's, and the new latent lies at ``pos`` of its layer while
    every other position keeps its bits.  A layer stepped twice (a cloned
    update), left stale or written into another layer's place fails here."""
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
        pos, old = lengths + i, jax.tree.map(np.asarray, cache)
        cache = decode(toks[rows, pos], pos, cache)
        new, want = jax.tree.map(np.asarray, cache), prefill(pos + 1)
        np.testing.assert_array_equal(new["conv"][..., :-c],
                                      old["conv"][..., c:])
        np.testing.assert_allclose(new["conv"], want["conv"], atol=F32_TOL)
        np.testing.assert_allclose(new["state"], want["state"], atol=F32_TOL)
        for b in range(3):
            np.testing.assert_allclose(
                new["latent"][:, b, pos[b]], want["latent"][:, b, pos[b]],
                atol=F32_TOL)
            kept = np.arange(width) != pos[b]
            np.testing.assert_array_equal(new["latent"][:, b, kept],
                                          old["latent"][:, b, kept])
        newest = new["conv"][..., -c:]
        assert np.abs(newest[1:] - newest[:-1]).max(-1).min() > 1e-2


def test_the_harness_two_layer_cut_sees_all_four_kinds_of_sub_block():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]``.  The cell's pattern
    starts ``K M`` and its first layer's FFN is dense, so that is KDA + the
    dense MLP and latent attention + the experts through a cache of both
    kinds of leaf, in the served type against the float32 reference, under
    the benchmark's own limit; a scalar gate in the reference's place is
    five times further off (at these toy widths, d 256 and two layers, that
    is 2-4 %: on both sides of the limit by the seed; at the published
    widths ``kimi_linear_all_layers.py --harness-cut`` holds it outside)."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16")), d_model=256,
                 layer_pattern="K" + "MKKK" * 5, n_layer=21)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    cut = dataclasses.replace(cfg, n_layer=2)
    assert cut.kinds == "kM"
    assert kimi_linear.stacks_in(cut.kinds) == {
        "kda": 1, "mla": 1, "dense": 1, "moe": 1}
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 24 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 24, 3)
    want = ref_logits(part, toks, cut)[0]
    errs = bench_server.logit_errors(got, [want[23 + i] for i in range(4)])
    assert errs["ok"], errs
    scalar = ref_logits(part, toks, cut, scalar_gate=True)[0]
    off = bench_server.logit_errors(got, [scalar[23 + i] for i in range(4)])
    assert max(off["rel_errs"]) > 5 * max(errs["rel_errs"])
    cache = model_family(cut).init_cache(cut, 1, 32)
    assert cache["latent"].shape[0] == 1 and cache["state"].shape[0] == 1


def test_state_kept_in_bfloat16_is_outside_the_tolerance(weights):
    """The lower-precision control: everything float32 but the delta rule's
    state ``S``, which the cache keeps in bfloat16 (rounded after prefill and
    after every decode step).  The first logits, which prefill computes
    before the state is rounded, are untouched; every decode step of every
    row is outside the float32 tolerance, the worst five times."""
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
    assert float(np.abs(bad - want)[:, 1:].max(-1).min()) > F32_TOL
    assert float(np.abs(bad - want).max()) > 5 * F32_TOL


def test_a_latent_read_one_position_short_is_outside_the_tolerance(weights):
    """The decode step told a position one too low reads ``[0, pos - 2]`` of
    the latents (and writes over the last one): at contexts of 5-14 that is
    a tenth of what latent attention sees, far outside float32's tolerance
    and outside the served type's at its worst position."""
    cfg, params = weights
    toks = tokens_of(cfg, 3, 23, seed=1)
    want = ref_logits(params, toks, cfg)
    lengths = [5, 9, 14]
    want = np.stack([want[b, n - 1:n + 8] for b, n in enumerate(lengths)])
    bad, _, _ = through_the_cache(cfg, params, toks, lengths, 8, shift=1)
    assert float(np.abs(bad - want)[:, 0].max()) < F32_TOL  # the prefill's
    assert rel_rms(bad[:, 1:], want[:, 1:]) > BF16_TOL


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


def test_engine_slots_hold_state_tail_and_latents_side_by_side():
    """What ``llm/engine.py`` needed for a vector-gated state, a convolution
    tail and a latent cache in its slots: nothing.  A slot's second tenant
    gives the ids it gives alone; a request among full slots gives the ids
    it gives alone; streamed equals unary; the family's counts reach
    ``stats()``."""
    params = SamplingParams(max_tokens=12, stop_token=-1)
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    one = make_engine(slots=1)
    order = [2, 1, 3, 0]
    assert by_hand(one, [PROMPTS[i] for i in order], params) == [
        alone[i] for i in order]
    full = make_engine()
    assert by_hand(full, PROMPTS, params) == alone
    assert all(s is None for s in full.slots)
    assert [r["token_ids"] for r in full.generate(PROMPTS, params)] == alone
    full.tokenizer = bench_server.VisibleTokenizer()
    rid = full.add_request(PROMPTS[2], params)
    streamed = "".join(full.stream_request(rid))
    assert bench_server.ids_of(streamed) == alone[2]
    stats = full.stats()
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0  # every stream ended by count
    prompt_tokens = sum(len(p) + 1 for p in PROMPTS)
    assert stats["prefill_delta_positions"] == 2 * prompt_tokens + len(
        PROMPTS[2]) + 1
    assert stats["prefill_delta_chunk_positions"] == stats["admitted"] * 64
    assert 0 < stats["delta_positions"] <= stats["delta_chunk_positions"]
    assert stats["delta_chunk_positions"] == 4 * stats["decode_steps"]
    # five expert layers, four choices a token, every expert held
    assert stats["prefill_routed_total"] == 20 * stats[
        "prefill_delta_positions"]
    assert stats["routed_total"] == 20 * stats["delta_positions"]
    assert stats["routed_held"] == stats["routed_total"]
    assert 0 < stats["experts_touched"] <= stats["routed_held"]
    assert stats["held_chunks"] == stats["experts_touched"]
    full.shutdown()


def test_idle_slots_stay_finite_through_two_hundred_steps():
    """Every slot is decoded every step, tenant or not: the state of the
    slots nobody occupies (token 0 at position 0, over and over, on whatever
    the last tenant left) must stay finite for a whole run: a step's map on
    ``S`` never expands (``|k| = 1``, ``0 < beta < 1``, ``alpha < 1``)."""
    engine = make_engine(slots=4, max_seq_len=256)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    by_hand(engine, PROMPTS, params)  # every slot has had a tenant
    long = SamplingParams(max_tokens=200, stop_token=-1)
    assert len(by_hand(engine, ["one long answer"], long)[0]) == 200
    assert engine.stats()["decode_steps"] >= 200
    for leaf in ("state", "conv", "latent"):
        assert bool(jnp.isfinite(engine.cache[leaf]).all()), leaf
    assert float(jnp.abs(engine.cache["state"][:, 1:]).max()) < 1e3
    again = by_hand(engine, PROMPTS[:1], params)
    assert again == by_hand(make_engine(), PROMPTS[:1], params)


def test_the_engine_and_serve_know_nothing_of_the_family():
    """Eight ``model_config`` PRs added a family with no edit of the engine
    or of ``serve/`` for it; so does this one."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    for path in [root / "llm" / "engine.py", *(root / "serve").rglob("*.py")]:
        text = path.read_text().lower()
        assert not any(word in text for word in (
            "kimi", "kda", "delta_positions", "delta_chunk",
            "gate_rank")), path


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_the_cache_by_name(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache of latents, tail and state when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "kimi_linear" in str(err.value)
    assert any(leaf in str(err.value) for leaf in ("latent", "conv", "state"))
