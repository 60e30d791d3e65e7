"""Nemotron-H family (``ray_tpu/models/nemotron_h*.py``) against its plain
float32 reference (``benchmarks/reference/nemotron_h_ref.py``: the Mamba-2
RECURRENCE, dense routing), at tiny widths on the CPU with seeded weights:
pattern ``MEM*EM*E``, 16 routed experts, 4 a token, chunks of 8.  Logits,
not tokens.  Each tolerance says what it allows for.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as bench_family
from benchmarks.lib import bench_server
from benchmarks.reference import nemotron_h_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (NemotronHConfig, mamba2, model_family,
                            nemotron_h, nemotron_h_init)
from ray_tpu.models.expert_share import chunk_rows

# float32 against float32: the two differ by the order of their sums only
# (the chunked scan against the recurrence, experts added in another order,
# exp(a) exp(b) against exp(a + b)); logits are ~1 wide and pass through
# eight blocks, so this is some tens of units in the last place (1-2e-6
# measured; the limit leaves ten times that).
F32_TOL = 2e-5
# bfloat16 products (2^-9 a rounding, some forty of them through eight
# blocks and the head) against float32, as a share of the logits' spread:
# the benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).
BF16_TOL = 0.03


def tiny(**kw):
    return NemotronHConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): scale the embedding to RMS 1 and
    the matrices by 5, so that every mixer moves the logits and a fault in
    one shows."""
    def scale(path, a):
        name = path[-1].key
        if name == "wte":
            return a * 50
        big = a.ndim >= 3 or name == "lm_head"
        return a * 5 if big and name != "conv_w" else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(nemotron_h_init(jax.random.PRNGKey(seed), cfg))


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
    assert fam.name == "nemotron_h" and fam.decode_step_counted is not None
    assert cfg.kinds == "MEM*EM*E"
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


def rel_rms(got, want):
    """RMS of the difference over the vocabulary as a share of the
    reference logits' spread, the worst position."""
    err = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
    return float(err.max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_full_forward(dtype):
    # Half the experts are held, so absent and held ones are both chosen.
    cfg = tiny(dtype=dtype, experts_held=8, expert_offset=4)
    params = weights_of(cfg, seed=1)
    lengths, steps = [5, 9, 14], 8
    toks = tokens_of(cfg, 3, 23, seed=1)
    got, cache, counts = through_the_cache(cfg, params, toks, lengths, steps)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, n - 1:n + steps] for b, n in enumerate(lengths)])
    if dtype == "float32":
        assert float(np.abs(got - want).max()) < F32_TOL
    else:
        assert rel_rms(got, want) < BF16_TOL
    n_moe = cfg.kinds.count("E")
    assert int(counts[0]["routed_total"]) == sum(lengths) * cfg.top_k * n_moe
    for step in counts[1:]:
        assert int(step["routed_total"]) == 3 * cfg.top_k * n_moe
        assert 0 < int(step["experts_touched"]) <= int(step["routed_held"])
        assert int(step["routed_held"]) < int(step["routed_total"])
    # The two kinds of leaf: positions on keys and values, none on state.
    assert cache["k"].shape == (2, 3, cfg.n_kv_head, 24, cfg.head_dim)
    assert cache["ssm"].shape == (3, 3, 8, 8, 16)
    assert cache["conv"].shape == (3, 3, 3 * cfg.d_conv)
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
    assert float(jnp.abs(padded_logits - exact_logits).max()) < F32_TOL
    for leaf in ("ssm", "conv"):
        assert padded[leaf].shape == exact[leaf].shape
        np.testing.assert_allclose(padded[leaf], exact[leaf], atol=F32_TOL)
    assert float(jnp.abs(padded["ssm"]).max()) > 1e-2  # there is a state
    for leaf in ("k", "v"):
        np.testing.assert_allclose(padded[leaf][:, :, :, :n], exact[leaf],
                                   atol=F32_TOL)
    # the convolution's state is its last three TRUE inputs, oldest first
    want = ref_conv_inputs(params, toks[:, :n], cfg)
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


def ref_conv_inputs(params, toks, cfg):
    """The first Mamba-2 layer's last three inputs to the convolution."""
    m = jax.tree.map(lambda a: a[0], params["blocks"]["mamba"])
    x = jnp.asarray(params["wte"][toks[0]], jnp.float32)
    u = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps)
    xbc = np.asarray(u @ m["w_xbc"])
    return np.concatenate([np.zeros((3, xbc.shape[1]), np.float32), xbc])[-3:]


def test_the_chunked_scan_equals_the_recurrence_over_several_chunks():
    """``ssd_chunked`` against the recurrence itself, position by position,
    in numpy float64: 29 positions in chunks of 8 (three whole chunks and a
    part), some positions with ``dt = 0`` in the middle, which must neither
    decay nor feed the state."""
    rng = np.random.default_rng(0)
    bsz, s, h, p, g, n = 2, 29, 4, 3, 2, 5
    x = rng.normal(size=(bsz, s, h, p))
    dt = rng.uniform(0.01, 0.5, size=(bsz, s, h))
    dt[:, 11:14] = 0.0
    dt[1, 20:] = 0.0  # a row's padding
    a = -rng.uniform(0.5, 4.0, size=h)
    b, c = rng.normal(size=(2, bsz, s, g, n))
    d_skip = rng.normal(size=h)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, last = mamba2.ssd_chunked(f32(x), f32(dt), f32(a), f32(b), f32(c),
                                     f32(d_skip), 8, jnp.float32)
    state = np.zeros((bsz, h, p, n))
    bh, ch = (np.repeat(v, h // g, axis=2) for v in (b, c))
    for t in range(s):
        keep = np.exp(dt[:, t] * a)[..., None, None]
        state = keep * state + (dt[:, t, :, None] * x[:, t])[..., None] * (
            bh[:, t, :, None])
        want = (state * ch[:, t, :, None]).sum(-1) + d_skip[:, None] * x[:, t]
        np.testing.assert_allclose(y[:, t], want, atol=2e-5)
        if t == 19:
            at_20 = state[1].copy()
    np.testing.assert_allclose(last, state, atol=2e-5)
    np.testing.assert_allclose(last[1], at_20, atol=2e-5)


@pytest.mark.parametrize("rows", [13, 150])
def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        weights, rows):
    """Both ways the held experts run: 13 rows (a decode step's: one chunk
    and a choice or more an expert, so every held expert runs on every row
    in two batched products) and 150 rows (a prefill's: the gather and the
    chunk loop of ``expert_share.held_experts``).  The deployment's cut: each of four chips holds a quarter of the
    experts, routes over all sixteen, sums ITS experts' part in the latent
    and applies ``W_ul`` to it.  The four parts and the shared expert,
    counted once, are the uncut reference's layer; the counts are the
    reference's choices recounted."""
    cfg, params = weights
    layer = 1
    w = jax.tree.map(lambda a: a[layer], params["blocks"]["moe"])
    experts = jax.tree.map(lambda a: a[layer], params["experts"])
    u = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.d_model))
    live = jnp.arange(rows) != 4  # a padded row chooses nothing
    sizes = bench_family.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        want_routed, want_shared, chosen = ref.latent_moe(
            u[None], w, experts, sizes, 0)
    total, held_sum = 0, 0
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = dict(params, experts=jax.tree.map(
            lambda a: a[:, offset:offset + 4], params["experts"]))
        routed, shared, counts = jax.jit(
            lambda u, part=part, share=share: nemotron_h.moe(
                u, live, part, layer, share))(u)
        np.testing.assert_allclose(shared, want_shared[0], atol=F32_TOL)
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
        total, held_sum = total + routed, held_sum + int(held.sum())
    assert held_sum == (rows - 1) * cfg.top_k  # every choice is somebody's
    np.testing.assert_allclose(
        np.asarray(total)[np.asarray(live)],
        np.asarray(want_routed[0])[np.asarray(live)], atol=F32_TOL)
    assert float(jnp.abs(total[4]).max()) == 0.0
    weights_sum = nemotron_h.route(u, w["router"], w["router_bias"], cfg)[1]
    np.testing.assert_allclose(weights_sum.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)  # renormalised, then x 5


def test_the_harness_two_layer_cut_runs_one_mamba_and_one_expert_layer():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]``.  The pattern starts
    ``ME``, so that is one Mamba-2 and one expert layer through a cache with
    NO attention layer (key/value leaves of extent 0), in the served type
    against the float32 reference, under the benchmark's own limit."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16", experts_held=8,
                                         expert_offset=4)), d_model=256)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    cut = dataclasses.replace(cfg, n_layer=2)
    assert cut.kinds == "ME"
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 24 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 24, 3)
    want = ref_logits(part, toks, cut)[0]
    errs = bench_server.logit_errors(got, [want[23 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 32)
    assert cache["k"].shape[0] == 0 and cache["ssm"].shape[0] == 1


def test_the_cells_draw_routes_by_the_token_alone(monkeypatch):
    """``families/nemotron_h.py`` keeps the first channels of the stream for
    the routers: no mixer writes them, so they carry the token's embedding
    through every layer and rounding upstream reaches a router only as the
    norm's common factor.  The bfloat16 program and the float32 reference
    then make the SAME choices at every token of every expert layer (with
    routers that read the whole stream 3-36 % of the tokens a layer flipped
    their 22nd choice at the published widths: PERF.md, PR 39), and a token
    chooses the same wherever it stands."""
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
    nemotron_h.nemotron_h_apply(params, jnp.asarray(toks), cfg)  # not jitted
    ref_logits(params, toks, cfg)
    layers = cfg.kinds.count("E")
    assert len(chosen) == 2 * layers
    for program, reference in zip(chosen[:layers], chosen[layers:]):
        np.testing.assert_array_equal(program, reference)
        np.testing.assert_array_equal(program[:, 30], program[:, 3])
        assert (program[:, 30] != program[:, 4]).any()
    assert (chosen[0] != chosen[1]).any()  # every layer its own choice


def test_state_kept_in_bfloat16_is_outside_the_tolerance(weights):
    """The lower-precision control: everything float32 but the recurrent
    state ``S``, which the cache keeps in bfloat16 (rounded after prefill and
    after every decode step).  That is off the reference by a hundred times
    what the float32 program is (2.1e-4 against 1.7e-6) and ten times the
    tolerance: the comparison sees one leaf's type.  The first logits, which
    prefill computes before the state is rounded, are untouched."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 28, seed=2)
    want = ref_logits(params, toks, cfg)
    want = np.stack([want[b, 18:19 + 8] for b in range(2)])
    good, _, _ = through_the_cache(cfg, params, toks, [19, 19], 8)
    bad, cache, _ = through_the_cache(cfg, params, toks, [19, 19], 8,
                                      state_dtype=jnp.bfloat16)
    assert cache["ssm"].dtype == jnp.bfloat16
    assert float(np.abs(good - want).max()) < F32_TOL
    # every decode step of every row is outside it, the worst ten times
    assert float(np.abs(bad - want)[:, 1:].max(-1).min()) > F32_TOL
    assert float(np.abs(bad - want).max()) > 5 * F32_TOL


# ------------------------------------------------------------------ engine
PROMPTS = ["the first prompt", "second", "a third, somewhat longer prompt",
           "and a fourth one to fill the last slot of the four"]


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


def test_a_row_that_over_ran_gives_its_next_tenant_what_a_fresh_engine_gives():
    """A stop by value reaches the host a step late, so the row rides one
    decode more: its recurrent state takes a step beyond the stream's end
    and its keys and values gain a position.  The next tenant's prefill
    replaces every leaf of the row: its ids are a fresh engine's."""
    free = SamplingParams(max_tokens=16, stop_token=-1)
    [ids] = by_hand(make_engine(slots=1), [PROMPTS[2]], free)
    k = next(k for k in range(2, len(ids)) if ids[k] not in ids[:k])
    engine = make_engine(slots=1)
    [first] = by_hand(engine, [PROMPTS[2]],
                      SamplingParams(max_tokens=16, stop_token=ids[k]))
    assert first == ids[:k]
    assert engine.has_unfinished()  # the step it rode for nothing, unread
    [second] = by_hand(engine, [PROMPTS[0]], free)
    assert [second] == by_hand(make_engine(slots=1), [PROMPTS[0]], free)
    stats = engine.stats()
    assert stats["overrun_row_steps"] == 1
    assert stats["generated_tokens"] == k + 1 + 16
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]


def test_engine_slots_hold_state_beside_keys_and_values():
    """What ``llm/engine.py`` needed for recurrent state in its slots:
    nothing.  A slot's second tenant gives the ids it gives alone (the state
    is replaced whole at admission, whatever the last tenant left); a
    request among full slots gives the ids it gives alone; streamed equals
    unary; the family's counts reach ``stats()``."""
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
    rid = full.add_request(PROMPTS[2], params)
    streamed = "".join(full.stream_request(rid))
    assert streamed == full.tokenizer.decode(alone[2])
    stats = full.stats()
    # Drained: every decode step's vector has been read, a step after it.
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0  # every stream ended by count
    assert stats["routed_held"] > 0 and stats["prefill_routed_held"] > 0
    assert stats["experts_touched"] <= stats["routed_held"] < (
        stats["routed_total"])
    full.shutdown()


def test_idle_slots_stay_finite_through_two_hundred_steps():
    """Every slot is decoded every step, tenant or not: the state of the
    slots nobody occupies (token 0 at position 0, over and over, on whatever
    the last tenant left) must stay finite for a whole run."""
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


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16", experts_held=8))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: nemotron_h_init(
        jax.random.PRNGKey(0), NemotronHConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    # the routers' channels: read by the routers alone, written by no mixer
    blocks, own = params["blocks"], model["d_model"] // 16
    router = np.asarray(blocks["moe"]["router"])
    assert abs(router[:, :own].std() - bench_family.SCALES["router"]) < 0.05
    assert not router[:, own:].any()
    for out in (blocks["mamba"]["w_out"], blocks["attn"]["wo"],
                blocks["moe"]["w_ul"], blocks["moe"]["ws2"]):
        out = np.asarray(out, np.float32)
        assert not out[..., :own].any() and out[..., own:].all()
    steps = jax.nn.softplus(params["blocks"]["mamba"]["dt_bias"])
    assert 1e-3 <= float(steps.min()) and float(steps.max()) <= 0.1 + 1e-6


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_state_beside_keys_and_values(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache with state beside them when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "nemotron_h" in str(err.value) and "ssm" in str(err.value)
