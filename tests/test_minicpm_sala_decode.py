"""MiniCPM-SALA through its cache and through the engine
(``ray_tpu/models/minicpm_sala_decode.py``): prefill then decode against the
plain float32 reference on both sides of ``dense_len`` and across it, what a
padded prefill leaves, the pooled keys kept step by step, the controls that
must fall outside the tolerance (a selection by recency, a state in
bfloat16), the harness's two-layer cut, and ``JaxLLMEngine`` itself.  The
mathematics of one call is ``tests/test_minicpm_sala.py``'s, whose weights,
tolerances and measures these cases share.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import minicpm_sala as bench_family
from benchmarks.lib import bench_server
from benchmarks.reference import minicpm_sala_ref as ref
from ray_tpu.llm import EngineConfig, JaxLLMEngine, SamplingParams
from ray_tpu.models import (MinicpmSalaConfig, layers, minicpm_sala,
                            minicpm_sala_decode, minicpm_sala_init,
                            model_family)
from test_minicpm_sala import (BF16_TOL, EDGE_TOL, F32_TOL, off, ref_logits,
                               rel_rms, rel_rms_each, tiny, tokens_of,
                               weights, weights_of)  # noqa: F401


def through_the_cache(cfg, params, toks, lengths, steps, padded_to=None,
                      state_dtype=None, max_len=None):
    """Ragged batch: prefill each row's first ``lengths[b]`` tokens (padded
    to ``padded_to``), then ``steps`` decode steps at each row's own
    position.  Returns the logits that predict positions ``lengths[b] + i``,
    the cache after prefill, the cache at the end and the counts of every
    program run."""
    fam = model_family(cfg)
    lengths = np.asarray(lengths, np.int32)
    width = padded_to or toks.shape[1]
    cache = fam.init_cache(cfg, len(lengths), max_len or max(
        width, -(-(toks.shape[1] + 1) // 8) * 8))
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
    return np.stack(out, 1), after_prefill, cache, all_counts


def wanted(params, toks, cfg, lengths, steps):
    """The reference's logits for ``through_the_cache``'s, a row at a time:
    a row's positions under its own length by its prefill's rule."""
    return np.stack([
        ref_logits(params, toks[b:b + 1], cfg, prompt_len=int(n))[
            0, n - 1:n + steps] for b, n in enumerate(lengths)])


# under / at / over dense_len (64), and two generations that cross it: one
# prefilled dense whose decode steps reach 64, one that ends just under it
LENGTHS, STEPS = [30, 64, 90, 58, 50], 12


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(dtype):
    cfg = tiny(dtype=dtype)
    params = weights_of(cfg, seed=1)
    toks = tokens_of(cfg, len(LENGTHS), 90 + STEPS, seed=1)
    got, cache, _, counts = through_the_cache(
        cfg, params, toks, LENGTHS, STEPS, padded_to=96, max_len=128)
    want = wanted(params, toks, cfg, LENGTHS, STEPS)
    if dtype == "float32":
        assert off(got, want) < F32_TOL
    else:
        errs = rel_rms_each(got, want)
        selects = (np.asarray(LENGTHS)[:, None]
                   + np.arange(STEPS + 1) >= cfg.dense_len)
        selects[np.asarray(LENGTHS) >= cfg.dense_len] = True
        assert errs[~selects].max() < BF16_TOL
        assert np.median(errs[selects]) < BF16_TOL
        assert errs[selects].max() < EDGE_TOL
    nl, ns = cfg.kinds.count("L"), cfg.kinds.count("S")
    assert int(counts[0]["lightning_positions"]) == nl * sum(LENGTHS)
    assert int(counts[0]["lightning_chunk_positions"]) == nl * 5 * 96
    for i, step in enumerate(counts[1:]):
        pos = np.asarray(LENGTHS) + i
        assert int(step["lightning_positions"]) == nl * 5
        assert int(step["sparse_live_positions"]) == ns * pos.sum()
        # a row under dense_len lists every block that starts before pos, a
        # row at or over it the six best
        listed = np.where(pos + 1 >= cfg.dense_len, cfg.topk,
                          -(-pos // cfg.block_size))
        assert int(step["sparse_read_positions"]) == (
            ns * cfg.block_size * listed.sum())
    # The three kinds of leaf: positions on keys and values, windows on the
    # pooled keys, neither on the state.
    assert cache["k"].shape == (3, 5, cfg.n_kv_head, 128, cfg.head_dim)
    assert cache["kbar"].shape == (3, 5, cfg.n_kv_head, 64, cfg.head_dim)
    assert cache["state"].shape == (3, 5, 4, 16, 16)
    assert cache["state"].dtype == jnp.float32


@pytest.mark.parametrize("n", [5, 31, 64, 70, 81])
def test_a_padded_prefill_leaves_the_cache_of_the_true_length(weights, n):
    """The engine pads a prompt to a rung; what is spliced into the slot
    must be the state after token ``n - 1``, keys and values of ``[0, n)``
    and the pooled keys of the windows INSIDE ``[0, n)``, zero beyond: ``n``
    on both sides of a chunk's and of ``dense_len``'s boundary, padded to
    96, against the same prompt prefilled at exactly ``n``.  The padding is
    not zeros: whatever the rung holds beyond ``n`` must not matter."""
    cfg, params = weights
    fam = model_family(cfg)
    toks = tokens_of(cfg, 1, 96, seed=n)
    run = jax.jit(lambda p, t, c: fam.prefill(p, t, jnp.asarray([n]), c, cfg))
    exact_logits, exact = run(params, toks[:, :n], fam.init_cache(cfg, 1, n))
    padded_logits, padded = run(params, toks, fam.init_cache(cfg, 1, 96))
    assert off(np.asarray(padded_logits), np.asarray(exact_logits)) < F32_TOL
    np.testing.assert_allclose(padded["state"], exact["state"], atol=F32_TOL)
    assert float(jnp.abs(padded["state"]).max()) > 1e-2  # there is a state
    for leaf in ("k", "v"):
        np.testing.assert_allclose(padded[leaf][:, :, :, :n],
                                   exact[leaf][:, :, :, :n], atol=F32_TOL)
        assert float(jnp.abs(exact[leaf]).max()) > 1e-2
    # windows of 4 every 2: window j is whole iff 2 j + 4 <= n
    whole = max((n - cfg.kernel_size) // cfg.kernel_stride + 1, 0)
    kbar = np.asarray(padded["kbar"])
    assert not kbar[:, :, :, whole:].any()
    k = np.asarray(padded["k"])
    for j in (0, whole // 2, whole - 1):
        if 0 <= j < whole:
            np.testing.assert_allclose(
                kbar[:, :, :, j], k[:, :, :, 2 * j:2 * j + 4].mean(3),
                atol=F32_TOL)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_pooled_keys_kept_step_by_step_are_the_keys_pooled_afresh(dtype):
    """A decode step writes the pooled key of the window its position
    completes, and nothing where none completes: after a prompt of 13 and 40
    decode steps the ``kbar`` leaf is what ``pooled_keys`` makes of the ``k``
    leaf at the true length (in bfloat16: to a unit in the last place, the
    sum's order)."""
    cfg = tiny(dtype=dtype, dense_len=32, topk=4)
    params = weights_of(cfg, seed=4)
    n, steps = 13, 40
    toks = tokens_of(cfg, 2, n + steps, seed=4)
    _, _, cache, _ = through_the_cache(cfg, params, toks, [n, n - 2], steps,
                                       padded_to=16, max_len=64)
    for b, length in enumerate([n + steps, n - 2 + steps]):
        k = cache["k"][:, b].transpose(0, 2, 1, 3)  # [Ns, T, Hkv, D]
        afresh = minicpm_sala.pooled_keys(
            k, jnp.full((k.shape[0],), length), cfg).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            np.asarray(cache["kbar"][:, b], np.float32),
            np.asarray(afresh.astype(cache["kbar"].dtype), np.float32),
            atol=1e-6 if dtype == "float32" else 2 ** -7)
        assert float(jnp.abs(afresh).max()) > 1e-2


def recency_for_selection(monkeypatch):
    """The control: the reference reads block 0 and the most RECENT blocks
    where the rule ranks by score."""
    def recent(q, k, rows, sizes):
        s, block = k.shape[1], sizes["block_size"]
        blocks = np.arange(-(-s // block))[None]
        last = rows[:, None] // block
        chosen = (blocks <= last) & ((blocks > last - sizes["topk"] + 1)
                                     | (blocks < sizes["init_blocks"]))
        return jnp.asarray(np.repeat(chosen, block, axis=-1)[:, :s])[
            None, None]
    monkeypatch.setattr(ref, "selection", recent)


def test_a_selection_by_recency_is_outside_the_tolerance(monkeypatch):
    """In the served type at contexts of 150-190 (of 19-24 blocks a query
    reads six): the program against the reference is inside the tolerance at
    the median position (``EDGE_TOL`` says why not at each), against a
    reference that reads the most recent blocks it is outside it at the
    median and several times further at the worst."""
    cfg = tiny(dtype="bfloat16")
    params = weights_of(cfg, seed=3)
    lengths, steps = [150, 170], 20
    toks = tokens_of(cfg, 2, 190, seed=3)
    got, _, _, _ = through_the_cache(cfg, params, toks, lengths, steps,
                                     padded_to=192, max_len=192)
    errs = rel_rms_each(got, wanted(params, toks, cfg, lengths, steps))
    assert np.median(errs) < BF16_TOL and errs.max() < EDGE_TOL
    recency_for_selection(monkeypatch)
    errs = rel_rms_each(got, wanted(params, toks, cfg, lengths, steps))
    assert np.median(errs) > BF16_TOL and errs.max() > EDGE_TOL, errs


def test_the_harness_two_layer_cut_runs_a_sparse_and_a_lightning_layer():
    """``bench_server.check_reference``'s shape for a family: ``n_layer = 2``
    and ``a[:2]`` of every leaf of ``params["blocks"]``.  The published cut
    starts ``S L``: one layer of each kind with the first two of the twelve
    MLPs, through a cache of all three kinds of leaf whose 28 positions are
    no whole number of blocks (under ``dense_len`` a cache is read as any
    family's), in the served type against the float32 reference, under the
    benchmark's own limit.  The selection is NOT in its sight."""
    model = dict(dataclasses.asdict(tiny(dtype="bfloat16")), d_model=256,
                 layer_pattern="SLLLLLLSSLLL", n_layer=12, dim_model_base=64)
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 3000000019)
    assert params["blocks"]["mlp"]["w_down"].shape[0] == 12
    cut = dataclasses.replace(cfg, n_layer=2)
    assert cut.kinds == "SL"
    part = dict(params, blocks=jax.tree.map(lambda a: a[:2], params["blocks"]))
    toks = tokens_of(cfg, 1, 24 + 3, seed=5)
    got = bench_server.through_the_cache(
        model_family(cut), part, cut, toks, 24, 3)
    want = np.asarray(bench_family.reference_logits(
        part, jnp.asarray(toks), cut))[0]
    errs = bench_server.logit_errors(got, [want[23 + i] for i in range(4)])
    assert errs["ok"], errs
    cache = model_family(cut).init_cache(cut, 1, 28)
    assert cache["k"].shape[0] == 1 and cache["state"].shape[0] == 1
    assert cache["kbar"].shape[3] == 14


def test_state_kept_in_bfloat16_is_outside_the_tolerance(weights):
    """The lower-precision control: everything float32 but the lightning
    state ``S``, which the cache keeps in bfloat16 (rounded after prefill and
    after every decode step).  That is off the reference by many times what
    the float32 program is.  The first logits, which prefill computes before
    the state is rounded, are untouched."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 40, seed=2)
    want = wanted(params, toks, cfg, [19, 19], 8)
    good, _, _, _ = through_the_cache(cfg, params, toks, [19, 19], 8)
    bad, _, cache, _ = through_the_cache(cfg, params, toks, [19, 19], 8,
                                         state_dtype=jnp.bfloat16)
    assert cache["state"].dtype == jnp.bfloat16
    assert off(good, want) < F32_TOL
    assert off(bad[:, 0], want[:, 0]) < F32_TOL
    assert min(off(bad[b, i], want[b, i])
               for b in range(2) for i in range(1, 9)) > F32_TOL
    assert off(bad, want) > 5 * F32_TOL


def test_importing_the_family_runs_no_jax_computation():
    """Every worker imports ``ray_tpu.models`` (the training gang's too): the
    family's two modules define functions and constants and nothing else; no
    array is made at import."""
    for module in (minicpm_sala, minicpm_sala_decode):
        made = [name for name, value in vars(module).items()
                if isinstance(value, (jax.Array, np.ndarray))]
        assert not made, made
        source = inspect.getsource(module)
        assert "jax.devices" not in source and "device_put" not in source


# ------------------------------------------------------------------ engine
# prompts under, just over and well over the tiny dense_len of 64: a prompt
# of n characters is n + 1 tokens
PROMPTS = ["the first prompt, well under sixty-four tokens",
           "second " * 10,
           "a third, somewhat longer prompt that reaches beyond it " * 2,
           "and a fourth one to fill the last slot of the four " * 3]


def make_engine(slots=4, max_seq_len=256):
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


def test_engine_slots_hold_state_keys_and_pooled_keys():
    """What ``llm/engine.py`` needed for a third kind of position-bearing
    leaf and a read that chooses its blocks: nothing.  A slot's second tenant
    gives the ids it gives alone (the state is replaced whole at admission;
    pooled keys the last tenant left beyond the new prompt are never seen);
    a request among full slots gives the ids it gives alone; streamed equals
    unary; the family's counts reach ``stats()``."""
    params = SamplingParams(max_tokens=12, stop_token=-1)
    assert [len(p) + 1 for p in PROMPTS] == [47, 71, 111, 154]
    alone = [by_hand(make_engine(), [p], params)[0] for p in PROMPTS]
    assert len({tuple(a) for a in alone}) == len(PROMPTS)
    # One slot: every request but the first is the slot's next tenant, and
    # the longest prompt's cache is what the shortest finds there.
    one = make_engine(slots=1)
    order = [3, 0, 2, 1]
    assert by_hand(one, [PROMPTS[i] for i in order], params) == [
        alone[i] for i in order]
    # Four slots, all full, admitted in one step and decoded together: rows
    # under and over dense_len in one program.
    full = make_engine()
    assert by_hand(full, PROMPTS, params) == alone
    assert all(s is None for s in full.slots)
    # Through the loop: unary and streamed.
    assert [r["token_ids"] for r in full.generate(PROMPTS, params)] == alone
    full.tokenizer = bench_server.VisibleTokenizer()
    rid = full.add_request(PROMPTS[2], params)
    streamed = "".join(full.stream_request(rid))
    assert bench_server.ids_of(streamed) == alone[2]
    stats = full.stats()
    assert stats["host_syncs"] == stats["decode_steps"] + stats["admitted"]
    assert stats["overrun_row_steps"] == 0  # every stream ended by count
    prompt_tokens = sum(len(p) + 1 for p in PROMPTS)
    nl = full.cfg.model.kinds.count("L")
    assert stats["prefill_lightning_positions"] == nl * (
        2 * prompt_tokens + len(PROMPTS[2]) + 1)
    assert stats["prefill_lightning_chunk_positions"] == (
        nl * stats["admitted"] * 256)
    assert 0 < stats["lightning_positions"] <= stats[
        "lightning_chunk_positions"]
    # rows over dense_len read 6 blocks of 8 of contexts of 71-166
    assert 0 < stats["sparse_read_positions"] < stats["sparse_live_positions"]
    full.shutdown()


def test_idle_slots_stay_finite_through_two_hundred_steps():
    """Every slot is decoded every step, tenant or not: the state of the
    slots nobody occupies (token 0 at position 0, over and over, on whatever
    the last tenant left) must stay finite for a whole run."""
    engine = make_engine(slots=4, max_seq_len=512)
    params = SamplingParams(max_tokens=8, stop_token=-1)
    by_hand(engine, PROMPTS, params)  # every slot has had a tenant
    long = SamplingParams(max_tokens=200, stop_token=-1)
    assert len(by_hand(engine, ["one long answer"], long)[0]) == 200
    assert engine.stats()["decode_steps"] >= 200
    for leaf in ("state", "k", "v", "kbar"):
        assert bool(jnp.isfinite(engine.cache[leaf]).all()), leaf
    assert float(jnp.abs(engine.cache["state"][:, 1:]).max()) < 1e3
    again = by_hand(engine, PROMPTS[:1], params)
    assert again == by_hand(make_engine(), PROMPTS[:1], params)


def test_the_engine_and_serve_know_nothing_of_the_family():
    """Seven ``model_config`` PRs added a family with no edit of the engine
    or of ``serve/`` for it; so does this one."""
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    for path in [root / "llm" / "engine.py", *(root / "serve").rglob("*.py")]:
        text = path.read_text().lower()
        assert not any(word in text for word in (
            "minicpm", "sala", "lightning", "kbar", "dense_len", "sparse_read",
            "listed_blocks", "scale_emb")), path


def test_bench_family_builds_the_programs_tree():
    model = dataclasses.asdict(tiny(dtype="bfloat16"))
    params = bench_family.load_params(model, 3)
    want = jax.eval_shape(lambda: minicpm_sala_init(
        jax.random.PRNGKey(0), MinicpmSalaConfig(**model)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    # no greedy stream ends early: the stop id's row of the head is 0
    from ray_tpu.llm.tokenizer import ByteTokenizer
    assert not np.asarray(params["lm_head"][ByteTokenizer.EOS]).any()
    assert np.asarray(params["lm_head"][ByteTokenizer.EOS + 1]).any()
    assert np.asarray(params["wte"][ByteTokenizer.EOS]).any()


def test_the_cells_draw_spreads_the_scores_the_selection_ranks_by():
    """Under the cell's draw at a width where the scales mean something (d
    256, heads of 128): a sparse layer's scores ``q . k / sqrt(D)`` spread by
    ~2, against a POOLED key (32 keys' mean) by 0.25-0.5, so the softmax over
    the windows is not flat and a block's score has an order that rounding
    does not set; the stream starts at RMS 0.5."""
    model = dataclasses.asdict(MinicpmSalaConfig(
        dtype="float32", vocab_size=512, d_model=256, n_head=4, n_kv_head=2,
        lightning_heads=4, layer_pattern="SL", n_layer=2, d_ff=256))
    cfg = bench_family.config(model)
    params = bench_family.load_params(model, 11)
    toks = jnp.asarray(tokens_of(cfg, 1, 512))
    x = ref.ref_embed(params, toks, dataclasses.asdict(cfg))
    assert 0.4 < float(jnp.sqrt((x * x).mean())) < 0.6
    w = params["blocks"]["sparse"]
    y = layers.rmsnorm(x, w["rms"][0], cfg.rms_eps)
    q, k, _, _ = minicpm_sala.project(y, w, 0, cfg)
    scores = jnp.einsum("bshd,bthd->bhst", q[:, :, :2], k) / np.sqrt(128)
    assert 1.7 < float(scores.std()) < 2.3
    kbar = minicpm_sala.pooled_keys(k, jnp.asarray([512]), cfg)
    pooled = jnp.einsum("bshd,bwhd->bhsw", q[:, :, :2], kbar[:, :-2]
                        ) / np.sqrt(128)
    assert 0.25 < float(pooled.std()) < 0.5


@pytest.mark.parametrize("kind", ["prefill", "decode_replica"])
def test_kv_handover_engines_refuse_state_and_pooled_keys_beside_keys(kind):
    """The disaggregated hand-over moves ``k`` and ``v`` pages only: both
    ends refuse a cache with more beside them when they are BUILT."""
    from ray_tpu.llm.disagg import DecodeReplica, PrefillEngine

    build = PrefillEngine if kind == "prefill" else DecodeReplica
    with pytest.raises(NotImplementedError) as err:
        build(EngineConfig(model=tiny(), max_batch_size=2, max_seq_len=32))
    assert "minicpm_sala" in str(err.value) and "kbar" in str(err.value)
