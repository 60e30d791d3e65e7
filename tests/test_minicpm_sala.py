"""MiniCPM-SALA family (``ray_tpu/models/minicpm_sala*.py``) against its plain
float32 reference (``benchmarks/reference/minicpm_sala_ref.py``: the lightning
RECURRENCE, dense masked softmax with the selection written out query by
query), at tiny widths on the CPU with seeded weights: pattern ``SLLSSL``, 4
heads of 16 (2 key-value heads in the sparse layers), ``dense_len`` 64, blocks
of 8, pooled windows of 4 every 2, the 6 best blocks, a window of 16.  Prompts
under, at and over ``dense_len`` and generations that cross it.  Logits, not
tokens.  Each tolerance says what it allows for.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala_ref as ref
from ray_tpu.models import (MinicpmSalaConfig, mamba2, minicpm_sala,
                            minicpm_sala_decode, minicpm_sala_init,
                            model_family)
from ray_tpu.ops import mamba_update as mamba_update_op

# float32 against float32, the largest difference of a logit as a share of
# the logits' spread (``off``): the two differ by the order of their sums
# only (the chunked scan against the recurrence, blocked softmax against
# dense, pooled keys by strides against by windows) through twelve branches:
# some tens of units in the last place (3e-6 measured), but for a lightning
# layer's first positions, where the read-out is ``q . k`` of ONE or two
# terms and the output norm divides by it: what cancels in the dot product
# is amplified (9e-5 at positions 0-1 of a pattern that starts ``L``; the
# limit leaves twice that).  A selection that ranks two blocks the other way
# round would be fifty times over it: the float32 program chooses what the
# reference chooses.  States and cache rows, which are ~1, are held to it
# as an absolute difference.
F32_TOL = 2e-4
# bfloat16 products (2^-9 a rounding, some fifty of them through six layers
# and the head) against float32, as a share of the logits' spread: the
# benchmark's measure (``bench_server.LOGIT_TOL`` is 3 % at d 4096).  Over
# ``dense_len`` a block at the rank's edge may be chosen the other way
# (pooled keys of bfloat16 keys against float32 ones): that is rounding, but
# of six blocks a query reads here one is a sixth of its read (of the
# published 64 a 64th), so positions that select are held to the tolerance
# at their MEDIAN and to ``EDGE_TOL`` at their worst (6.3 % measured).
BF16_TOL = 0.03
EDGE_TOL = 0.12


def tiny(**kw):
    return MinicpmSalaConfig.tiny(dtype=kw.pop("dtype", "float32"), **kw)


def lively(params):
    """The family's init at tiny widths is an embedding nothing perturbs
    (every matrix 0.02 on a width of 64): the tables stay, the matrices
    times 5, and the sparse layers' ``qk_norm`` weights 1.6 (scores of spread
    ~2.5: neither uniform nor one-hot, and the pooled scores spread enough
    to rank), so that every mixer moves the logits THROUGH the muP scales
    and a fault in one shows."""
    def scale(path, a):
        name, stack = path[-1].key, path[-2].key if len(path) > 1 else ""
        if name in ("wte", "lm_head"):
            return a
        if stack == "sparse" and name in ("q_norm", "k_norm"):
            return a * 1.6
        return a * 5 if a.ndim >= 3 else a
    return jax.tree_util.tree_map_with_path(scale, params)


def weights_of(cfg, seed=0):
    return lively(minicpm_sala_init(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def weights():
    cfg = tiny()
    return cfg, weights_of(cfg)


def tokens_of(cfg, rows, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length), dtype=np.int32)


def ref_logits(params, tokens, cfg, prompt_len=None, **sizes):
    return np.asarray(ref.minicpm_sala_ref_logits(
        params, jnp.asarray(tokens), dict(dataclasses.asdict(cfg), **sizes),
        cfg.kinds, prompt_len))


def off(got, want):
    """Largest difference of a logit over the logits' spread."""
    return float(np.abs(np.asarray(got) - want).max() / want.std())


def rel_rms_each(got, want):
    """The benchmark's measure, position by position."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)


def rel_rms(got, want):
    """The benchmark's measure, the worst position's."""
    return float(rel_rms_each(got, want).max())


@pytest.mark.parametrize("length", [40, 64, 100])
def test_family_resolves_and_full_forward_matches_the_reference(weights,
                                                                length):
    """One call over ``length`` positions: every position reads by the rule of
    a prompt of that length: under ``dense_len`` (40) everything, at and over
    it (64, 100) the selection (63 and 65: the cache's tests)."""
    cfg, params = weights
    fam = model_family(cfg)
    assert fam.name == "minicpm_sala" and fam.decode_step_counted is not None
    toks = tokens_of(cfg, 2, length, seed=length)
    got = np.asarray(jax.jit(lambda p, t: fam.apply(p, t, cfg))(params, toks))
    want = ref_logits(params, toks, cfg)
    assert want.std() > 0.02 and off(got, want) < F32_TOL
    if length == 40:
        loss = float(jax.jit(lambda p, t: fam.loss(p, t, cfg))(
            params, jnp.asarray(toks)))
        assert np.isfinite(loss) and abs(loss - np.log(cfg.vocab_size)) < 1.0


def test_the_lightning_mixer_is_nemotrons_scan_and_the_trees_update():
    """No second scan and no second update in the tree: the family's modules
    CALL ``mamba2.ssd_chunked`` and ``ops.mamba_update.mamba_update`` and
    define no recurrence of their own."""
    assert minicpm_sala.ssd_chunked is mamba2.ssd_chunked
    assert minicpm_sala_decode.mamba_update is mamba_update_op.mamba_update
    for module in (minicpm_sala, minicpm_sala_decode):
        source = inspect.getsource(module)
        assert "lax.scan(" not in source and "cumsum" not in source


@pytest.mark.parametrize("pattern", ["SLLSSL", "SLLLLLLSSLLL", "LLSLLS", "LL"])
def test_folded_layers_are_the_layers_in_order(pattern):
    """``layer_plan``'s scanned runs against the layers one by one (the
    reference's loop): the published cut ``S L6 S2 L3`` among them."""
    cfg = tiny(layer_pattern=pattern, n_layer=len(pattern))
    params = weights_of(cfg, seed=2)
    toks = tokens_of(cfg, 1, 70, seed=2)
    got = np.asarray(jax.jit(
        lambda p, t: minicpm_sala.minicpm_sala_apply(p, t, cfg))(params, toks))
    assert off(got, ref_logits(params, toks, cfg)) < F32_TOL


def without_gate_in(monkeypatch, kind):
    """The reference's ``lightning`` or ``sparse`` with its output gate left
    out."""
    whole = getattr(ref, kind)

    def faulty(*args, **kw):
        saved, ref.gate = ref.gate, lambda o, g: o
        try:
            return whole(*args, **kw)
        finally:
            ref.gate = saved
    monkeypatch.setattr(ref, kind, faulty)


FAULTS = {
    "scale_emb": dict(scale_emb=1.0),
    # r = 1.4 / sqrt(32) read off the CUT's depth
    "r_of_the_cut": dict(published_layers=6),
    "logit_divisor": dict(dim_model_base=64),
    "lightning_gate": lambda mp: without_gate_in(mp, "lightning"),
    "sparse_gate": lambda mp: without_gate_in(mp, "sparse"),
    "qk_norm": lambda mp: mp.setattr(
        ref, "head_norm", lambda x, g, eps: x if g.ndim == 1 else ref._rms(
            x, g, eps)),
    "output_norm": lambda mp: mp.setattr(
        ref, "head_norm", lambda x, g, eps: x if g.ndim == 2 else ref._rms(
            x, g, eps)),
    "no_rotary": lambda mp: mp.setattr(ref, "rotary", lambda x, theta: x),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_dropped_piece_is_outside_the_tolerance(weights, fault, monkeypatch):
    """A reference that leaves one piece of the mathematics out (a muP
    scale, a gate, a norm, the rotary term) is off the program by MORE than
    the served type's tolerance, not only the float32 one: each is seen by
    the comparison the benchmark makes."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 80, seed=6)
    got = np.asarray(jax.jit(
        lambda p, t: minicpm_sala.minicpm_sala_apply(p, t, cfg))(params, toks))
    assert rel_rms(got, ref_logits(params, toks, cfg)) < 1e-4
    sizes = FAULTS[fault]
    if callable(sizes):
        sizes(monkeypatch)
        sizes = {}
    if fault == "r_of_the_cut":  # the slopes still read the published depth
        r = cfg.scale_depth / np.sqrt(6)
        sizes = dict(scale_depth=r * np.sqrt(cfg.published_layers))
    assert rel_rms(got, ref_logits(params, toks, cfg, **sizes)) > BF16_TOL


def test_rotary_added_to_the_sparse_layers_is_outside_the_tolerance(
        weights, monkeypatch):
    """The sparse layers are position-free: a PROGRAM that turns their
    queries and keys as the lightning layers' are is off the reference by
    more than the served type's tolerance."""
    cfg, params = weights
    toks = tokens_of(cfg, 2, 80, seed=7)
    want = ref_logits(params, toks, cfg)
    project = minicpm_sala.project

    def turned(y, w, i, cfg):
        q, k, v, g = project(y, w, i, cfg)
        if k.shape[-2] == cfg.n_kv_head and y.ndim == 3:
            at = jnp.arange(y.shape[1])[None]
            q, k = (minicpm_sala.rope(a, at, cfg.rope_theta) for a in (q, k))
        return q, k, v, g

    monkeypatch.setattr(minicpm_sala, "project", turned)
    got = np.asarray(minicpm_sala.minicpm_sala_apply(params, toks, cfg))
    assert rel_rms(got, want) > BF16_TOL


@pytest.mark.parametrize("chunk", [4, 8, 16, 29, 64])
def test_the_chunked_scan_equals_the_recurrence_at_a_group_a_head(chunk):
    """``mamba2.ssd_chunked`` at ``G = H`` (every head its own ``B``,
    ``C``: lightning attention's ``k``, ``q``), ``dt`` 1 inside a row's length
    and 0 beyond it, no skip, against the recurrence itself, position by
    position, in numpy float64: 29 positions in chunks that divide them
    (29), that do not (4, 8, 16) and that hold them all (64)."""
    rng = np.random.default_rng(0)
    bsz, s, h, p, n = 2, 29, 6, 5, 7
    x = rng.normal(size=(bsz, s, h, p))
    b, c = rng.normal(size=(2, bsz, s, h, n))
    dt = np.ones((bsz, s, h))
    dt[1, 20:] = 0.0  # a row's padding
    a = -np.asarray(minicpm_sala.slopes(
        tiny(lightning_heads=h), 12), np.float64)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, last = mamba2.ssd_chunked(
        f32(x), f32(dt), f32(a), f32(b), f32(c), jnp.zeros(h), chunk,
        jnp.float32)
    state = np.zeros((bsz, h, p, n))
    for t in range(s):
        state = (np.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None]
                 * b[:, t][:, :, None, :])
        np.testing.assert_allclose(
            y[:, t], (state * c[:, t][:, :, None, :]).sum(-1), atol=2e-5)
        if t == 19:
            at_20 = state[1].copy()
    np.testing.assert_allclose(last, state, atol=2e-5)
    np.testing.assert_allclose(last[1], at_20, atol=2e-5)


@pytest.mark.parametrize("force_pallas", [False, True])
def test_the_one_token_update_at_a_group_a_head_is_its_oracle(force_pallas):
    """``ops.mamba_update`` at ``G = H`` = 32 heads of ``[128, 128]`` (the
    published lightning state: sixteen registers a head where Granite's is
    eight), the kernel in interpret mode against its XLA oracle: the new
    state and ``y`` to a float32 operation's rounding (the CPU fuses the
    oracle's multiply-add); the other layer of the leaf untouched, bit for
    bit."""
    rng = np.random.default_rng(1)
    layers, slots, h, d = 2, 2, 8, 128
    leaf = jnp.asarray(rng.normal(size=(layers, slots, h, d, d)), jnp.float32)
    x, b, c = (jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
               for _ in range(3))
    keep = jnp.broadcast_to(jnp.exp(-minicpm_sala.slopes(
        MinicpmSalaConfig(lightning_heads=h), 9)), (slots, h))
    want_y, want = mamba_update_op.mamba_update_xla(
        leaf, 1, x, jnp.ones_like(keep), keep, b, c)
    y, new = mamba_update_op.mamba_update(
        leaf, 1, x, jnp.ones_like(keep), keep, b, c,
        force_pallas=force_pallas)
    np.testing.assert_allclose(new[1], want[1], rtol=0, atol=2e-6)
    np.testing.assert_array_equal(new[0], leaf[0])
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-4)


def brute_force_blocks(scores, t, cfg):
    """The selection in numpy float64 loops.  scores ``[G, W]`` -> the set of
    blocks position ``t`` reads."""
    kernel, stride, block = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    nw = scores.shape[1]
    visible = [j for j in range(nw) if stride * j + kernel - 1 <= t]
    p = np.zeros(nw)
    for g in range(scores.shape[0]):
        e = np.exp(scores[g, visible] - scores[g, visible].max())
        p[visible] += e / e.sum()
    ranked = []
    for b in range(t // block + 1):
        meets = [j for j in visible
                 if stride * j < (b + 1) * block and stride * j + kernel
                 > b * block]
        score = max([p[j] for j in meets], default=0.0)
        if b < cfg.init_blocks or (b + 1) * block - 1 >= t - cfg.window_size + 1:
            score = np.inf
        ranked.append((-score, b))
    return {b for _, b in sorted(ranked)[:cfg.topk]}


def test_the_selection_is_the_brute_force_one():
    """``choose_blocks`` against loops in numpy, a query at a time: block 0
    and the blocks over the last 16 positions are inside the six, a pooled
    window is seen only once its last position is, the rest by score, fewer
    than six blocks give -1, and equal scores go to the lower block."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    g, nw = 2, 100
    scores = rng.normal(size=(1, 1, g, 1, nw)) * 2.0
    for t in (3, 17, 47, 63, 64, 90, 133, 199):
        ids = np.asarray(minicpm_sala.choose_blocks(
            jnp.asarray(scores, jnp.float32), jnp.asarray([t]), cfg))[0, 0, 0]
        want = brute_force_blocks(scores[0, 0, :, 0], t, cfg)
        assert set(ids[ids >= 0].tolist()) == want, t
        mask = np.asarray(minicpm_sala.chosen_blocks(  # a prefill's form
            jnp.asarray(scores, jnp.float32), jnp.asarray([t]), cfg))[0, 0, 0]
        assert set(np.flatnonzero(mask).tolist()) == want, t
        assert (ids >= 0).sum() == min(cfg.topk, t // cfg.block_size + 1)
        assert 0 in want and t // cfg.block_size in want
        assert max(t - 15, 0) // cfg.block_size in want
    # equal scores everywhere: after the forced blocks, the LOWEST blocks
    ids = np.asarray(minicpm_sala.choose_blocks(
        jnp.zeros((1, 1, g, 1, nw), jnp.float32), jnp.asarray([199]), cfg))
    assert sorted(ids[0, 0, 0].tolist()) == [0, 1, 2, 3, 23, 24]
    mask = np.asarray(minicpm_sala.chosen_blocks(
        jnp.zeros((1, 1, g, 1, nw), jnp.float32), jnp.asarray([199]), cfg))
    assert np.flatnonzero(mask[0, 0, 0]).tolist() == [0, 1, 2, 3, 23, 24]
    # a window that ends AT t is seen, one that ends after it is not: a huge
    # score on window j moves block j // 4's rank only once 2 j + 3 <= t
    spike = np.zeros((1, 1, g, 1, nw))
    spike[..., 40] = 30.0  # positions 80-83: block 10
    for t, seen in ((180, True), (83, True), (82, False)):
        ids = np.asarray(minicpm_sala.choose_blocks(
            jnp.asarray(spike, jnp.float32), jnp.asarray([t]), cfg))[0, 0, 0]
        recent = {0, *range(max(t - 15, 0) // 8, t // 8 + 1)}
        assert (10 in set(ids.tolist()) - recent) == (seen and 10 not in recent)
