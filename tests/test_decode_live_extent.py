"""Decode attention reads a full-extent cache up to the batch's longest live
context (``ops/decode_attention.py`` ``attend_live_blocks``; LongCat's
``mla_absorbed`` through the same helper), in blocks of ``extent_step(T)``.

What is pinned here, on the CPU: the extent is one function of ``pos`` for the
host and the program; the result is the one-shot softmax's to rounding and a
row's BITS do not depend on its neighbours' contexts; nothing beyond the
extent is read; a ring and a cache of one extent take the old path.  The
latent families' pass is pinned in both its forms: the XLA loop
(``mla_absorbed`` as it runs here) and the Pallas kernel a TPU takes
(``ops/latent_attention.py``, forced in interpret mode, at the three served
head and channel shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mla
from ray_tpu.models.layers import matmul
from ray_tpu.models.longcat import LongcatConfig
from ray_tpu.ops.decode_attention import (decode_attention, extent_step,
                                          live_extent)
from test_llama_kernels import dense_decode_attention
from test_mimo_v2 import (OLDER_FAMILIES, decode_attention_before,
                          decode_operands)

T = 2048  # four extents of 512
STEP = 512
# (B, H, Hkv, D, Dv, dtype, sink): the older families' head shapes and MiMo's
# full layer (a sink, values narrower than keys), as the tiny configs have them
SHAPES = [(b, h, hkv, d, d, dtype, False)
          for b, h, hkv, _t, d, dtype in OLDER_FAMILIES] + [
    (3, 8, 4, 24, 16, "float32", True), (3, 8, 4, 24, 16, "bfloat16", True)]
# rounding of a result of magnitude ~1: the one-shot softmax rounds its
# weights once, the blocks' running one rescales them
TOL = {"float32": 2e-6, "bfloat16": 2e-2}


def test_the_step_is_blocks_of_512_positions():
    assert {extent_step(t) for t in (1024, 2048, 4096, 8192, 16384)} == {512}
    # one block, or no whole number of them: one extent, no loop
    alone = (8, 24, 32, 64, 128, 256, 512, 1000, 1280, 2047)
    assert [extent_step(t) for t in alone] == list(alone)


@pytest.mark.parametrize("t", [128, 1024, 2048, 4096, 16384])
def test_live_extent_on_ints_equals_the_traced_value(t):
    traced = jax.jit(lambda longest: live_extent(longest, t))
    step = extent_step(t)
    for longest in range(t + 1):
        on_host = live_extent(longest, t)
        assert isinstance(on_host, int)
        assert on_host == max(step, -(-longest // step) * step) <= t
        if longest % 7 == 0 or longest % step in (0, 1, step - 1):
            assert int(traced(jnp.int32(longest))) == on_host
    assert int(jax.jit(jax.vmap(lambda n: live_extent(n, t)))(
        jnp.arange(t + 1)).sum()) == sum(
            live_extent(n, t) for n in range(t + 1))


def attend(shape, pos, with_self, seed=0):
    b, h, hkv, d, dv, dtype, with_sink = shape
    q, kc, vc, ks, vs = decode_operands(b, h, hkv, T, d, dv, dtype, seed)
    own = dict(k_self=ks, v_self=vs) if with_self else {}
    if with_sink:
        own["sink"] = jnp.asarray(
            np.random.default_rng(2).normal(size=h), jnp.float32)
    return decode_attention(q, kc, vc, jnp.asarray(pos, jnp.int32), 1,
                            **own), (q, kc, vc, own)


def one_shot(q, kc, vc, pos, own):
    """The whole cache in one softmax, in float64."""
    return dense_decode_attention(q, kc, vc, pos, 1, own.get("k_self"),
                                  own.get("v_self"), own.get("sink"))


# pos 0; an extent's last position and the next one's first, in the form
# with the current token beside the cache (reads [0, pos)) and in the form
# with it written (reads [0, pos]); T - 1
EDGES = [0, STEP - 1, STEP, STEP + 1, 3 * STEP, T - 1]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("with_self", [True, False])
def test_blocks_up_to_the_live_extent_give_the_one_shot_softmax(
        shape, with_self):
    b, dtype = shape[0], shape[5]
    for edge in EDGES:
        pos = [edge] + [min(edge, 5 + 3 * i) for i in range(1, b)]
        got, (q, kc, vc, own) = attend(shape, pos, with_self, seed=edge)
        assert got.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), one_shot(q, kc, vc, pos, own),
            atol=TOL[dtype])
        if not shape[6]:  # PR 44's function knew no sink
            before = jax.jit(decode_attention_before, static_argnums=4)(
                q, kc, vc, jnp.asarray(pos, jnp.int32), 1,
                *[own[k] for k in ("k_self", "v_self") if k in own])
            np.testing.assert_allclose(
                np.asarray(got, np.float64), np.asarray(before, np.float64),
                atol=TOL[dtype])


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("with_self", [True, False])
def test_a_rows_bits_do_not_depend_on_its_neighbours_contexts(
        shape, with_self):
    """Row 0 at 100 positions beside neighbours that force the first extent,
    a middle one and the whole cache: the same bits.  (A reduction over a
    longer axis is NOT the same bits on this backend, even where all it adds
    is zeros: one softmax over a prefix would fail this.)"""
    b = shape[0]
    results = [np.asarray(attend(shape, [100] + [other] * (b - 1),
                                 with_self)[0], np.float32)[0]
               for other in (7, STEP, 5 * STEP + 1, T - 1)]
    for other in results[1:]:
        np.testing.assert_array_equal(results[0], other)


@pytest.mark.parametrize("with_self", [True, False])
def test_nothing_beyond_the_live_extent_is_read(with_self):
    """Not-a-numbers beyond the extent the batch needs do not reach the
    result: those blocks are never run.  (Masked, they would: a weight of
    zero times not-a-number.)  One position further, and they do."""
    shape = SHAPES[1]
    b = shape[0]
    q, kc, vc, ks, vs = decode_operands(*shape[:3], T, *shape[3:6], seed=3)
    own = dict(k_self=ks, v_self=vs) if with_self else {}
    reach = 2 * STEP  # of the longest row; the others are short
    # the row reads [0, pos) beside its own token, [0, pos] with it written
    longest = reach if with_self else reach - 1
    poisoned = vc.at[:, :, :, reach:].set(jnp.nan)
    pos = jnp.asarray([longest] + [9] * (b - 1), jnp.int32)
    clean = decode_attention(q, kc, vc, pos, 1, **own)
    got = decode_attention(q, kc, poisoned, pos, 1, **own)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    beyond = decode_attention(q, kc, poisoned, pos.at[0].add(1), 1, **own)
    assert not np.isfinite(np.asarray(beyond, np.float32)).all()


def loops(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("while[")


def test_a_ring_and_a_cache_of_one_extent_take_the_unbounded_path():
    """Nothing in a ring is dead, and 128 positions are one extent: no loop
    is emitted and the program is what it was (``tests/test_mimo_v2.py``
    holds that path to PR 44's bits and operations)."""
    pos = jnp.asarray([5, 1900, 17], jnp.int32)
    q, kc, vc, ks, vs = decode_operands(3, 4, 2, T, 16, 16, "float32")
    call = lambda **kw: lambda q, kc, vc, pos: decode_attention(
        q, kc, vc, pos, 1, k_self=ks, v_self=vs, **kw)
    assert loops(call(), q, kc, vc, pos) == 1
    assert loops(call(window=T), q, kc, vc, pos) == 0
    short = decode_operands(3, 4, 2, 128, 16, 16, "float32")[:3]
    assert loops(call(), *short, pos) == 0
    # a ring that has not wrapped yet holds what a plain cache holds
    np.testing.assert_allclose(
        call(window=T)(q, kc, vc, pos),
        one_shot(q, kc, vc, [5, 1900, 17], dict(k_self=ks, v_self=vs)),
        atol=TOL["float32"])


@pytest.mark.parametrize("h", [12, 18])
def test_a_ring_of_512_beside_a_cache_of_16384_positions(h):
    """Laguna's pair of extents, with its query groups of 6 and 9 over two
    key-value heads: rows at 5, 511, 512, 9,000 and 15,871 positions.  The
    full leaf (``T`` = 16,384, thirty-two extents) is read in one loop up to
    the batch's longest context and gives the one-shot softmax over ``[0,
    pos)`` and the row's own token; the ring (four lane tiles' worth, 512
    slots, position ``p`` at ``p mod 512``, wrapped up to thirty times) is
    read whole in one softmax, no loop, and gives the softmax over the last
    511 positions and the row's own token, whatever the slots held
    before."""
    t, w, pos = 16384, 512, [5, 511, 512, 9000, 15871]
    q, kc, vc, ks, vs = decode_operands(len(pos), h, 2, t, 16, 16, "float32",
                                        seed=h)
    own = dict(k_self=ks, v_self=vs)
    at = jnp.asarray(pos, jnp.int32)
    full = lambda q, kc, vc, at: decode_attention(q, kc, vc, at, 1, **own)
    assert loops(full, q, kc, vc, at) == 1
    np.testing.assert_allclose(full(q, kc, vc, at),
                               one_shot(q, kc, vc, pos, own),
                               atol=TOL["float32"])
    # the rings as prefill and the steps since have left them: slot r the
    # newest position < pos with p = r mod 512, junk where none has come
    held = np.stack([(n - 1) - np.mod((n - 1) - np.arange(w), w)
                     for n in pos])  # [B, w]
    take = jnp.asarray(np.clip(held, 0, t - 1))[None, :, None, :, None]
    ring_k, ring_v = (jnp.where(
        jnp.asarray(held >= 0)[None, :, None, :, None],
        jnp.take_along_axis(a, take, axis=3), 7.0) for a in (kc, vc))
    assert ring_k.shape == (kc.shape[0], len(pos), 2, w, 16)
    ring = lambda q, kc, vc, at: decode_attention(
        q, kc, vc, at, 1, window=w, **own)
    assert loops(ring, q, ring_k, ring_v, at) == 0
    got = ring(q, ring_k, ring_v, at)
    for b, n in enumerate(pos):  # the window's positions out of the full leaf
        low = max(n - (w - 1), 0)
        want = dense_decode_attention(
            q[b:b + 1], kc[:, b:b + 1, :, low:n], vc[:, b:b + 1, :, low:n],
            [n - low], 1, ks[b:b + 1], vs[b:b + 1])
        np.testing.assert_allclose(got[b], want[0], atol=TOL["float32"])


# ------------------------------------------------------- models/mla.py
def mla_absorbed_before(q, latent_self, latent_cache, pos, att, cfg):
    """``mla_absorbed`` as it was at PR 45, verbatim: one attention's slice
    ``[B, T, C]`` of the cache, scored whole."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w_k, w_v = att["wkv_b"][..., :dn], att["wkv_b"][..., dn:]
    qt = matmul("bhn,chn->bhc", q[..., :dn], w_k).astype(q.dtype)
    qc = jnp.concatenate([qt, q[..., dn:]], -1)  # [B, H, C]
    scale = q.shape[-1] ** -0.5
    scores = matmul("bhc,btc->bht", qc, latent_cache) * scale
    before = jnp.arange(latent_cache.shape[1])[None, None] < pos[:, None, None]
    scores = jnp.where(before, scores, -1e30)
    s_self = matmul("bhc,bc->bh", qc, latent_self) * scale
    probs = jax.nn.softmax(
        jnp.concatenate([scores, s_self[..., None]], -1), axis=-1)
    oc = (matmul("bht,btc->bhc", probs[..., :-1].astype(q.dtype),
                 latent_cache[..., :rkv])
          + probs[..., -1:] * latent_self[:, None, :rkv])
    o = matmul("bhc,chv->bhv", oc.astype(q.dtype), w_v)
    return matmul("bhv,hve->be", o.astype(q.dtype), att["wo"])


def mla_operands(t, dtype, seed=0):
    cfg = LongcatConfig.tiny(dtype=dtype)
    rng = np.random.default_rng(seed)
    b, h = 3, cfg.n_head
    dn, dr, rkv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    draw = lambda *shape: jnp.asarray(
        rng.normal(size=shape) / np.sqrt(shape[-1]), dtype)
    att = {"wkv_b": draw(rkv, h, dn + cfg.v_head_dim),
           "wo": draw(h, cfg.v_head_dim, cfg.d_model)}
    return cfg, att, (draw(b, h, dn + dr) * 4, draw(b, rkv + dr),
                      draw(3, b, t, rkv + dr) * 4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mla_absorbed_reads_the_stack_in_blocks_and_gives_what_it_gave(dtype):
    cfg, att, (q, latent_self, cache) = mla_operands(T, dtype)
    now = jax.jit(lambda *a: mla.mla_absorbed(
        *a, att, cfg, layer=2))
    before = jax.jit(lambda q, ls, cache, pos: mla_absorbed_before(
        q, ls, cache[2], pos, att, cfg))
    beside = {}
    for others in (3, STEP + 1, T - 1):
        for edge in (0, STEP - 1, STEP, 700):
            pos = jnp.asarray([edge, others, others], jnp.int32)
            got = now(q, latent_self, cache, pos)
            assert got.dtype == jnp.float32
            np.testing.assert_allclose(
                got, before(q, latent_self, cache, pos),
                atol=TOL[dtype], rtol=TOL[dtype])
            beside.setdefault(edge, []).append(np.asarray(got[0]))
    for rows in beside.values():  # one row, three neighbourhoods
        np.testing.assert_array_equal(rows[0], rows[1])
        np.testing.assert_array_equal(rows[0], rows[2])
    poisoned = cache.at[:, :, 2 * STEP:].set(jnp.nan)
    pos = jnp.asarray([2 * STEP, 1, 40], jnp.int32)
    np.testing.assert_array_equal(now(q, latent_self, poisoned, pos),
                                  now(q, latent_self, cache, pos))


def test_mla_absorbed_over_a_cache_of_one_extent_is_bit_for_bit_what_it_was():
    cfg, att, (q, latent_self, cache) = mla_operands(64, "bfloat16", seed=1)
    pos = jnp.asarray([0, 17, 63], jnp.int32)
    now = jax.jit(lambda *a: mla.mla_absorbed(
        *a, att, cfg, layer=1))(q, latent_self, cache, pos)
    before = jax.jit(lambda q, ls, cache, pos: mla_absorbed_before(
        q, ls, cache[1], pos, att, cfg))(q, latent_self, cache, pos)
    np.testing.assert_array_equal(now, before)
    assert loops(lambda *a: mla.mla_absorbed(
        *a, att, cfg, layer=1), q, latent_self, cache, pos) == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mla_absorbed_over_thirty_two_blocks_with_the_halves_as_two_leaves(
    dtype
):
    """``T`` = 16,384 (the Mistral-4 cell's: 32 blocks of 512 where the older
    cells have 4-8), ``Wkvb`` as two leaves (``wk_b`` / ``wv_b``) instead of
    one sliced here: the one-shot softmax's result to rounding, rows at 1,
    9,000 and 16,383 cached positions in one batch; a row's bits do not
    depend on whether its neighbour makes the loop run 1 or 32 blocks; and
    nothing beyond the longest context's block is read."""
    t = 16384
    cfg, att, (q, latent_self, cache) = mla_operands(t, dtype, seed=2)
    dn = cfg.qk_nope_head_dim
    halves = {"wk_b": att["wkv_b"][..., :dn], "wv_b": att["wkv_b"][..., dn:],
              "wo": att["wo"]}
    now = jax.jit(lambda *a: mla.mla_absorbed(
        *a, halves, cfg, layer=1))
    before = jax.jit(lambda q, ls, cache, pos: mla_absorbed_before(
        q, ls, cache[1], pos, att, cfg))
    pos = jnp.asarray([1, 9000, t - 1], jnp.int32)
    got = now(q, latent_self, cache, pos)
    np.testing.assert_allclose(got, before(q, latent_self, cache, pos),
                               atol=TOL[dtype], rtol=TOL[dtype])
    near = now(q, latent_self, cache, jnp.asarray([1, 9000, 9001], jnp.int32))
    np.testing.assert_array_equal(got[:2], near[:2])
    poisoned = cache.at[:, :, 18 * STEP:].set(jnp.nan)
    np.testing.assert_array_equal(
        now(q, latent_self, poisoned, jnp.asarray([1, 9000, 9001], jnp.int32)),
        near)
    assert loops(lambda *a: mla.mla_absorbed(
        *a, halves, cfg, layer=1), q, latent_self, cache, pos) == 1


# ------------------------------ the pass as ONE kernel (ops/latent_attention)
# (family, H, rkv, Wkvb as two leaves): the three served head / channel
# shapes, ``C`` = rkv + 64, with everything else cut small
SERVED_LATENTS = [("mistral4", 32, 256, True), ("kimi_linear", 32, 512, True),
                  ("longcat", 64, 512, False)]


def served_operands(h, rkv, two_leaves, t, b=4, layers=3, seed=0):
    """``mla_operands`` at a served head count and latent width: bfloat16,
    ``dn`` 32 and ``dv`` 16 (the absorbed products do not see them), the key
    of all heads ``dr`` 64 wide as published."""
    import types

    dn, dr, dv, d = 32, 64, 16, 64
    cfg = types.SimpleNamespace(kv_lora_rank=rkv, qk_nope_head_dim=dn)
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(
        rng.normal(size=shape) / np.sqrt(shape[-1]), "bfloat16")
    att = {"wkv_b": draw(rkv, h, dn + dv), "wo": draw(h, dv, d)}
    now = att if not two_leaves else {
        "wk_b": att["wkv_b"][..., :dn], "wv_b": att["wkv_b"][..., dn:],
        "wo": att["wo"]}
    return cfg, att, now, (draw(b, h, dn + dr) * 4, draw(b, rkv + dr),
                           draw(layers, b, t, rkv + dr) * 4)


@pytest.fixture
def as_one_kernel(monkeypatch):
    """``mla_absorbed`` takes the Pallas kernel, in interpret mode: what a TPU
    decides from the shapes the test decides here (no argument of
    ``mla_absorbed`` does)."""
    import functools

    from ray_tpu.ops.latent_attention import latent_attention

    def force(slots=None):
        monkeypatch.setattr(mla, "latent_attention", functools.partial(
            latent_attention, force_pallas=True, slots=slots))
    return force


@pytest.mark.parametrize("slots", [None, 2], ids=["by_shape", "two_a_cell"])
@pytest.mark.parametrize("family,h,rkv,two_leaves", SERVED_LATENTS,
                         ids=[s[0] for s in SERVED_LATENTS])
def test_mla_absorbed_as_one_kernel_gives_what_the_whole_softmax_gave(
    as_one_kernel, family, h, rkv, two_leaves, slots
):
    """Against PR 45's one-shot form at the existing tolerance: rows at 1,
    ``step``, ``step + 1`` and ``t - 1`` cached positions and an idle row,
    ``layer`` 2 of three reading its own slice; a row's bits the same whether
    its neighbours make the live bound one block or all four, and whichever
    cell of the grid it shares with them."""
    as_one_kernel(slots)
    cfg, att, now_att, (q, latent_self, cache) = served_operands(
        h, rkv, two_leaves, T)
    now = jax.jit(lambda *a: mla.mla_absorbed(*a, now_att, cfg, layer=2))
    before = jax.jit(lambda q, ls, cache, pos: mla_absorbed_before(
        q, ls, cache[2], pos, att, cfg))
    assert "pallas" in str(jax.make_jaxpr(now)(
        q, latent_self, cache, jnp.zeros(4, jnp.int32)))
    beside = {}
    for others in (1, STEP, STEP + 1, T - 1):
        for edge in (0, 1, STEP, STEP + 1):
            pos = jnp.asarray([edge, 0, others, others], jnp.int32)
            got = now(q, latent_self, cache, pos)
            assert got.dtype == jnp.float32
            np.testing.assert_allclose(
                got, before(q, latent_self, cache, pos),
                atol=TOL["bfloat16"], rtol=TOL["bfloat16"])
            beside.setdefault(edge, []).append(np.asarray(got[0]))
            beside.setdefault("idle", []).append(np.asarray(got[1]))
    for rows in beside.values():  # one row, four neighbourhoods
        for other in rows[1:]:
            np.testing.assert_array_equal(rows[0], other)
    poisoned = cache.at[:, :, 2 * STEP:].set(jnp.nan).at[:2].set(jnp.nan)
    pos = jnp.asarray([2 * STEP, 1, 40, 0], jnp.int32)
    np.testing.assert_array_equal(
        now(q, latent_self, poisoned, pos), now(q, latent_self, cache, pos))


@pytest.mark.parametrize("family,h,rkv,two_leaves", SERVED_LATENTS,
                         ids=[s[0] for s in SERVED_LATENTS])
def test_mla_absorbed_as_one_kernel_is_the_xla_loop_bit_for_bit(
    as_one_kernel, family, h, rkv, two_leaves
):
    """Off a TPU ``mla_absorbed`` is the XLA loop; forced through the kernel
    (interpret mode runs the same operations) it gives the same bits, at
    every layer of the stack."""
    cfg, _, att, (q, latent_self, cache) = served_operands(
        h, rkv, two_leaves, 1024, seed=1)
    pos = jnp.asarray([0, 1023, 512, 513], jnp.int32)
    loop = [jax.jit(lambda *a, i=i: mla.mla_absorbed(*a, att, cfg, layer=i))(
        q, latent_self, cache, pos) for i in range(3)]
    assert loops(lambda *a: mla.mla_absorbed(*a, att, cfg, layer=1),
                 q, latent_self, cache, pos) == 1
    as_one_kernel()
    for i, want in enumerate(loop):
        np.testing.assert_array_equal(jax.jit(
            lambda *a: mla.mla_absorbed(*a, att, cfg, layer=i))(
                q, latent_self, cache, pos), want)
    assert not np.array_equal(loop[0], loop[1])
