"""Llama model family + decode attention against a dense float64 oracle
(CPU, following tests/test_models.py conventions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.layers import rmsnorm, rope
from ray_tpu.models.llama import (
    LlamaConfig,
    llama_apply,
    llama_init,
    llama_loss,
    llama_param_axes,
)
from ray_tpu.models.llama_decode import llama_init_cache, llama_prefill
from ray_tpu.ops.decode_attention import decode_attention, extent_step


def _cfg(**kw):
    kw.setdefault("dtype", "float32")
    return LlamaConfig.tiny(**kw)


class TestLlama:
    def test_forward_shapes(self):
        cfg = _cfg()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = llama_apply(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert jnp.isfinite(logits).all()

    def test_param_axes_cover_tree(self):
        cfg = _cfg()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        axes = llama_param_axes()
        p_leaves = jax.tree.leaves(params)
        a_leaves = jax.tree.leaves(
            axes, is_leaf=lambda x: hasattr(x, "index")
        )
        assert len(p_leaves) == len(a_leaves)

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        cfg = _cfg()
        params = llama_init(jax.random.PRNGKey(1), cfg)
        t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
        t2 = t1.at[0, 6].set(9)
        l1 = llama_apply(params, t1, cfg)
        l2 = llama_apply(params, t2, cfg)
        np.testing.assert_allclose(l1[0, :6], l2[0, :6], atol=1e-5)
        assert not np.allclose(l1[0, 6], l2[0, 6])

    def test_gqa_group_count(self):
        cfg = _cfg(n_head=4, n_kv_head=2)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        assert params["blocks"]["wk"].shape == (
            cfg.n_layer, cfg.d_model, 2, cfg.head_dim
        )
        assert params["blocks"]["wq"].shape == (
            cfg.n_layer, cfg.d_model, 4, cfg.head_dim
        )

    def test_loss_and_grads(self):
        cfg = _cfg()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (2, 17), 0, cfg.vocab_size
        )
        loss, grads = jax.value_and_grad(
            lambda p: llama_loss(p, tokens, cfg)
        )(params)
        assert np.isfinite(float(loss))
        assert float(loss) > 0
        gnorm = sum(
            float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads)
        )
        assert gnorm > 0

    def test_rope_rotation_properties(self):
        # Position 0 is identity; dot products depend only on distance.
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 8))
        out0 = rope(x[:, :1], jnp.array([0]), 10000.0)
        np.testing.assert_allclose(out0, x[:, :1], atol=1e-6)
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 8))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 8))
        def dot_at(pq, pk):
            qr = rope(q, jnp.array([pq]), 10000.0)
            kr = rope(k, jnp.array([pk]), 10000.0)
            return float(jnp.sum(qr * kr))
        assert dot_at(3, 1) == pytest.approx(dot_at(7, 5), abs=1e-4)

    def test_sharded_training_step_on_mesh(self):
        from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree

        devices = jax.devices()[:8]
        mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2), devices)
        cfg = _cfg()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        params = shard_pytree(params, llama_param_axes(), mesh)
        tokens = jnp.zeros((4, 17), jnp.int32)

        @jax.jit
        def step(p, t):
            return jax.grad(lambda pp: llama_loss(pp, t, cfg, mesh))(p)

        grads = step(params, tokens)
        assert all(np.isfinite(x).all() for x in jax.tree.leaves(grads))


def dense_decode_attention(q, k, v, pos, layer, ks=None, vs=None, sink=None):
    """The oracle: each row's softmax over its cache prefix in float64,
    ``[0, pos)`` plus the token itself where its k/v ride beside the cache,
    ``[0, pos]`` where they are written in it.  Head ``h`` reads kv head
    ``h // (H // Hkv)``; ``sink`` [H] is one more logit a head whose weight
    counts in the sum and nothing else.  -> [B, H, Dv] float64."""
    f = lambda x: None if x is None else np.asarray(x, np.float64)
    q, k, v, ks, vs = f(q), f(k)[layer], f(v)[layer], f(ks), f(vs)
    (b, h, d), hkv = q.shape, k.shape[1]
    out = np.zeros((b, h, v.shape[-1]))
    for row in range(b):
        n = int(pos[row]) + (ks is None)
        for head in range(h):
            kv = head // (h // hkv)
            keys, vals = k[row, kv, :n], v[row, kv, :n]
            if ks is not None:
                keys = np.vstack([keys, ks[row, kv]])
                vals = np.vstack([vals, vs[row, kv]])
            scores = keys @ q[row, head] / np.sqrt(d)
            p = np.exp(scores - scores.max())
            drop = (0.0 if sink is None
                    else np.exp(float(sink[head]) - scores.max()))
            out[row, head] = (p / (p.sum() + drop)) @ vals
    return out


# rounding of a result of magnitude ~1, as tests/test_decode_live_extent.py
ORACLE_TOL = {"float32": 1e-5, "bfloat16": 2e-2}

# T=1536 is three extents of 512: the batch's longest context decides how
# many of them a step reads (one, two, three); the other rows are short
_THREE = dict(hkv=2, t=1536)
_FIRST, _SECOND, _THIRD = [400, 3, 17], [3, 1000, 17], [3, 17, 1535]
# (id, shape of ``_data``, pos, the token beside the cache (what the decode
# steps do) or written in it)
ORACLE_CASES = [
    ("heads-8:1-self", dict(h=8, hkv=1), [5, 31, 63], True),
    ("heads-8:1-written", dict(h=8, hkv=1), [5, 31, 63], False),
    ("heads-8:1-bf16-values-wider-1536-third-self",
     dict(h=8, hkv=1, t=1536, dv=24, dtype=jnp.bfloat16), _THIRD, True),
    ("bf16-heads-4:2-written", dict(hkv=2, dtype=jnp.bfloat16),
     [5, 31, 63], False),
    ("values-narrower-self", dict(dv=8), [0, 31, 63], True),
    ("values-wider-written", dict(hkv=2, dv=24), [5, 31, 62], False),
    ("1536-first-self", _THREE, _FIRST, True),
    ("1536-first-written", _THREE, _FIRST, False),
    ("1536-second-self", _THREE, _SECOND, True),
    ("1536-second-written", _THREE, _SECOND, False),
    ("1536-third-self", _THREE, _THIRD, True),
    ("1536-third-written", _THREE, _THIRD, False),
]


class TestDecodeAttention:
    def _data(self, b=3, t=64, h=4, hkv=None, d=16, dv=None, layers=2,
              dtype=jnp.float32):
        hkv = hkv if hkv is not None else h
        dv = dv if dv is not None else d
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        q = jax.random.normal(keys[0], (b, h, d), dtype)
        k = jax.random.normal(keys[1], (layers, b, hkv, t, d), dtype)
        v = jax.random.normal(keys[2], (layers, b, hkv, t, dv), dtype)
        ks = jax.random.normal(keys[3], (b, hkv, d), dtype)
        vs = jax.random.normal(keys[4], (b, hkv, dv), dtype)
        pos = jnp.array([5, 31, 63], jnp.int32)[:b]
        return q, k, v, ks, vs, pos

    def test_matches_the_dense_oracle(self):
        q, k, v, ks, vs, pos = self._data()
        for layer in (0, 1):
            ref = dense_decode_attention(q, k, v, pos, layer, ks, vs)
            out = decode_attention(q, k, v, pos, layer, k_self=ks, v_self=vs)
            np.testing.assert_allclose(np.asarray(out), ref,
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("shape,pos,with_self",
                             [case[1:] for case in ORACLE_CASES],
                             ids=[case[0] for case in ORACLE_CASES])
    def test_against_the_dense_oracle(self, shape, pos, with_self):
        """What the older cases leave out: eight query heads on one kv head,
        bfloat16 with the token written, values of another width than the
        keys, and a cache of three extents read up to its first, second and
        third block (``attend_live_blocks``: 1536 is no power of two, so no
        other test's cache has an odd number of blocks)."""
        q, k, v, ks, vs, _ = self._data(**shape)
        own = dict(k_self=ks, v_self=vs) if with_self else {}
        out = decode_attention(q, k, v, jnp.asarray(pos, jnp.int32), 1, **own)
        assert out.shape == (3, q.shape[1], v.shape[-1])
        assert out.dtype == v.dtype
        np.testing.assert_allclose(
            np.asarray(out, np.float64),
            dense_decode_attention(q, k, v, pos, 1, *own.values()),
            atol=ORACLE_TOL[jnp.dtype(v.dtype).name])

    def test_gqa_grouped_heads(self):
        q, k, v, ks, vs, pos = self._data(h=4, hkv=2)
        ref = dense_decode_attention(q, k, v, pos, 0, ks, vs)
        out = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   atol=1e-5, rtol=1e-5)

    def test_self_vs_prewritten_cache_agree(self):
        """Deferred-scatter form == attending a cache with the current
        token already written at pos."""
        q, k, v, ks, vs, pos = self._data(b=3)
        bidx = jnp.arange(3)
        k_written = k.at[0, bidx, :, pos].set(ks)
        v_written = v.at[0, bidx, :, pos].set(vs)
        a = decode_attention(q, k_written, v_written, pos, 0)
        b_ = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(a), dense_decode_attention(q, k, v, pos, 0, ks, vs),
            atol=1e-5)

    def test_ragged_positions_masked(self):
        """Cache entries at or past pos must not affect the output."""
        q, k, v, ks, vs, _ = self._data()
        pos = jnp.array([5, 20, 39])
        k_poisoned = k.at[:, :, :, 39:].set(1e4)
        v_poisoned = v.at[:, :, :, 39:].set(1e4)
        out_a = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs)
        out_b = decode_attention(
            q, k_poisoned, v_poisoned, pos, 0, k_self=ks, v_self=vs)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out_b),
            dense_decode_attention(q, k, v, pos, 0, ks, vs), atol=1e-5)

    def test_pos_zero_attends_only_self(self):
        """Empty prefix: output is exactly v_self per head group."""
        q, k, v, ks, vs, _ = self._data(b=3)
        pos = jnp.zeros((3,), jnp.int32)
        out = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs)
        expect = jnp.broadcast_to(
            vs[:, :, None, :], (3, 4, 1, 16)
        ).reshape(3, 4, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-5)

    def test_bf16_inputs(self):
        q, k, v, ks, vs, pos = self._data(dtype=jnp.bfloat16)
        ref = dense_decode_attention(q, k, v, pos, 0, ks, vs)
        out = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, atol=2e-2, rtol=2e-2,
        )

    def test_a_cache_no_block_divides_is_read_whole_and_right(self):
        """T=60 is no multiple of anything the op blocks by: one extent
        (``extent_step``), one softmax over all of it, the last position
        included."""
        q, k, v, ks, vs, _ = self._data(t=60)
        pos = jnp.array([5, 31, 59], jnp.int32)
        assert extent_step(60) == 60
        for own in (dict(k_self=ks, v_self=vs), {}):
            out = decode_attention(q, k, v, pos, 0, **own)
            np.testing.assert_allclose(
                np.asarray(out),
                dense_decode_attention(q, k, v, pos, 0, *own.values()),
                atol=1e-5)


def dense_prefill(params, tokens, cfg):
    """The dense form ``llama_prefill`` had, in float32: every key-value head
    repeated over its group and the whole ``[S, S]`` square scored under a
    lower-triangular mask.  tokens ``[B, S]`` -> (logits ``[B, S, V]``, keys
    and values ``[L, B, Hkv, S, D]`` as the cache holds them)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s, groups = tokens.shape[1], cfg.n_head // cfg.n_kv_head
    positions, seen = jnp.arange(s), jnp.tril(jnp.ones((s, s), bool))
    x, ks, vs = p["wte"][tokens], [], []
    for l in range(cfg.n_layer):
        w = jax.tree.map(lambda a: a[l], p["blocks"])
        y = rmsnorm(x, w["rms1"], cfg.rms_eps)
        q = rope(jnp.einsum("bse,ehd->bshd", y, w["wq"]), positions,
                 cfg.rope_theta)
        k = rope(jnp.einsum("bse,ekd->bskd", y, w["wk"]), positions,
                 cfg.rope_theta)
        v = jnp.einsum("bse,ekd->bskd", y, w["wv"])
        ks.append(k.transpose(0, 2, 1, 3))
        vs.append(v.transpose(0, 2, 1, 3))
        scores = jnp.einsum("bshd,bthd->bhst", q, jnp.repeat(
            k, groups, 2)) / cfg.head_dim ** 0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.einsum("bhst,bthd->bshd", probs, jnp.repeat(v, groups, 2))
        x = x + jnp.einsum("bshd,hde->bse", o, w["wo"])
        y = rmsnorm(x, w["rms2"], cfg.rms_eps)
        x = x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
    x = rmsnorm(x, p["rms_f"], cfg.rms_eps)
    return jnp.einsum("bse,ve->bsv", x, p["lm_head"]), jnp.stack(
        ks), jnp.stack(vs)


class TestPrefillInTiles:
    """``llama_prefill`` scores through ``layers.blocked_attention`` (tiles
    of 8 x 8 here, 512 x 512 in the programs), four query heads over two
    key-value heads that are never repeated."""

    def _run(self, cfg, lengths, s, query_block=8, key_block=8):
        params = llama_init(jax.random.PRNGKey(0), cfg)
        lengths = np.asarray(lengths, np.int32)
        tokens = np.where(
            np.arange(s)[None] < lengths[:, None], np.asarray(jax.random.randint(
                jax.random.PRNGKey(1), (len(lengths), s), 1, cfg.vocab_size)),
            0)
        logits, cache = jax.jit(lambda p, t, n, c: llama_prefill(
            p, t, n, c, cfg, query_block=query_block, key_block=key_block))(
                params, tokens, lengths, llama_init_cache(
                    cfg, len(lengths), s + 3))
        return params, tokens, lengths, np.asarray(logits), cache

    # one tile, several, and blocks that do not divide the rung
    @pytest.mark.parametrize("s,lengths", [
        (8, (8, 3)), (24, (24, 11)), (29, (29, 13))])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_logits_and_the_written_cache_are_the_dense_forms(
        self, dtype, s, lengths
    ):
        """The last prompt token's logits and the whole written rung, the
        padding rows beyond a prompt too (every query block runs, so they
        are attended as the dense form attends them), against the dense
        float32 form on the same weights: to float32's rounding, and in
        bfloat16 by the benchmark's measure (root mean square over the
        spread, 3 %)."""
        cfg = LlamaConfig.tiny(dtype=dtype)
        params, tokens, lengths, logits, cache = self._run(cfg, lengths, s)
        want, ks, vs = jax.jit(lambda p, t: dense_prefill(p, t, cfg))(
            params, tokens)
        pairs = [(logits, np.asarray(want)[np.arange(2), lengths - 1])]
        pairs += [(np.asarray(cache[leaf][:, :, :, :s], np.float32),
                   np.asarray(dense))
                  for leaf, dense in (("k", ks), ("v", vs))]
        for got, dense in pairs:
            if dtype == "float32":
                np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)
            else:
                assert np.sqrt(((got - dense) ** 2).mean()) < 0.03 * dense.std()
        assert float(jnp.abs(cache["k"][:, :, :, s:]).max()) == 0.0

    @pytest.mark.parametrize("query_block,key_block", [(16, 8), (8, 16)])
    def test_the_tiles_shape_does_not_change_the_answer(
        self, query_block, key_block
    ):
        """A rung of 29 in tiles of 16 x 8 and of 8 x 16 against tiles of
        8 x 8: the online softmax regroups float32 sums and nothing else."""
        cfg = _cfg()
        *_, logits, cache = self._run(cfg, (29, 13), 29)
        *_, other, other_cache = self._run(
            cfg, (29, 13), 29, query_block, key_block)
        np.testing.assert_allclose(other, logits, rtol=1e-5, atol=1e-5)
        for leaf in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(other_cache[leaf]), np.asarray(cache[leaf]),
                rtol=1e-5, atol=1e-5)
