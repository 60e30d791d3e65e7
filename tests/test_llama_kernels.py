"""Llama model family + decode-attention kernel tests (CPU via pallas
interpret mode, following tests/test_models.py conventions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_apply,
    llama_init,
    llama_loss,
    llama_param_axes,
    rope,
)
from ray_tpu.ops.decode_attention import (
    decode_attention,
    reference_decode_attention,
)


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


class TestDecodeAttention:
    def _data(self, b=3, t=64, h=4, hkv=None, d=16, layers=2,
              dtype=jnp.float32):
        hkv = hkv if hkv is not None else h
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        q = jax.random.normal(keys[0], (b, h, d), dtype)
        k = jax.random.normal(keys[1], (layers, b, hkv, t, d), dtype)
        v = jax.random.normal(keys[2], (layers, b, hkv, t, d), dtype)
        ks = jax.random.normal(keys[3], (b, hkv, d), dtype)
        vs = jax.random.normal(keys[4], (b, hkv, d), dtype)
        pos = jnp.array([5, 31, 63], jnp.int32)[:b]
        return q, k, v, ks, vs, pos

    def test_kernel_matches_reference(self):
        q, k, v, ks, vs, pos = self._data()
        for layer in (0, 1):
            ref = reference_decode_attention(q, k, v, pos, layer, ks, vs)
            out = decode_attention(
                q, k, v, pos, layer, k_self=ks, v_self=vs, block_t=16,
                kernel=True, interpret=True,
            )
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_gqa_grouped_heads(self):
        q, k, v, ks, vs, pos = self._data(h=4, hkv=2)
        ref = reference_decode_attention(q, k, v, pos, 0, ks, vs)
        out = decode_attention(
            q, k, v, pos, 0, k_self=ks, v_self=vs, block_t=16, kernel=True,
            interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_self_vs_prewritten_cache_agree(self):
        """Deferred-scatter form == attending a cache with the current
        token already written at pos."""
        q, k, v, ks, vs, pos = self._data(b=3)
        bidx = jnp.arange(3)
        k_written = k.at[0, bidx, :, pos].set(ks)
        v_written = v.at[0, bidx, :, pos].set(vs)
        a = reference_decode_attention(q, k_written, v_written, pos, 0)
        b_ = reference_decode_attention(q, k, v, pos, 0, ks, vs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)

    def test_ragged_positions_masked(self):
        """Cache entries at or past pos must not affect the output."""
        q, k, v, ks, vs, _ = self._data()
        pos = jnp.array([5, 20, 39])
        k_poisoned = k.at[:, :, :, 39:].set(1e4)
        v_poisoned = v.at[:, :, :, 39:].set(1e4)
        out_a = decode_attention(
            q, k, v, pos, 0, k_self=ks, v_self=vs, block_t=16,
            interpret=True,
        )
        out_b = decode_attention(
            q, k_poisoned, v_poisoned, pos, 0, k_self=ks, v_self=vs,
            block_t=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                                   atol=1e-5)

    def test_pos_zero_attends_only_self(self):
        """Empty prefix: output is exactly v_self per head group."""
        q, k, v, ks, vs, _ = self._data(b=3)
        pos = jnp.zeros((3,), jnp.int32)
        out = decode_attention(
            q, k, v, pos, 0, k_self=ks, v_self=vs, block_t=16,
            interpret=True,
        )
        expect = jnp.broadcast_to(
            vs[:, :, None, :], (3, 4, 1, 16)
        ).reshape(3, 4, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-5)

    def test_bf16_inputs(self):
        q, k, v, ks, vs, pos = self._data(dtype=jnp.bfloat16)
        ref = reference_decode_attention(q, k, v, pos, 0, ks, vs)
        out = decode_attention(
            q, k, v, pos, 0, k_self=ks, v_self=vs, block_t=32, kernel=True,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2,
        )

    def test_non_divisible_t_raises(self):
        """A caller who asks for the kernel and cannot have it gets an
        error, not the reference in silence; kernel=False is the explicit
        way to the reference."""
        q, k, v, ks, vs, pos = self._data(t=60)
        with pytest.raises(ValueError, match="multiple of block_t"):
            decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs,
                             block_t=16, kernel=True, interpret=True)
        ref = reference_decode_attention(q, k, v, pos, 0, ks, vs)
        out = decode_attention(q, k, v, pos, 0, k_self=ks, v_self=vs,
                               block_t=16, kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
