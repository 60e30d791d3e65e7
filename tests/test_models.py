"""Model tests: GPT-2 forward/loss/grad under DP/FSDP/TP/SP shardings on the
8-device CPU mesh; MLP smoke."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    GPT2Config,
    LlamaConfig,
    LongcatConfig,
    gpt2_apply,
    gpt2_init,
    gpt2_loss,
    gpt2_param_axes,
    mlp_apply,
    mlp_init,
    model_family,
)
from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree


def _tokens(b=2, s=32, vocab=512, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, vocab)


class TestMLP:
    def test_forward_and_grad(self):
        params = mlp_init(jax.random.PRNGKey(0), [8, 16, 4])
        x = jnp.ones((3, 8))
        y = mlp_apply(params, x)
        assert y.shape == (3, 4)
        g = jax.grad(lambda p: mlp_apply(p, x).sum())(params)
        assert g[0]["w"].shape == (8, 16)


class TestGPT2:
    def test_forward_shapes(self):
        cfg = GPT2Config.tiny()
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        toks = _tokens(2, 16, cfg.vocab_size)
        logits = gpt2_apply(params, toks, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)

    def test_loss_decreases_with_sgd(self):
        cfg = GPT2Config.tiny(dtype="float32")
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        toks = _tokens(2, 17, cfg.vocab_size)

        loss_fn = jax.jit(lambda p: gpt2_loss(p, toks, cfg))
        grad_fn = jax.jit(jax.grad(lambda p: gpt2_loss(p, toks, cfg)))
        l0 = float(loss_fn(params))
        for _ in range(5):
            g = grad_fn(params)
            params = jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g)
        l1 = float(loss_fn(params))
        assert l1 < l0

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        cfg = GPT2Config.tiny(dtype="float32")
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        toks = np.asarray(_tokens(1, 16, cfg.vocab_size))
        logits_a = np.asarray(gpt2_apply(params, jnp.asarray(toks), cfg))
        toks_b = toks.copy()
        toks_b[0, -1] = (toks_b[0, -1] + 7) % cfg.vocab_size
        logits_b = np.asarray(gpt2_apply(params, jnp.asarray(toks_b), cfg))
        np.testing.assert_allclose(
            logits_a[0, :-1], logits_b[0, :-1], rtol=1e-5, atol=1e-5
        )

    @pytest.mark.parametrize("mesh_kw,attention", [
        (dict(fsdp=4, model=2), "dense"),
        (dict(data=2, seq=4), "ring"),
        (dict(data=2, seq=4), "ulysses"),
    ])
    def test_sharded_matches_single_device(self, mesh_kw, attention):
        cfg_ref = GPT2Config.tiny(dtype="float32")
        cfg = GPT2Config.tiny(dtype="float32", attention=attention)
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        toks = _tokens(4, 32, cfg.vocab_size)
        ref = gpt2_apply(params, toks, cfg_ref)

        mesh = build_mesh(MeshConfig(**mesh_kw))
        sharded = shard_pytree(params, gpt2_param_axes(), mesh)
        out = jax.jit(
            lambda p, t: gpt2_apply(p, t, cfg, mesh)
        )(sharded, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=5e-3, atol=5e-3
        )

    def test_remat_matches(self):
        cfg = GPT2Config.tiny(dtype="float32")
        cfg_r = GPT2Config.tiny(dtype="float32", remat=True)
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        toks = _tokens(2, 17, cfg.vocab_size)
        g = jax.grad(lambda p: gpt2_loss(p, toks, cfg))(params)
        gr = jax.grad(lambda p: gpt2_loss(p, toks, cfg_r))(params)
        np.testing.assert_allclose(
            np.asarray(g["wte"]), np.asarray(gr["wte"]), rtol=1e-4, atol=1e-5
        )


class TestResNet:
    def test_forward_shapes_and_state(self):
        from ray_tpu.models import ResNetConfig, resnet_apply, resnet_init

        cfg = ResNetConfig.tiny(dtype="float32")
        params, state = resnet_init(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((2, 32, 32, 3))
        logits, new_state = resnet_apply(params, state, x, cfg, train=True)
        assert logits.shape == (2, cfg.num_classes)
        # running stats must move in train mode
        assert not np.allclose(
            np.asarray(new_state["stem"]["mean"]),
            np.asarray(state["stem"]["mean"]),
        )
        logits_eval, st = resnet_apply(params, state, x, cfg, train=False)
        assert logits_eval.shape == (2, cfg.num_classes)

    def test_loss_decreases(self):
        from ray_tpu.models import ResNetConfig, resnet_init, resnet_loss

        cfg = ResNetConfig.tiny(dtype="float32")
        params, state = resnet_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
        y = jnp.array([0, 1, 2, 3])

        @jax.jit
        def step(params, state):
            (loss, new_state), grads = jax.value_and_grad(
                resnet_loss, has_aux=True
            )(params, state, x, y, cfg)
            params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
            return params, new_state, loss

        losses = []
        for _ in range(5):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_resnet50_geometry(self):
        from ray_tpu.models import ResNetConfig, resnet_init

        cfg = ResNetConfig.resnet50()
        params, _ = resnet_init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert 2.0e7 < n < 3.0e7  # ~25.6M params

    def test_data_parallel_matches(self):
        from ray_tpu.models import ResNetConfig, resnet_apply, resnet_init

        cfg = ResNetConfig.tiny(dtype="float32")
        params, state = resnet_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        ref, _ = resnet_apply(params, state, x, cfg)
        mesh = build_mesh(MeshConfig(data=8))
        out, _ = jax.jit(
            lambda p, s, xx: resnet_apply(p, s, xx, cfg, mesh=mesh)
        )(params, state, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestViT:
    def test_forward_shapes(self):
        from ray_tpu.models import ViTConfig, vit_apply, vit_init

        cfg = ViTConfig.tiny(dtype="float32")
        params = vit_init(jax.random.PRNGKey(0), cfg)
        x = jnp.ones((2, cfg.image_size, cfg.image_size, 3))
        logits = vit_apply(params, x, cfg)
        assert logits.shape == (2, cfg.num_classes)

    def test_loss_decreases(self):
        from ray_tpu.models import ViTConfig, vit_init, vit_loss

        cfg = ViTConfig.tiny(dtype="float32")
        params = vit_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(
            jax.random.PRNGKey(1), (4, cfg.image_size, cfg.image_size, 3))
        y = jnp.array([0, 1, 2, 3])
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p: vit_loss(p, x, y, cfg)))
        l0 = None
        for _ in range(5):
            loss, g = grad_fn(params)
            l0 = l0 if l0 is not None else float(loss)
            params = jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)
        assert float(loss) < l0

    def test_sharded_matches_single_device(self):
        from ray_tpu.models import (
            ViTConfig, vit_apply, vit_init, vit_param_axes)

        cfg = ViTConfig.tiny(dtype="float32")
        params = vit_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(
            jax.random.PRNGKey(1), (4, cfg.image_size, cfg.image_size, 3))
        ref = vit_apply(params, x, cfg)
        mesh = build_mesh(MeshConfig(fsdp=4, model=2))
        sharded = shard_pytree(params, vit_param_axes(), mesh)
        out = jax.jit(lambda p, xx: vit_apply(p, xx, cfg, mesh))(sharded, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


class TestDecodeThroughTheCache:
    """Every family with a cache: prefill, then ragged decode steps whose
    writes cross the tiles ``write_token_to_cache`` updates, against the
    full forward over the same tokens.  Heads of 128 put positions on a
    tile's sublanes (8 rows of float32, 16 of bf16: crossed from 13, 14, 16
    and 30), every other tiny width on its 128 lanes (crossed from 118 and
    126)."""

    STARTS, STEPS = [13, 14, 16, 30, 118, 126], 20
    CONFIGS = {
        "gpt2": lambda **kw: GPT2Config.tiny(max_seq=256, **kw),
        "llama": lambda **kw: LlamaConfig.tiny(max_seq=256, **kw),
        "llama-head128": lambda **kw: LlamaConfig.tiny(
            max_seq=256, d_model=256, n_head=2, n_kv_head=1, **kw),
        "longcat": lambda **kw: LongcatConfig.tiny(**kw),
    }

    # bf16 where it changes the tile (16 sublane rows for 8); on lanes the
    # tile is 128 positions whatever the dtype.
    @pytest.mark.parametrize("family,dtype", [
        ("gpt2", "float32"), ("llama", "float32"), ("longcat", "float32"),
        ("llama-head128", "float32"), ("llama-head128", "bfloat16")])
    def test_prefill_then_ragged_decode_matches_full_forward(
        self, family, dtype
    ):
        cfg = self.CONFIGS[family](dtype=dtype)
        fam = model_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        starts = np.asarray(self.STARTS, np.int32)
        rows = np.arange(len(starts))
        toks = np.asarray(_tokens(
            len(starts), starts.max() + self.STEPS, cfg.vocab_size, seed=3))
        full = np.asarray(jax.jit(
            lambda p, t: fam.apply(p, t, cfg))(params, toks), np.float32)

        prompts = np.where(
            np.arange(starts.max())[None] < starts[:, None],
            toks[:, :starts.max()], 0)
        cache = fam.init_cache(cfg, len(starts), 256)
        logits, cache = jax.jit(
            lambda p, t, n, c: fam.prefill(p, t, n, c, cfg)
        )(params, prompts, starts, cache)
        decode = jax.jit(
            lambda p, t, pos, c: fam.decode_step(p, t, pos, c, cfg),
            donate_argnums=(3,))
        got, want = [np.asarray(logits)], [full[rows, starts - 1]]
        for i in range(self.STEPS):
            pos = starts + i
            logits, cache = decode(params, toks[rows, pos], pos, cache)
            got.append(np.asarray(logits))
            want.append(full[rows, pos])
        got, want = np.stack(got, 1), np.stack(want, 1)  # [B, 1 + steps, V]
        if dtype == "float32":
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        else:
            # the benchmark's measure and limit (PERF.md section 2): root
            # mean square over the vocabulary / the logits' spread <= 3 %
            rms = np.sqrt(((got - want) ** 2).mean(-1)) / want.std(-1)
            assert rms.max() < 0.03, rms

