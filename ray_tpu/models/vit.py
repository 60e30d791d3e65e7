"""ViT (Vision Transformer) — BASELINE.md north-star config #5:
ViT-B/16 batch inference on TPU-chip Serve replicas.

TPU-first: patchify is a single reshape+matmul (keeps the MXU busy instead
of an im2col conv), the encoder stack is ``lax.scan`` over stacked layer
params (one compile for any depth), attention is pluggable through
``ray_tpu.ops.attention``, and params carry logical sharding axes so the
same model runs replicated (Serve replicas) or TP/FSDP-sharded (Train).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import layernorm


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    mlp_dim: int = 3072
    num_classes: int = 1000
    dtype: str = "bfloat16"
    attention: str = "dense"  # dense | flash

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @classmethod
    def b16(cls, **kw) -> "ViTConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "ViTConfig":
        kw.setdefault("image_size", 32)
        kw.setdefault("patch_size", 8)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("d_model", 64)
        kw.setdefault("mlp_dim", 128)
        kw.setdefault("num_classes", 10)
        return cls(**kw)


def vit_init(key, cfg: ViTConfig):
    e, h, d, L = cfg.d_model, cfg.n_head, cfg.head_dim, cfg.n_layer
    patch_dim = cfg.patch_size * cfg.patch_size * 3
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 16))
    init = lambda kk, shape, scale: (jax.random.normal(kk, shape) * scale).astype(dt)
    s = 0.02
    return {
        "patch_w": init(next(k), (patch_dim, e), (1.0 / patch_dim) ** 0.5),
        "patch_b": jnp.zeros((e,), dt),
        "cls": jnp.zeros((1, 1, e), dt),
        "pos": init(next(k), (cfg.n_patches + 1, e), s),
        "blocks": {
            "ln1_g": jnp.ones((L, e), dt),
            "ln1_b": jnp.zeros((L, e), dt),
            "wqkv": init(next(k), (L, e, 3, h, d), s),
            "bqkv": jnp.zeros((L, 3, h, d), dt),
            "wo": init(next(k), (L, h, d, e), s),
            "bo": jnp.zeros((L, e), dt),
            "ln2_g": jnp.ones((L, e), dt),
            "ln2_b": jnp.zeros((L, e), dt),
            "wi": init(next(k), (L, e, cfg.mlp_dim), s),
            "bi": jnp.zeros((L, cfg.mlp_dim), dt),
            "wo2": init(next(k), (L, cfg.mlp_dim, e), s),
            "bo2": jnp.zeros((L, e), dt),
        },
        "lnf_g": jnp.ones((e,), dt),
        "lnf_b": jnp.zeros((e,), dt),
        "head_w": init(next(k), (e, cfg.num_classes), (1.0 / e) ** 0.5),
        "head_b": jnp.zeros((cfg.num_classes,), dt),
    }


def vit_param_axes():
    return {
        "patch_w": P(None, "embed"),
        "patch_b": P("norm"),
        "cls": P(None, None, "norm"),
        "pos": P(None, "embed"),
        "blocks": {
            "ln1_g": P(None, "norm"),
            "ln1_b": P(None, "norm"),
            "wqkv": P(None, "embed", None, "heads", "kv"),
            "bqkv": P(None, None, "heads", "kv"),
            "wo": P(None, "heads", "kv", "embed"),
            "bo": P(None, "norm"),
            "ln2_g": P(None, "norm"),
            "ln2_b": P(None, "norm"),
            "wi": P(None, "embed", "mlp"),
            "bi": P(None, "mlp"),
            "wo2": P(None, "mlp", "embed"),
            "bo2": P(None, "norm"),
        },
        "lnf_g": P("norm"),
        "lnf_b": P("norm"),
        "head_w": P("embed", None),
        "head_b": P(None),
    }


def _encoder_block(x, layer, cfg: ViTConfig, mesh):
    from ..parallel.sharding import with_logical_constraint as wlc

    y = layernorm(x, layer["ln1_g"], layer["ln1_b"], eps=1e-6)
    qkv = jnp.einsum("bse,ethd->bsthd", y, layer["wqkv"]) + layer["bqkv"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if cfg.attention == "flash":
        from ..ops.attention import flash_attention

        o = flash_attention(q, k, v, causal=False)
    else:
        from ..ops.attention import reference_attention

        o = reference_attention(q, k, v, causal=False)
    x = x + (jnp.einsum("bshd,hde->bse", o, layer["wo"]) + layer["bo"]).astype(x.dtype)
    y = layernorm(x, layer["ln2_g"], layer["ln2_b"], eps=1e-6)
    hdn = jax.nn.gelu(jnp.einsum("bse,ef->bsf", y, layer["wi"]) + layer["bi"])
    hdn = wlc(hdn, P("batch", "seq", "mlp"), mesh)
    x = x + (jnp.einsum("bsf,fe->bse", hdn, layer["wo2"]) + layer["bo2"]).astype(x.dtype)
    return wlc(x, P("batch", "seq", "act_embed"), mesh)


def patchify(images, cfg: ViTConfig):
    """[B, H, W, 3] → [B, n_patches, patch_dim] by pure reshape/transpose."""
    b, hh, ww, c = images.shape
    p = cfg.patch_size
    x = images.reshape(b, hh // p, p, ww // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def vit_apply(params, images, cfg: ViTConfig, mesh=None):
    """images: [B, H, W, 3] → logits [B, num_classes]."""
    from ..parallel.sharding import with_logical_constraint as wlc

    dt = jnp.dtype(cfg.dtype)
    x = patchify(images.astype(dt), cfg) @ params["patch_w"] + params["patch_b"]
    cls = jnp.broadcast_to(params["cls"], (x.shape[0], 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"][None]
    x = wlc(x, P("batch", "seq", "act_embed"), mesh)

    block = functools.partial(_encoder_block, cfg=cfg, mesh=mesh)

    def scan_body(x, layer):
        return block(x, layer), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = layernorm(x[:, 0], params["lnf_g"], params["lnf_b"], eps=1e-6)
    logits = x.astype(jnp.float32) @ params["head_w"].astype(jnp.float32) + \
        params["head_b"].astype(jnp.float32)
    return wlc(logits, P("batch", None), mesh)


def vit_loss(params, images, labels, cfg: ViTConfig, mesh=None):
    logits = vit_apply(params, images, cfg, mesh)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
