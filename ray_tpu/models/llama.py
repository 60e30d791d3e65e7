"""Llama-family decoder LM: RMSNorm + RoPE + GQA + SwiGLU.

The reference serves Llama-class models by delegating to vLLM
(ray ``python/ray/llm/_internal/serve/engines/vllm/vllm_models.py``); here
the architecture is native JAX with the same TPU-first structure as
``gpt2.py``: layer-stacked params applied under ``lax.scan``, logical
sharding axes for DP/FSDP/TP/SP, pluggable attention (dense/flash/ring/
ulysses), optional per-layer remat, bf16 with f32 norm/softmax.

Grouped-query attention shards cleanly on the ``heads`` axis: KV heads are
replicated within a query-head group, so TP on query heads keeps KV local
to the shard (no extra collectives versus MHA).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq: int = 2048
    n_layer: int = 22
    n_head: int = 32
    n_kv_head: int = 8  # GQA: query heads per kv head = n_head // n_kv_head
    d_model: int = 2048
    d_ff: int = 5632
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("n_kv_head", 2)
        kw.setdefault("d_model", 64)
        kw.setdefault("d_ff", 128)
        return cls(**kw)

    @classmethod
    def tinyllama_1b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)  # defaults above are the 1.1B shape

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        kw.setdefault("n_layer", 32)
        kw.setdefault("n_head", 32)
        kw.setdefault("n_kv_head", 32)
        kw.setdefault("d_model", 4096)
        kw.setdefault("d_ff", 11008)
        kw.setdefault("max_seq", 4096)
        return cls(**kw)


def llama_init(key, cfg: LlamaConfig):
    e, hd = cfg.d_model, cfg.head_dim
    L, H, KV, F = cfg.n_layer, cfg.n_head, cfg.n_kv_head, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 12))
    init = lambda kk, shape, scale: (
        jax.random.normal(kk, shape) * scale
    ).astype(dt)
    s = 0.02
    so = s / (2 * L) ** 0.5
    return {
        "wte": init(next(k), (cfg.vocab_size, e), s),
        "blocks": {
            "rms1": jnp.ones((L, e), dt),
            "wq": init(next(k), (L, e, H, hd), s),
            "wk": init(next(k), (L, e, KV, hd), s),
            "wv": init(next(k), (L, e, KV, hd), s),
            "wo": init(next(k), (L, H, hd, e), so),
            "rms2": jnp.ones((L, e), dt),
            "w_gate": init(next(k), (L, e, F), s),
            "w_up": init(next(k), (L, e, F), s),
            "w_down": init(next(k), (L, F, e), so),
        },
        "rms_f": jnp.ones((e,), dt),
        "lm_head": init(next(k), (cfg.vocab_size, e), s),
    }


def llama_param_axes():
    """Logical sharding axes (leading None = layer-stack axis)."""
    return {
        # vocab axis unsharded: the token gather along a vocab-sharded table
        # forces SPMD full rematerialization (see gpt2.py:gpt2_param_axes).
        # lm_head keeps its vocab sharding — it is only ever contracted over
        # embed, producing vocab-sharded logits with no gather.
        "wte": P(None, "embed"),
        "blocks": {
            "rms1": P(None, "norm"),
            "wq": P(None, "embed", "heads", "kv"),
            "wk": P(None, "embed", "heads", "kv"),
            "wv": P(None, "embed", "heads", "kv"),
            "wo": P(None, "heads", "kv", "embed"),
            "rms2": P(None, "norm"),
            "w_gate": P(None, "embed", "mlp"),
            "w_up": P(None, "embed", "mlp"),
            "w_down": P(None, "mlp", "embed"),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


def _attention(q, k, v, cfg: LlamaConfig, mesh):
    if cfg.attention == "flash":
        from ..ops.attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    if cfg.attention == "ring":
        from ..parallel.ring_attention import ring_attention

        assert mesh is not None, "ring attention requires a mesh"
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.attention == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        assert mesh is not None, "ulysses attention requires a mesh"
        return ulysses_attention(q, k, v, mesh, causal=True)
    from ..ops.attention import reference_attention

    return reference_attention(q, k, v, causal=True)


def _block(x, layer, positions, cfg: LlamaConfig, mesh):
    from ..parallel.sharding import with_logical_constraint as wlc

    groups = cfg.n_head // cfg.n_kv_head
    y = rmsnorm(x, layer["rms1"], cfg.rms_eps)
    q = jnp.einsum("bse,ehd->bshd", y, layer["wq"])
    k = jnp.einsum("bse,ekd->bskd", y, layer["wk"])
    v = jnp.einsum("bse,ekd->bskd", y, layer["wv"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # GQA: repeat kv heads across their query-head group.
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    q = wlc(q, P("batch", "seq", "heads", "kv"), mesh)
    k = wlc(k, P("batch", "seq", "heads", "kv"), mesh)
    v = wlc(v, P("batch", "seq", "heads", "kv"), mesh)
    o = _attention(q, k, v, cfg, mesh)
    x = x + jnp.einsum("bshd,hde->bse", o, layer["wo"]).astype(x.dtype)
    y = rmsnorm(x, layer["rms2"], cfg.rms_eps)
    gate = jax.nn.silu(jnp.einsum("bse,ef->bsf", y, layer["w_gate"]))
    up = jnp.einsum("bse,ef->bsf", y, layer["w_up"])
    h = wlc(gate * up, P("batch", "seq", "mlp"), mesh)
    x = x + jnp.einsum("bsf,fe->bse", h, layer["w_down"]).astype(x.dtype)
    return wlc(x, P("batch", "seq", "act_embed"), mesh)


def llama_apply(params, tokens, cfg: LlamaConfig, mesh=None):
    """tokens: [B, S] int32 → logits [B, S, V]."""
    from ..parallel.sharding import with_logical_constraint as wlc

    b, s = tokens.shape
    # Replicated-view gather — see gpt2.gpt2_apply for the SPMD rationale.
    wte = wlc(params["wte"], P(None, "act_embed"), mesh)
    x = wte[tokens].astype(jnp.dtype(cfg.dtype))
    x = wlc(x, P("batch", "seq", "act_embed"), mesh)
    positions = jnp.arange(s, dtype=jnp.int32)

    block = functools.partial(_block, positions=positions, cfg=cfg, mesh=mesh)
    if cfg.remat:
        block = jax.checkpoint(block)

    def scan_body(x, layer):
        return block(x, layer), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = rmsnorm(x, params["rms_f"], cfg.rms_eps)
    logits = jnp.einsum("bse,ve->bsv", x, params["lm_head"])
    return wlc(logits, P("batch", "seq", "vocab"), mesh)


def llama_loss(params, tokens, cfg: LlamaConfig, mesh=None,
               z_loss: float = 0.0):
    """Next-token cross-entropy; tokens [B, S+1]."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = llama_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    if z_loss > 0:
        nll = nll + z_loss * (logz ** 2).mean()
    return nll
