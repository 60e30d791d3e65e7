"""Mixture-of-Experts LM with native expert parallelism (EP).

The reference reaches expert parallelism only through vLLM engine config
(SURVEY.md §2.3 — EP delegated to external engines); here EP is a
first-class mesh axis.  GShard/Switch-style top-2 routing with static
shapes throughout:

  - routing is einsum + one_hot + cumsum (no dynamic shapes — XLA-friendly);
  - dispatched token buffers are [experts, batch, capacity, model] with the
    leading axis sharded over the ``expert`` mesh axis; the dispatch and
    combine einsums are expected to lower to ``all_to_all`` over ICI
    (asserted, never measured on a chip);
  - per-expert FFN weights are stacked [n_experts, d_model, d_ff] and
    sharded over (``expert``, -, ``model``), so EP composes with TP;
  - a Switch-style load-balancing aux loss accumulates through the
    ``lax.scan`` over layers.

Not the expert layer the LongCat family uses, and not the supported one:
it has a capacity and drops tokens, no cache, no ``ModelFamily``, and has
never run on a chip.  ``models/longcat.py`` holds the dropless layer that is
told which experts it holds.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 50304
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    dtype: str = "bfloat16"
    attention: str = "dense"
    remat: bool = False

    def __post_init__(self):
        # Routing implements top-1 and top-2 (GShard-style second expert);
        # a silently-ignored larger top_k would still inflate capacity().
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {self.top_k}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    def capacity(self, seq_len: int) -> int:
        c = int(self.top_k * seq_len * self.capacity_factor / self.n_experts)
        return max(c, 4)

    @classmethod
    def tiny(cls, **kw) -> "MoEConfig":
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("d_model", 64)
        kw.setdefault("n_experts", 4)
        return cls(**kw)


def moe_init(key, cfg: MoEConfig):
    e, h, d, L, E = (cfg.d_model, cfg.n_head, cfg.head_dim, cfg.n_layer,
                     cfg.n_experts)
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 16))
    init = lambda kk, shape, scale: (jax.random.normal(kk, shape) * scale).astype(dt)
    s = 0.02
    so = s / (2 * L) ** 0.5
    return {
        "wte": init(next(k), (cfg.vocab_size, e), s),
        "wpe": init(next(k), (cfg.max_seq, e), s),
        "blocks": {
            "ln1_g": jnp.ones((L, e), dt),
            "ln1_b": jnp.zeros((L, e), dt),
            "wqkv": init(next(k), (L, e, 3, h, d), s),
            "bqkv": jnp.zeros((L, 3, h, d), dt),
            "wo": init(next(k), (L, h, d, e), so),
            "bo": jnp.zeros((L, e), dt),
            "ln2_g": jnp.ones((L, e), dt),
            "ln2_b": jnp.zeros((L, e), dt),
            # router in f32 for stable softmax over experts
            "wg": (jax.random.normal(next(k), (L, e, E)) * s).astype(jnp.float32),
            "wi": init(next(k), (L, E, e, 4 * e), s),
            "wo2": init(next(k), (L, E, 4 * e, e), so),
        },
        "lnf_g": jnp.ones((e,), dt),
        "lnf_b": jnp.zeros((e,), dt),
    }


def moe_param_axes():
    return {
        # vocab axis unsharded — a vocab-sharded table under the token
        # gather forces SPMD full rematerialization (see gpt2.py).
        "wte": P(None, "embed"),
        "wpe": P(None, "embed"),
        "blocks": {
            "ln1_g": P(None, "norm"),
            "ln1_b": P(None, "norm"),
            "wqkv": P(None, "embed", None, "heads", "kv"),
            "bqkv": P(None, None, "heads", "kv"),
            "wo": P(None, "heads", "kv", "embed"),
            "bo": P(None, "norm"),
            "ln2_g": P(None, "norm"),
            "ln2_b": P(None, "norm"),
            "wg": P(None, "embed", None),
            "wi": P(None, "expert", "embed", "expert_mlp"),
            "wo2": P(None, "expert", "expert_mlp", "embed"),
        },
        "lnf_g": P("norm"),
        "lnf_b": P("norm"),
    }


def _layernorm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def moe_ffn(x, wg, wi, wo, cfg: MoEConfig, mesh=None):
    """Top-2 routed expert FFN.  x: [B, S, M] → (y [B, S, M], aux_loss).

    Each batch row is a routing group (GShard grouping); the capacity
    cumsum runs over the sequence axis.
    """
    from ..parallel.sharding import with_logical_constraint as wlc

    b, s_len, m = x.shape
    E, C = cfg.n_experts, cfg.capacity(s_len)

    logits = jnp.einsum("bsm,me->bse", x.astype(jnp.float32), wg)
    gates = jax.nn.softmax(logits, axis=-1)  # [B,S,E] f32

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)
    # Switch aux loss on the top-1 assignment (fraction × mean prob)
    density = mask1.mean(axis=1)            # [B,E] fraction routed to e
    prob_mean = gates.mean(axis=1)          # [B,E]
    aux = E * jnp.mean(jnp.sum(density * prob_mean, axis=-1))

    pos1 = jnp.cumsum(mask1, axis=1) - mask1      # [B,S,E] queue position
    mask1 = mask1 * (pos1 < C)

    if cfg.top_k >= 2:
        gates2 = gates * (1.0 - jax.nn.one_hot(idx1, E, dtype=jnp.float32))
        idx2 = jnp.argmax(gates2, axis=-1)
        mask2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)
        pos2 = jnp.cumsum(mask2, axis=1) - mask2 + mask1.sum(axis=1, keepdims=True)
        mask2 = mask2 * (pos2 < C)
    else:
        mask2 = jnp.zeros_like(mask1)
        pos2 = jnp.zeros_like(pos1)

    w1 = jnp.sum(gates * mask1, axis=-1)    # [B,S]
    w2 = jnp.sum(gates * mask2, axis=-1)
    denom = w1 + w2 + 1e-9
    w1, w2 = w1 / denom, w2 / denom

    onehot_c1 = jax.nn.one_hot(
        pos1.astype(jnp.int32), C, dtype=jnp.float32) * mask1[..., None]
    onehot_c2 = jax.nn.one_hot(
        pos2.astype(jnp.int32), C, dtype=jnp.float32) * mask2[..., None]
    combine = (w1[..., None, None] * onehot_c1 +
               w2[..., None, None] * onehot_c2)   # [B,S,E,C]
    dispatch = (onehot_c1 + onehot_c2).astype(x.dtype)

    # [B,S,E,C] × [B,S,M] → [E,B,C,M]: lowers to all_to_all (batch-sharded
    # tokens → expert-sharded buffers) when both shardings are annotated.
    xe = jnp.einsum("bsec,bsm->ebcm", dispatch, x)
    xe = wlc(xe, P("expert", "batch", "capacity", None), mesh)
    h = jax.nn.gelu(jnp.einsum("ebcm,emh->ebch", xe, wi))
    h = wlc(h, P("expert", "batch", "capacity", "expert_mlp"), mesh)
    ye = jnp.einsum("ebch,ehm->ebcm", h, wo)
    ye = wlc(ye, P("expert", "batch", "capacity", None), mesh)
    y = jnp.einsum("bsec,ebcm->bsm", combine.astype(ye.dtype), ye)
    return y.astype(x.dtype), aux


def _attention(q, k, v, cfg: MoEConfig, mesh):
    if cfg.attention == "flash":
        from ..ops.attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    from ..ops.attention import reference_attention

    return reference_attention(q, k, v, causal=True)


def _block(x, layer, cfg: MoEConfig, mesh):
    from ..parallel.sharding import with_logical_constraint as wlc

    y = _layernorm(x, layer["ln1_g"], layer["ln1_b"])
    qkv = jnp.einsum("bse,ethd->bsthd", y, layer["wqkv"]) + layer["bqkv"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    o = _attention(q, k, v, cfg, mesh)
    x = x + (jnp.einsum("bshd,hde->bse", o, layer["wo"]) + layer["bo"]).astype(x.dtype)
    y = _layernorm(x, layer["ln2_g"], layer["ln2_b"])
    ffn, aux = moe_ffn(y, layer["wg"], layer["wi"], layer["wo2"], cfg, mesh)
    x = x + ffn
    return wlc(x, P("batch", "seq", "act_embed"), mesh), aux


def moe_apply(params, tokens, cfg: MoEConfig, mesh=None):
    """tokens: [B, S] int32 → (logits [B, S, V], aux_loss)."""
    from ..parallel.sharding import with_logical_constraint as wlc

    b, s = tokens.shape
    # Replicated-view gather — see gpt2.gpt2_apply for the SPMD rationale.
    wte = wlc(params["wte"], P(None, "act_embed"), mesh)
    x = wte[tokens] + params["wpe"][:s][None]
    x = wlc(x, P("batch", "seq", "act_embed"), mesh)

    block = functools.partial(_block, cfg=cfg, mesh=mesh)
    if cfg.remat:
        block = jax.checkpoint(block)

    def scan_body(x, layer):
        x, aux = block(x, layer)
        return x, aux

    x, auxes = jax.lax.scan(scan_body, x, params["blocks"])
    x = _layernorm(x, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("bse,ve->bsv", x, params["wte"])
    return wlc(logits, P("batch", "seq", "vocab"), mesh), jnp.mean(auxes)


def moe_loss(params, tokens, cfg: MoEConfig, mesh=None):
    """Next-token cross-entropy + aux load-balance loss."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = moe_apply(params, inputs, cfg, mesh)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean() + cfg.aux_loss_coef * aux
