"""LongCat-Flash-style decoder: latent attention (MLA), a shortcut-connected
expert layer (ScMoE) with zero-compute experts, and an expert layer that is
told which experts it holds.

Source of the sizes: ``huggingface.co/meituan-longcat/LongCat-Flash-Chat``
``config.json``.  Symbols: ``d`` d_model, ``H`` heads, ``dn``/``dr`` the
no-position / rotary parts of a query-key head, ``dv`` the value head,
``rq``/``rkv`` the query / key-value latent ranks, ``F`` the dense width,
``Fe`` the expert width, ``E`` routed experts, ``Z`` zero-compute (identity)
experts, ``k`` experts a token, ``s`` the routed scaling factor.  No bias, no
YaRN.  One *layer* here is the model's double layer: two attentions, two
dense FFNs, one expert layer.

**MLA(x, pos)**: ``cq = RMSNorm(x Wqa)``; ``q = aq * (cq Wqb)`` as
``[T, H, dn+dr]``, split ``qn | qr``; ``x Wkva`` split ``[T, rkv] | [T, dr]``
-> ``ckv = akv * RMSNorm(.)``, ``kr = RoPE(., pos)`` (one rotary key for all
heads); ``qr = RoPE(qr, pos)``; ``ckv Wkvb`` as ``[T, H, dn+dv]``, split
``kn | v``; ``score_h = (qn_h . kn_h + qr_h . kr) / sqrt(dn+dr)``, causal
softmax in float32, ``o_h = P v_h``, output ``concat(o_h) Wo``.
``aq = sqrt(d/rq)``, ``akv = sqrt(d/rkv)``.  **The cache holds
``[ckv | kr]``**: ``rkv + dr`` values a token an attention, after norm, scale
and rope.  Decode uses the absorbed form (``longcat_decode.py``):
``qt_h = qn_h Wkvb_K,h^T`` in ``R^rkv``, ``score = (qt_h . ckv + qr_h . kr) /
sqrt(dn+dr)``, ``o_h = (P ckv) Wkvb_V,h``: the same mathematics, and no
per-head key or value is ever stored.

**MoE(u)**: ``p = softmax(float32(u) Wr)`` over all ``E+Z``; ``sel`` = the
``k`` largest of ``p + bias``; ``w_i = s * p_i`` for ``i`` in ``sel``, not
renormalised; ``y = sum_{i in sel, i < E} w_i SwiGLU_i(u) + sum_{i in sel,
i >= E} w_i u``.  No capacity, no drop: a token's result never depends on the
other rows.  **The share**: the layer holds ``experts_held`` experts from
``expert_offset`` (``params["experts"]``, its own subtree because it is the
part of a layer that is divided over expert-parallel chips); it routes over
all ``E+Z``, computes ``sum_{i in sel, offset <= i < offset+held} w_i
SwiGLU_i(u)`` and the identity term (which belongs to the chip where the
token lives).  What absent experts would add is left out.

**Layer (ScMoE)**: ``a0 = h + MLA0(RMSNorm(h))``; ``u0 = RMSNorm(a0)``;
``m = MoE(u0)``; ``b0 = a0 + FFN0(u0)``; ``a1 = b0 + MLA1(RMSNorm(b0))``;
``u1 = RMSNorm(a1)``; ``out = a1 + FFN1(u1) + m``: the expert layer's output
skips the second half, so a layer is not a chain of blocks.
``FFN(u) = (silu(u Wg) * (u Wu)) Wd``.  Then a final RMSNorm and an untied
head.  Rope rotates interleaved pairs as ``layers.rope`` does (a fixed
permutation of the published layout).

Device operations carry ``jax.named_scope``s ``longcat.embed`` (the token
gather, and the positions and masks a program makes once from its inputs),
``longcat.mla`` (norm to residual, and the cache write), ``longcat.moe``
(``u0``'s norm, router, held and identity experts, counts), ``longcat.ffn``
(a dense half with its norm and residual; the second takes up ``m``) and
``longcat.head`` (final norm + vocabulary product).  Routing is counted in the program:
``routed_total`` (choices made by live tokens), ``routed_zero`` (those that
fell on identity experts), ``routed_held`` (on experts held here) and
``experts_touched`` (distinct held experts a layer ran, summed over layers).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .expert_share import (EXPERT_CHUNK, LOOP_COUNT_NAMES,  # noqa: F401
                           held_choices, held_experts, loop_counts)
from .layers import add_counts, ffn, matmul, rmsnorm
from .mla import mla_expanded, mla_project

ATTENTION = ("wq_a", "rms_q", "wq_b", "wkv_a", "rms_kv", "wkv_b", "wo")
COUNT_NAMES = ("routed_total", "routed_zero", "routed_held", "experts_touched",
               *LOOP_COUNT_NAMES)


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    n_layer: int = 28  # double layers
    n_head: int = 64
    d_model: int = 6144
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288
    d_expert: int = 2048
    n_routed_experts: int = 512  # the router's width, whatever is held
    experts_held: int = 512
    expert_offset: int = 0
    zero_expert_num: int = 256
    top_k: int = 12
    routed_scaling_factor: float = 6.0
    rope_theta: float = 1e7
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")

    @property
    def latent_dim(self) -> int:
        """Values the cache holds a token an attention: ``[ckv | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_router(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @classmethod
    def tiny(cls, **kw) -> "LongcatConfig":
        for key, value in dict(
                vocab_size=512, n_layer=2, n_head=4, d_model=64,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, d_ff=128, d_expert=32,
                n_routed_experts=8, experts_held=8, zero_expert_num=4,
                top_k=3).items():
            kw.setdefault(key, value)
        return cls(**kw)


def longcat_init(key, cfg: LongcatConfig):
    d, L, H = cfg.d_model, cfg.n_layer, cfg.n_head
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 16))
    s, so = 0.02, 0.02 / (4 * L) ** 0.5

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(k), shape) * scale).astype(dtype)

    return {
        "wte": init((cfg.vocab_size, d), s),
        "blocks": {
            "rms_attn": jnp.ones((L, 2, d), dt),
            "wq_a": init((L, 2, d, rq), s),
            "rms_q": jnp.ones((L, 2, rq), dt),
            "wq_b": init((L, 2, rq, H, dn + dr), s),
            "wkv_a": init((L, 2, d, rkv + dr), s),
            "rms_kv": jnp.ones((L, 2, rkv), dt),
            "wkv_b": init((L, 2, rkv, H, dn + dv), s),
            "wo": init((L, 2, H, dv, d), so),
            "rms_ffn": jnp.ones((L, 2, d), dt),
            "w_gate": init((L, 2, d, cfg.d_ff), s),
            "w_up": init((L, 2, d, cfg.d_ff), s),
            "w_down": init((L, 2, cfg.d_ff, d), so),
            # Router and its load-balancing bias stay float32.
            "router": init((L, d, cfg.n_router), s, jnp.float32),
            "router_bias": jnp.zeros((L, cfg.n_router), jnp.float32),
        },
        "experts": {
            "w_gate": init((L, cfg.experts_held, d, cfg.d_expert), s),
            "w_up": init((L, cfg.experts_held, d, cfg.d_expert), s),
            "w_down": init((L, cfg.experts_held, cfg.d_expert, d), so),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), s),
    }


def longcat_param_axes():
    """Logical sharding axes (leading None = layer-stack axis, then the
    sub-layer axis of the two halves)."""
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "rms_attn": P(None, None, "norm"),
            "wq_a": P(None, None, "embed", None),
            "rms_q": P(None, None, "norm"),
            "wq_b": P(None, None, None, "heads", "kv"),
            "wkv_a": P(None, None, "embed", None),
            "rms_kv": P(None, None, "norm"),
            "wkv_b": P(None, None, None, "heads", "kv"),
            "wo": P(None, None, "heads", "kv", "embed"),
            "rms_ffn": P(None, None, "norm"),
            "w_gate": P(None, None, "embed", "mlp"),
            "w_up": P(None, None, "embed", "mlp"),
            "w_down": P(None, None, "mlp", "embed"),
            "router": P(None, "embed", None),
            "router_bias": P(None, None),
        },
        "experts": {
            "w_gate": P(None, "expert", "embed", "mlp"),
            "w_up": P(None, "expert", "embed", "mlp"),
            "w_down": P(None, "expert", "mlp", "embed"),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


def route(u, router, bias, cfg: LongcatConfig):
    """u ``[N, d]`` float32 -> the ``k`` experts each token chose ``[N, k]`` and
    their combine weights ``s * p`` (float32, not renormalised)."""
    logits = jnp.dot(u, router, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, sel = jax.lax.top_k(p + bias, cfg.top_k)
    w = cfg.routed_scaling_factor * jnp.take_along_axis(p, sel, axis=-1)
    return sel, w


def moe(u, live, router, bias, experts, layer: int, cfg: LongcatConfig):
    """The expert layer's share on this chip.  ``u [N, d]`` normed tokens
    in float32 (the router and the identity experts read them as they are;
    the held experts read them in ``cfg.dtype``), ``live [N]`` bool (a
    padded or idle row chooses nothing here: it touches no expert and is not
    counted), the layer's ``router`` and ``bias``, ``experts`` the
    layer-stacked expert subtree -> ``([N, d] float32, counts)``."""
    with jax.named_scope("longcat.moe"):
        sel, w = route(u, router, bias, cfg)
        w = jnp.where(live[:, None], w, 0.0)
        zero = sel >= cfg.n_routed_experts
        held, hit, w_held = held_choices(
            sel, w, live, cfg.expert_offset, cfg.experts_held)

        def swiglu(x, e):  # [layer, e] inside the loop: expert_share.py
            return ffn(x, experts["w_gate"][layer, e],
                       experts["w_up"][layer, e], experts["w_down"][layer, e])

        y = held_experts(u.astype(jnp.dtype(cfg.dtype)), hit, w_held, swiglu)
        # Identity experts: one multiply-add, no weights.
        w_zero = (w * zero).sum(-1, keepdims=True)
        y = y + w_zero * u
        return y, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_zero": (zero & live[:, None]).sum(),
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit),
        }


def double_layer(h, params, layer: int, live, attend, cfg: LongcatConfig):
    """ScMoE double layer ``layer``.  ``h [..., d]`` float32: the residual
    stream is kept in float32 (its eight additions a layer would each round
    it to bf16 otherwise; the sub-layers read and write ``cfg.dtype``);
    ``attend(att, y)`` is MLA of the normed state with one attention's
    weights (expanded or absorbed, the caller's choice); ``live`` has
    ``h``'s leading shape.  Every weight is taken as ``stack[layer, j]`` in
    one step, where it is used: a layer's slice taken first and indexed
    later is a copy of the layer (the compiler fuses only the direct form
    into the matrix product that reads it)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)

    def norm(v, name, j):  # the stream is float32; matrices read cfg.dtype
        return rmsnorm(v, blocks[name][layer, j], cfg.rms_eps).astype(dt)

    def dense(u, j):
        return ffn(u, blocks["w_gate"][layer, j], blocks["w_up"][layer, j],
                   blocks["w_down"][layer, j])

    def attention(j, y):
        return attend({k: blocks[k][layer, j] for k in ATTENTION}, y)

    # Each part's scope holds its norm and the addition that takes it up.
    with jax.named_scope("longcat.mla"):
        a0 = h + attention(0, norm(h, "rms_attn", 0))
    with jax.named_scope("longcat.moe"):  # u0 feeds the dense half too
        u0 = rmsnorm(a0, blocks["rms_ffn"][layer, 0], cfg.rms_eps)  # float32
        flat, live = u0.reshape(-1, u0.shape[-1]), live.reshape(-1)
        router, bias = blocks["router"][layer], blocks["router_bias"][layer]
    m, counts = moe(flat, live, router, bias, params["experts"], layer, cfg)
    with jax.named_scope("longcat.ffn"):
        b0 = a0 + dense(u0.astype(dt), 0)
    with jax.named_scope("longcat.mla"):
        a1 = b0 + attention(1, norm(b0, "rms_attn", 1))
    with jax.named_scope("longcat.ffn"):  # and the expert layer's sum
        u1 = norm(a1, "rms_ffn", 1)
        return a1 + dense(u1, 1) + m.reshape(h.shape), counts


def longcat_forward(params, tokens, live, cfg: LongcatConfig):
    """tokens ``[B, S]`` -> (final normed state ``[B, S, d]``, the latents of
    every attention ``[2L, B, S, rkv+dr]``, routing counts)."""
    with jax.named_scope("longcat.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    latents, total = [], None

    def attend(att, y):
        q, latent = mla_project(y, att, positions, cfg)
        latents.append(latent)
        return mla_expanded(q, latent, att, cfg)

    for layer in range(cfg.n_layer):
        x, counts = double_layer(x, params, layer, live, attend, cfg)
        with jax.named_scope("longcat.moe"):
            total = add_counts(total, counts)
    with jax.named_scope("longcat.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    with jax.named_scope("longcat.mla"):
        latents = jnp.stack(latents)
    return x, latents, total


def longcat_apply(params, tokens, cfg: LongcatConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "longcat runs one chip's share of a layer; no mesh yet")
    x, _, _ = longcat_forward(params, tokens, jnp.ones(tokens.shape, bool),
                              cfg)
    with jax.named_scope("longcat.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def longcat_loss(params, tokens, cfg: LongcatConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = longcat_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
