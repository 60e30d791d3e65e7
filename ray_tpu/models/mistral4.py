"""Mistral-Small-4-style decoder: latent attention (MLA) published for long
contexts (YaRN-scaled rotary, a softmax scale that grows with the query's
position), and in every layer a shared expert beside routed experts of which
the layer is told which it holds.

Source of the sizes: ``huggingface.co/mistralai/Mistral-Small-4-119B-2603``
``config.json`` (``model_type`` ``mistral4``, 119B-A6.5B).  Symbols: ``d``
d_model, ``H`` heads, ``dn`` / ``dr`` the no-position / rotary parts of a
query-key head (64 / 64), ``dv`` the value head (128), ``rq`` / ``rkv`` the
query / key-value latent ranks (1024 / 256), ``Fe`` the expert width, ``E``
routed experts, ``k`` experts a token.  No bias anywhere.

**Model**: ``n_layer`` identical blocks ``x = x + MLA(RMSNorm(x)); x = x +
MoE(RMSNorm(x))`` (eps 1e-6; no leading dense layer), a final RMSNorm, an
untied head.  The residual stream is float32; matrix products read
``cfg.dtype`` and accumulate in float32, and what lies between two products
is float32, rounded once where the next product reads it
(``layers.matmul``).

**MLA(x, pos)**: ``cq = RMSNorm(x Wqa)``; ``q = cq Wqb`` as ``[T, H, dn+dr]``,
split ``qn | qr``; ``x Wkva`` split ``[T, rkv] | [T, dr]`` -> ``ckv =
RMSNorm(.)``, ``kr = RoPE_yarn(., pos)`` (one rotary key for all heads); ``qr
= RoPE_yarn(qr, pos)``; ``ckv Wkb`` ``[T, H, dn]`` = ``kn``, ``ckv Wvb`` ``[T,
H, dv]`` = ``v`` (the two halves of the published ``kv_b_proj``, kept as two
stacks so that each product reads its own where it lies); ``score_h(i, j) =
a(i) (qn_h(i) . kn_h(j) + qr_h(i) . kr(j)) (dn+dr)^-0.5 m^2``, causal softmax
in float32, ``o_h = P v_h``, output ``concat_h(o_h) Wo``.  LongCat's latent
attention without its ``aq`` / ``akv`` scales (``mla.mla_project``, which
this module calls with its own frequencies and query factor).  **The cache
holds ``[ckv | kr]``**: ``rkv + dr`` = 320 values a token a layer, after norm
and rope.  Decode absorbs (``mla.mla_absorbed``): ``qt_h = qn_h
Wkb_h^T`` in ``R^rkv``, ``score = (qt_h . ckv + qr_h . kr) (dn+dr)^-0.5``,
``o_h = (P ckv) Wvb_h``; ``a(pos) m^2`` is folded into the query before it
is rounded, in prefill and decode alike.

**RoPE_yarn** (``yarn_inv_freq``; interleaved pairs, ``layers.rope``'s layout,
which is the published ``rope_interleave``): over the ``dr/2`` pairs ``i``,
``f_i = theta^(-2i/dr)``; ``c(b) = dr ln(L0 / (2 pi b)) / (2 ln theta)`` with
``L0 = rope_original_max``; ``low = floor(c(beta_fast))``, ``high =
ceil(c(beta_slow))`` (12 and 25 at the published sizes); ``r_i = clip((i -
low) / (high - low), 0, 1)``; ``inv_freq_i = (1 - r_i) f_i + r_i f_i /
rope_factor``.  ``g(s) = 0.1 s ln(rope_factor) + 1``; cos and sin carry ``g(
mscale) / g(mscale_all_dim)``, which is 1 because the two are published
equal (anything else is refused here), and ``m = g(mscale_all_dim)`` =
1.4852 multiplies the softmax scale twice (``yarn_mscale``; the DeepSeek-V3
convention whose keys these are).

**The query scale** (``query_factor``): ``a(pos) = 1 + beta ln(1 + floor(pos /
L0))`` (``llama_4_scaling_beta`` 0.1): 1 below ``L0`` = 8192 positions, 1.0693
from there to 16383.  It depends on the QUERY's position alone, so at decode
it is one number a row.

**MoE(u)**: ``p = softmax(float32(u) Wr)`` over all ``E``; ``sel`` = the ``k``
largest; ``w = s p_sel / sum(p_sel)``; ``y = SwiGLU_shared(u) + sum_{e in sel}
w_e SwiGLU_e(u)``, ``SwiGLU(u) = (silu(u Wg) * (u Wu)) Wd``; no capacity, no
drop.  **The share** (``expert_share.py``): the layer holds ``experts_held``
experts from ``expert_offset`` (``params["experts"]``, its own subtree),
routes over all ``E``, sums ITS experts' part and adds the shared expert,
which every chip computes for the tokens that live on it.  The held parts
of all shares and the shared expert counted once add up to the whole layer.

**Prefill** expands the latent to per-head keys and values once a layer and
scores them in blocks (``blocked_attention``): ``QUERY_BLOCK`` queries
against ``KEY_BLOCK`` keys at a time (``mla.mla_blocked``) under an online
softmax (``ops.decode_attention.attend_blocks``), key blocks wholly above
the diagonal and query blocks wholly beyond the longest prompt not
computed, so that no array grows with the square of the rung.

Not here: the vision encoder (the catalog gives the language model's
configuration alone; the model's own logits on token ids do not depend on
it).

Device operations carry ``jax.named_scope``s ``mistral4.embed`` (the token
gather, and the positions and masks a program makes once from its inputs),
``mistral4.mla`` (norm to residual, and the cache write), ``mistral4.moe``
(norm, router, held experts, counts), ``mistral4.shared`` (the shared expert
and the residual) and ``mistral4.head`` (final norm + vocabulary product).
Routing is counted in the program:
``routed_total`` (choices made by live tokens), ``routed_held`` (those on
experts held here) and ``experts_touched`` (distinct held experts a layer
ran, summed over layers).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .expert_share import (LOOP_COUNT_NAMES, held_choices, held_experts,
                           held_experts_dense, loop_counts,
                           runs_every_held_expert, softmax_route)
from .layers import add_counts, ffn, matmul, rmsnorm, yarn_inv_freq
from .mla import mla_blocked, mla_project

ATTENTION = ("wq_a", "rms_q", "wq_b", "wkv_a", "rms_kv", "wk_b", "wv_b", "wo")
COUNT_NAMES = ("routed_total", "routed_held", "experts_touched",
               *LOOP_COUNT_NAMES)


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    vocab_size: int = 131072
    n_layer: int = 36
    n_head: int = 32
    d_model: int = 4096
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_expert: int = 2048  # routed and shared experts alike
    n_routed_experts: int = 128  # the router's width, whatever is held
    experts_held: int = 128
    expert_offset: int = 0
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e4
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    query_scale_beta: float = 0.1  # llama_4_scaling_beta
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError(
                f"rope_mscale {self.rope_mscale} != rope_mscale_all_dim "
                f"{self.rope_mscale_all_dim}: cos and sin would carry their "
                "ratio, which this family does not apply")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even qk_rope_head_dim")

    @property
    def latent_dim(self) -> int:
        """Values the cache holds a token a layer: ``[ckv | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def tiny(cls, **kw) -> "Mistral4Config":
        """Sixteen trained positions scaled by 8: a hundred positions cross
        ``floor(pos / 16)`` several times and turn the slowed pairs (1-3 of
        4) by radians."""
        for key, value in dict(
                vocab_size=512, n_layer=2, n_head=4, d_model=64,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=16, d_expert=32,
                n_routed_experts=16, experts_held=16, top_k=4,
                rope_factor=8.0, rope_original_max=16).items():
            kw.setdefault(key, value)
        return cls(**kw)


# ------------------------------------------------------------------- rotary
def yarn_numbers(cfg: Mistral4Config) -> tuple:
    """``yarn_inv_freq``'s arguments as this family's config names them."""
    return (cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow)


def yarn_mscale(cfg: Mistral4Config) -> float:
    """``m``: the softmax scale is multiplied by ``m^2``."""
    if cfg.rope_factor <= 1 or not cfg.rope_mscale_all_dim:
        return 1.0
    return 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0


def query_factor(positions, cfg: Mistral4Config):
    """What multiplies a query at ``positions`` (int, any shape) beside
    ``(dn+dr)^-0.5``: ``a(pos) m^2``, float32 of that shape."""
    periods = (positions // cfg.rope_original_max).astype(jnp.float32)
    a = 1.0 + cfg.query_scale_beta * jnp.log1p(periods)
    return a * yarn_mscale(cfg) ** 2


def project(y, att, positions, cfg: Mistral4Config):
    """y ``[B, S, d]`` at ``positions`` (``[S]`` or ``[B, S]``) -> queries
    ``[B, S, H, dn+dr]`` (roped, ``a(pos) m^2`` folded in) and the latent
    ``[B, S, rkv+dr]`` that the cache holds."""
    return mla_project(
        y, att, positions, cfg, latent_scales=False,
        inv_freq=yarn_inv_freq(*yarn_numbers(cfg)),
        q_factor=query_factor(positions, cfg)[..., None, None])


# --------------------------------------------------------------- parameters
def mistral4_init(key, cfg: Mistral4Config):
    d, L, H, Fe = cfg.d_model, cfg.n_layer, cfg.n_head, cfg.d_expert
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    k = iter(jax.random.split(key, 16))
    s, so = 0.02, 0.02 / (2 * L) ** 0.5

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(k), shape) * scale).astype(dtype)

    return {
        "wte": init((cfg.vocab_size, d), s),
        "blocks": {
            "rms_attn": jnp.ones((L, d), dt),
            "wq_a": init((L, d, rq), s),
            "rms_q": jnp.ones((L, rq), dt),
            "wq_b": init((L, rq, H, dn + dr), s),
            "wkv_a": init((L, d, rkv + dr), s),
            "rms_kv": jnp.ones((L, rkv), dt),
            "wk_b": init((L, rkv, H, dn), s),
            "wv_b": init((L, rkv, H, dv), s),
            "wo": init((L, H, dv, d), so),
            "rms_ffn": jnp.ones((L, d), dt),
            # The router stays float32.
            "router": init((L, d, cfg.n_routed_experts), s, jnp.float32),
            # The shared expert.
            "w_gate": init((L, d, Fe), s),
            "w_up": init((L, d, Fe), s),
            "w_down": init((L, Fe, d), so),
        },
        "experts": {
            "w_gate": init((L, cfg.experts_held, d, Fe), s),
            "w_up": init((L, cfg.experts_held, d, Fe), s),
            "w_down": init((L, cfg.experts_held, Fe, d), so),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), s),
    }


def mistral4_param_axes():
    """Logical sharding axes (leading None = layer-stack axis)."""
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "rms_attn": P(None, "norm"),
            "wq_a": P(None, "embed", None),
            "rms_q": P(None, "norm"),
            "wq_b": P(None, None, "heads", "kv"),
            "wkv_a": P(None, "embed", None),
            "rms_kv": P(None, "norm"),
            "wk_b": P(None, None, "heads", "kv"),
            "wv_b": P(None, None, "heads", "kv"),
            "wo": P(None, "heads", "kv", "embed"),
            "rms_ffn": P(None, "norm"),
            "router": P(None, "embed", None),
            "w_gate": P(None, "embed", "mlp"),
            "w_up": P(None, "embed", "mlp"),
            "w_down": P(None, "mlp", "embed"),
        },
        "experts": {
            "w_gate": P(None, "expert", "embed", "mlp"),
            "w_up": P(None, "expert", "embed", "mlp"),
            "w_down": P(None, "expert", "mlp", "embed"),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# ------------------------------------------------------------------ experts
def moe(u, live, params, i: int, cfg: Mistral4Config):
    """Layer ``i``'s expert layer on this chip: its held experts' part of
    the routed sum + the shared expert.  ``u [N, d]`` normed tokens in float32
    (the router reads them as they are, the experts in ``cfg.dtype``), ``live
    [N]`` bool (a padded or idle row chooses nothing: it touches no held
    expert and is not counted) -> (``[N, d]`` float32, counts).  A decode step
    of 32 slots (32 x 4 / 128 = 1.0 choices an expert: independent rows
    touch 64 % of the held experts, the served ones 43-49 %) takes the loop
    over the touched ones like a prefill, in its one-chunk form: a turn
    reads ONE expert's 50 MB for the whole batch, 77 us on the v5e, where
    the batched products over all sixteen took 1.14 ms a layer whatever was
    chosen (PERF.md, PR 54).  The way is read off the SHAPES, never off the
    load (``expert_share.runs_every_held_expert``)."""
    blocks, experts = params["blocks"], params["experts"]
    with jax.named_scope("mistral4.moe"):
        ud = u.astype(jnp.dtype(cfg.dtype))
        sel, w = softmax_route(u, blocks["router"][i], cfg.top_k,
                               cfg.routed_scaling_factor)
        held, hit, w_held = held_choices(
            sel, w, live, cfg.expert_offset, cfg.experts_held)
        dense = runs_every_held_expert(u.shape[0], cfg.top_k,
                                       cfg.n_routed_experts)
        if dense:
            y = held_experts_dense(ud, w_held, experts, i)
        else:  # [i, e] inside the loop: expert_share.py
            y = held_experts(ud, hit, w_held, lambda x, e: ffn(
                x, experts["w_gate"][i, e], experts["w_up"][i, e],
                experts["w_down"][i, e]))
    with jax.named_scope("mistral4.shared"):
        y = y + ffn(ud, blocks["w_gate"][i], blocks["w_up"][i],
                    blocks["w_down"][i])
    with jax.named_scope("mistral4.moe"):
        return y, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit, looped=not dense),
        }


# -------------------------------------------------------------------- model
def layer(params, x, live, i, attend, cfg: Mistral4Config):
    """Block ``i`` over the float32 stream ``x [..., d]`` -> (the stream after
    it, what ``attend`` kept for the cache, routing counts).  ``attend(att,
    y)`` is MLA of the normed state ``y`` in ``cfg.dtype`` with the layer's
    weights ``att`` (blocked over a sequence or absorbed over the cache: the
    caller's) -> (``[..., d]`` float32, the latent); ``live`` has ``x``'s
    leading shape.  ``i`` is a Python int in the decode step, whose layers are
    written out (every weight is taken as ``stack[i]`` where it is used: a
    layer's slice taken first and indexed later is a copy of the layer, which
    a step bound by the memory's speed cannot pay), and the loop's counter
    in a forward over a sequence (``mistral4_forward``)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    with jax.named_scope("mistral4.mla"):
        y = rmsnorm(x, blocks["rms_attn"][i], cfg.rms_eps).astype(dt)
        o, latent = attend({k: blocks[k][i] for k in ATTENTION}, y)
        x = x + o
    with jax.named_scope("mistral4.moe"):
        u = rmsnorm(x, blocks["rms_ffn"][i], cfg.rms_eps)  # float32
        u, live = u.reshape(-1, u.shape[-1]), live.reshape(-1)
    y, counts = moe(u, live, params, i, cfg)
    with jax.named_scope("mistral4.shared"):  # the sum's last term
        return x + y.reshape(x.shape), latent, counts


def mistral4_forward(params, tokens, lengths, cfg: Mistral4Config):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S,
    d]``, every layer's latents ``[L, B, S, rkv+dr]``, routing counts of the
    positions ``< length``).  Rows at or beyond the longest prompt's last
    query block carry no attention (``blocked_attention``).  The layers are
    ONE loop's body (``lax.scan`` over the layer index): a sequence's products
    are bound by compute, so a layer's weights may be sliced out of their
    stacks as they are needed, the program is a ninth as long (seven rungs
    are compiled a replica) and a rung's temporaries are one layer's."""
    with jax.named_scope("mistral4.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        live = positions[None] < lengths[:, None]
        longest = jnp.max(lengths)

    def attend(att, y):
        q, latent = project(y, att, positions, cfg)
        return mla_blocked(q, latent, att, cfg, longest), latent

    def one_layer(carry, i):
        x, total = carry
        x, latent, counts = layer(params, x, live, i, attend, cfg)
        with jax.named_scope("mistral4.moe"):
            return (x, add_counts(total, counts)), latent

    zero = dict.fromkeys(COUNT_NAMES, jnp.zeros((), jnp.int32))
    (x, counts), latents = jax.lax.scan(
        one_layer, (x, zero), jnp.arange(cfg.n_layer))
    with jax.named_scope("mistral4.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    return x, latents, counts


def mistral4_apply(params, tokens, cfg: Mistral4Config, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "mistral4 runs one chip's share of a layer; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = mistral4_forward(params, tokens, lengths, cfg)
    with jax.named_scope("mistral4.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def mistral4_loss(params, tokens, cfg: Mistral4Config, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = mistral4_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
