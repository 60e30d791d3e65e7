"""Kimi-Linear-style decoder: Kimi Delta Attention (KDA: a delta rule whose
decay is a VECTOR over the key channels of every head) three layers in four,
latent attention (MLA) with no positional term beside it, and after the
first layer's dense MLP a shared expert beside routed experts of which the
layer is told which it holds.

Source of the sizes: ``huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct``
``config.json`` (``model_type`` ``kimi_linear``, 48B-A3B); the equations are
the Kimi Linear report's (arXiv:2510.26692).  Symbols: ``d`` d_model; KDA:
``H`` heads of ``dk = dv`` (128), ``K`` the convolution's taps, ``r`` the rank
of the two gates' bottlenecks, ``C`` the chunk; MLA: ``Ha`` heads, ``dn`` /
``dr`` the two parts of a query-key head (128 / 64: BOTH without position),
``dv`` the value head, ``rkv`` the latent; ``F`` the dense MLP's width, ``Fe``
an expert's, ``E`` routed experts, ``k`` experts a token.  No bias but the
router's selection bias.  ``u`` is a sub-block's normed input; everything
between two matrix products is float32 (``layers.matmul``), and so are the
residual stream and the state.

**KDA(u)**: ``[q~ | k~ | v~] = u Wqkv`` (three ``[d, H dk]`` side by side);
``(q, k, v) = silu(conv_K(.))``, causal and depthwise, no bias; a head: ``q =
q / sqrt(|q|^2 + 1e-6) * dk^-1/2``, ``k = k / sqrt(|k|^2 + 1e-6)``; the decay
``g = -exp(A_log[h]) softplus((u Wfa) Wfb + dt_bias)`` in ``R^{H dk}`` (a
bottleneck of ``r``; ``A_log`` a head, ``dt_bias`` a channel), ``alpha =
exp(g)`` in ``(0, 1)^dk`` a head; ``beta = sigmoid(u Wb)`` a head (no factor
2).  State ``S [dk, dv]`` float32: ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' +
k_t (x) beta_t (v_t - S'^T k_t)``; ``o_t = S_t^T q_t``.  ``y = RMSNorm_dv(o) * w
* sigmoid((u Wga) Wgb)`` (the norm BEFORE the gate, the gate a sigmoid
through its own bottleneck), ``out = y Wo``.  A sequence runs the chunked
form (``delta_rule.delta_chunked`` with a vector gate: the decay inside the
sums over ``dk``, pair by pair), decode one step of the recurrence
(``ops.delta_update`` with the decay a column).  A position with ``beta = 0``
and ``g = 0`` neither writes nor decays the state: padding.

**MLA(u)** (``mla.py``; no query bottleneck, ``q_lora_rank`` null, and no
rotation, ``mla_use_nope``): ``q = u Wq`` as ``[T, Ha, dn+dr]``; ``u Wkva`` split
``[T, rkv] | [T, dr]`` -> ``ckv = RMSNorm(.)``, ``kr`` as projected (one key of
``dr`` for all heads); a head's key ``[ckv Wkb | kr]``, value ``ckv Wvb``;
causal softmax in float32 at ``(dn+dr)^-1/2``; ``Wo``.  **The cache holds
``[ckv | kr]``**, ``rkv + dr`` = 576 values a token a layer; decode absorbs
``Wkb`` into the query (``mla.mla_absorbed``).  Position is the KDA layers'.

**FFN**: the first ``first_k_dense`` layers ``(silu(u W1) * (u W3)) W2`` of
width ``F``; every other layer ``p = sigmoid(float32(u) Wr)`` over all ``E``,
``sel`` = the ``k`` largest of ``p + bias`` (no group limit:
``num_expert_group = topk_group = 1``), ``w = s p_sel / sum(p_sel)``
(``moe_renormalize``; ``s`` = ``routed_scaling_factor`` 2.446), ``y =
SwiGLU_shared(u) + sum_{e in sel} w_e SwiGLU_e(u)``; no capacity, no drop.
**The share** (``expert_share.py``): the layer holds ``experts_held`` experts
from ``expert_offset`` (``params["experts"]``, its own subtree), routes over
all ``E``, sums ITS experts' part and adds the shared expert.  The held parts
of all shares and the shared expert counted once add up to the whole layer.

**Model**: pre-norm both halves, ``x = x + Mixer(RMSNorm(x))``, ``x = x +
FFN(RMSNorm(x))`` (eps 1e-5), a final RMSNorm, an untied head.  The mixer's
kind is ``layer_pattern[i]``: ``K`` KDA, ``M`` latent attention; ``n_layer``
layers are taken from the FRONT of ``layer_pattern``.  ``kinds`` writes both
halves in one letter a layer: lower case where the FFN is the dense MLP.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of sub-block
(``kda``, ``mla``, ``dense``, ``moe``: each with its norm), as long as the
pattern has sub-blocks of that kind; a model of fewer layers reads the front
of each stack.  Device operations carry ``jax.named_scope``s ``kimi.embed``,
``kimi.delta`` (a KDA mixer with its norm, residual and the state it
leaves), ``kimi.mla`` (norm to residual, and the cache write), ``kimi.moe``
(norm, router, held experts, counts), ``kimi.shared`` (the shared expert and
the residual), ``kimi.mlp`` (the dense MLP) and ``kimi.head`` (final norm +
vocabulary product).  Counted in the program: ``delta_positions`` (true
positions a prefill scanned; rows a decode step served),
``delta_chunk_positions`` (positions of the chunks it ran; a decode step's
rows), ``routed_total``, ``routed_held``, ``experts_touched`` and the held
loop's ``held_chunks`` / ``held_chunk_rows`` (``expert_share``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .delta_rule import delta_chunked
from .expert_share import (LOOP_COUNT_NAMES, held_choices, held_experts,
                           held_experts_dense, loop_counts,
                           runs_every_held_expert, sigmoid_route)
from .layers import (add_counts, conv_sequence, ffn, layer_plan, matmul,
                     rmsnorm, scan_or_call)
from .mla import mla_blocked, mla_project

# linear_attn_config: full_attn_layers 4, 8, .. 24 and 27 of layers 1-27
PUBLISHED_PATTERN = "KKKM" * 6 + "KKM"
# a letter of ``kinds`` -> the stacks of its mixer and of its FFN
STACKS = {"K": ("kda", "moe"), "M": ("mla", "moe"),
          "k": ("kda", "dense"), "m": ("mla", "dense")}
MLA_WEIGHTS = ("wq", "wkv_a", "rms_kv", "wk_b", "wv_b", "wo")
ROUTING_COUNTS = ("routed_total", "routed_held", "experts_touched",
                  *LOOP_COUNT_NAMES)
L2_EPS = 1e-6


def stacks_in(kinds: str) -> dict:
    """Sub-blocks of each kind among the layers ``kinds`` (its letters as
    ``KimiLinearConfig.kinds`` writes them): how far into each parameter
    stack those layers reach."""
    sizes = dict.fromkeys(("kda", "mla", "dense", "moe"), 0)
    for c in kinds:
        for stack in STACKS[c]:
            sizes[stack] += 1
    return sizes


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    layer_pattern: str = PUBLISHED_PATTERN
    n_layer: int = 27  # layers taken from the front of ``layer_pattern``
    first_k_dense: int = 1  # layers whose FFN is the dense MLP
    d_model: int = 2304
    n_head: int = 32  # latent attention's
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # the shared key's part: NOT rotated here
    v_head_dim: int = 128
    linear_num_heads: int = 32
    linear_head_dim: int = 128  # keys = values
    conv_kernel: int = 4
    gate_rank: int = 128  # the bottleneck of the decay and of the output gate
    chunk_size: int = 32
    d_ff: int = 9216
    d_expert: int = 1024  # routed and shared experts alike
    n_routed_experts: int = 256  # the router's width, whatever is held
    experts_held: int = 256
    expert_offset: int = 0
    top_k: int = 8
    routed_scaling_factor: float = 2.446
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_pattern) - set("KM"):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             "is K (delta attention) or M (latent attention)")
        if not 0 < self.n_layer <= len(self.layer_pattern):
            raise ValueError(f"n_layer {self.n_layer} of a pattern of "
                             f"{len(self.layer_pattern)} layers")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")

    @property
    def kinds(self) -> str:
        """The layers this model runs, a letter each: the mixer's, in lower
        case where the FFN is the dense MLP."""
        return "".join(
            c.lower() if i < self.first_k_dense else c
            for i, c in enumerate(self.layer_pattern[:self.n_layer]))

    def stack_sizes(self) -> dict:
        """How long each parameter stack is: the sub-blocks of its kind in
        the WHOLE pattern, of which ``n_layer`` layers read the front."""
        return stacks_in(dataclasses.replace(
            self, n_layer=len(self.layer_pattern)).kinds)

    @property
    def d_key(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def d_conv(self) -> int:
        """Channels the convolution runs over: ``q | k | v``."""
        return 3 * self.d_key

    @property
    def latent_dim(self) -> int:
        """Values the cache holds a token a latent layer: ``[ckv | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def tiny(cls, **kw) -> "KimiLinearConfig":
        for key, value in dict(
                vocab_size=512, layer_pattern="KMKKMK", n_layer=6,
                d_model=64, n_head=4, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=16, linear_num_heads=4,
                linear_head_dim=16, gate_rank=8, chunk_size=8, d_ff=128,
                d_expert=32, n_routed_experts=16, experts_held=16,
                top_k=4).items():
            kw.setdefault(key, value)
        return cls(**kw)


# --------------------------------------------------------------- parameters
def kimi_linear_init(key, cfg: KimiLinearConfig):
    """Random weights with every stack as long as ``layer_pattern`` has
    sub-blocks of its kind.  ``A_log = log(U(1, 16))`` a head, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly in [0.001, 0.1] a CHANNEL
    (the Gated DeltaNet's init, a channel where it had a head)."""
    s, d, dt = 0.02, cfg.d_model, jnp.dtype(cfg.dtype)
    n = cfg.stack_sizes()
    nk, nm, nd, ne = n["kda"], n["mla"], n["dense"], n["moe"]
    H, dk, r = cfg.linear_num_heads, cfg.linear_head_dim, cfg.gate_rank
    Ha, rkv = cfg.n_head, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fe, Eh = cfg.d_ff, cfg.d_expert, cfg.experts_held
    so = s / (2 * len(cfg.layer_pattern)) ** 0.5
    keys = iter(jax.random.split(key, 40))

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (nk, H * dk), minval=math.log(1e-3),
        maxval=math.log(0.1)))
    return {
        "wte": init((cfg.vocab_size, d), s),
        "blocks": {
            "kda": {
                "rms": jnp.ones((nk, d), dt),
                "w_qkv": init((nk, d, cfg.d_conv), s),
                "conv_w": init((nk, cfg.conv_kernel, cfg.d_conv), 0.3,
                               jnp.float32),
                "w_fa": init((nk, d, r), s),
                "w_fb": init((nk, r, H * dk), s),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (nk, H), minval=1.0, maxval=16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "w_b": init((nk, d, H), s),
                "w_ga": init((nk, d, r), s),
                "w_gb": init((nk, r, H * dk), s),
                "norm": jnp.ones((nk, dk), dt),
                "w_o": init((nk, H * dk, d), so),
            },
            "mla": {
                "rms": jnp.ones((nm, d), dt),
                "wq": init((nm, d, Ha, dn + dr), s),
                "wkv_a": init((nm, d, rkv + dr), s),
                "rms_kv": jnp.ones((nm, rkv), dt),
                "wk_b": init((nm, rkv, Ha, dn), s),
                "wv_b": init((nm, rkv, Ha, dv), s),
                "wo": init((nm, Ha, dv, d), so),
            },
            "dense": {
                "rms": jnp.ones((nd, d), dt),
                "w_gate": init((nd, d, F), s),
                "w_up": init((nd, d, F), s),
                "w_down": init((nd, F, d), so),
            },
            "moe": {
                "rms": jnp.ones((ne, d), dt),
                # The router and its selection bias stay float32.
                "router": init((ne, d, cfg.n_routed_experts), s, jnp.float32),
                "router_bias": jnp.zeros((ne, cfg.n_routed_experts),
                                         jnp.float32),
                # The shared expert.
                "w_gate": init((ne, d, Fe), s),
                "w_up": init((ne, d, Fe), s),
                "w_down": init((ne, Fe, d), so),
            },
        },
        "experts": {
            "w_gate": init((ne, Eh, d, Fe), s),
            "w_up": init((ne, Eh, d, Fe), s),
            "w_down": init((ne, Eh, Fe, d), so),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), s),
    }


def kimi_linear_param_axes():
    """Logical sharding axes (leading None = a kind's layer-stack axis)."""
    mlp = {"rms": P(None, "norm"),
           "w_gate": P(None, "embed", "mlp"),
           "w_up": P(None, "embed", "mlp"),
           "w_down": P(None, "mlp", "embed")}
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "kda": {
                "rms": P(None, "norm"),
                "w_qkv": P(None, "embed", "mlp"),
                "conv_w": P(None, None, "mlp"),
                "w_fa": P(None, "embed", None),
                "w_fb": P(None, None, "mlp"),
                "a_log": P(None, "heads"),
                "dt_bias": P(None, "mlp"),
                "w_b": P(None, "embed", "heads"),
                "w_ga": P(None, "embed", None),
                "w_gb": P(None, None, "mlp"),
                "norm": P(None, None),
                "w_o": P(None, "mlp", "embed"),
            },
            "mla": {
                "rms": P(None, "norm"),
                "wq": P(None, "embed", "heads", "kv"),
                "wkv_a": P(None, "embed", None),
                "rms_kv": P(None, "norm"),
                "wk_b": P(None, None, "heads", "kv"),
                "wv_b": P(None, None, "heads", "kv"),
                "wo": P(None, "heads", "kv", "embed"),
            },
            "dense": dict(mlp),
            "moe": {"router": P(None, "embed", None),
                    "router_bias": P(None, None), **mlp},
        },
        "experts": {
            "w_gate": P(None, "expert", "embed", "mlp"),
            "w_up": P(None, "expert", "embed", "mlp"),
            "w_down": P(None, "expert", "mlp", "embed"),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# ---------------------------------------------------------- delta attention
def kda_project(y, m, i: int, cfg: KimiLinearConfig):
    """y ``[..., d]`` in ``cfg.dtype`` -> ``qkv [..., 3 H dk]`` (before the
    convolution), the output gate ``z [..., H dv]`` (before its sigmoid), the
    log of the decay ``g [..., H, dk]`` (< 0: a vector a head) and ``beta
    [..., H]``, float32."""
    h, dk = cfg.linear_num_heads, cfg.linear_head_dim

    def low_rank(a, b):  # (y a) b, the bottleneck rounded once
        return matmul("...r,rf->...f", matmul(
            "...e,er->...r", y, a[i]).astype(y.dtype), b[i])

    qkv = matmul("...e,ef->...f", y, m["w_qkv"][i])
    f = low_rank(m["w_fa"], m["w_fb"]) + m["dt_bias"][i]
    g = -jnp.exp(m["a_log"][i])[:, None] * jax.nn.softplus(
        f.reshape(*f.shape[:-1], h, dk))
    beta = jax.nn.sigmoid(matmul("...e,eh->...h", y, m["w_b"][i]))
    return qkv, low_rank(m["w_ga"], m["w_gb"]), g, beta


def split_heads(conv, cfg: KimiLinearConfig):
    """The convolution's output after its silu ``[..., 3 H dk]`` -> ``q``
    (normalised, times ``dk^-1/2``), ``k`` (normalised) and ``v``, ``[..., H,
    dk]`` each."""
    lead, h = conv.shape[:-1], cfg.linear_num_heads
    q, k, v = (a.reshape(*lead, h, -1) for a in jnp.split(conv, 3, axis=-1))
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS)
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    return q * cfg.linear_head_dim ** -0.5, k, v


def kda_output(o, z, m, i: int, cfg: KimiLinearConfig):
    """``(RMSNorm_dv(o) * w * sigmoid(z)) Wo``: o ``[..., H, dv]`` float32, z
    ``[..., H dv]`` -> ``[..., d]`` float32."""
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps)
    o = o * m["norm"][i].astype(jnp.float32)
    y = o.reshape(z.shape) * jax.nn.sigmoid(z)
    return matmul("...f,fe->...e", y.astype(jnp.dtype(cfg.dtype)),
                  m["w_o"][i])


def kda_sequence(y, lengths, m, i: int, cfg: KimiLinearConfig):
    """KDA over whole sequences.  y ``[B, S, d]``, lengths ``[B]`` -> (``[B, S,
    d]`` float32, the convolution's state ``[B, (K-1) 3 H dk]`` = its last
    ``K-1`` TRUE inputs side by side, oldest first, the state ``[B, H, dk,
    dv]`` after position ``length - 1``).  Positions ``>= length`` change
    neither."""
    qkv, z, g, beta = kda_project(y, m, i, cfg)
    live = jnp.arange(y.shape[1])[None, :, None] < lengths[:, None, None]
    g = jnp.where(live[..., None], g, 0.0)
    beta = jnp.where(live, beta, 0.0)
    conv, conv_state = conv_sequence(qkv, lengths, m["conv_w"], i)
    q, k, v = split_heads(jax.nn.silu(conv), cfg)
    o, state = delta_chunked(q, k, v, g, beta, cfg.chunk_size)
    return (kda_output(o, z, m, i, cfg),
            conv_state.reshape(y.shape[0], -1), state)


# --------------------------------------------------------- latent attention
def project(y, att, cfg: KimiLinearConfig):
    """y ``[B, S, d]`` -> queries ``[B, S, Ha, dn+dr]`` and the latent ``[B, S,
    rkv+dr]`` that the cache holds; no position enters."""
    return mla_project(y, att, None, cfg, latent_scales=False, rotate=False)


def mla_weights(blocks, i):
    """Latent layer ``i``'s weights, each taken out of its stack."""
    return {name: blocks["mla"][name][i] for name in MLA_WEIGHTS}


# ---------------------------------------------------------------------- FFN
def moe(u, live, params, i: int, cfg: KimiLinearConfig):
    """Expert layer ``i`` on this chip: its held experts' part of the routed
    sum + the shared expert.  ``u [N, d]`` normed tokens in float32 (the
    router reads them as they are, the experts in ``cfg.dtype``), ``live [N]``
    bool (a padded or idle row chooses nothing: it touches no held expert
    and is not counted) -> (``[N, d]`` float32, counts).  A decode step of 64
    slots (64 x 8 / 256 = 2.0 choices an expert: independent rows touch 87 %
    of the held) runs every held expert in batched products, MiMo-V2's
    shape and MiMo-V2's way; a prefill gathers each expert's rows.  The way
    is read off the SHAPES (``expert_share.runs_every_held_expert``)."""
    w, experts = params["blocks"]["moe"], params["experts"]
    with jax.named_scope("kimi.moe"):
        ud = u.astype(jnp.dtype(cfg.dtype))
        sel, weight = sigmoid_route(
            u, w["router"][i], w["router_bias"][i], cfg.top_k,
            cfg.routed_scaling_factor)
        held, hit, w_held = held_choices(
            sel, weight, live, cfg.expert_offset, cfg.experts_held)
        dense = runs_every_held_expert(u.shape[0], cfg.top_k,
                                       cfg.n_routed_experts)
        if dense:
            y = held_experts_dense(ud, w_held, experts, i)
        else:  # [i, e] inside the loop: expert_share.py
            y = held_experts(ud, hit, w_held, lambda x, e: ffn(
                x, experts["w_gate"][i, e], experts["w_up"][i, e],
                experts["w_down"][i, e]))
    with jax.named_scope("kimi.shared"):
        y = y + ffn(ud, w["w_gate"][i], w["w_up"][i], w["w_down"][i])
    with jax.named_scope("kimi.moe"):
        return y, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit, looped=not dense),
        }


# -------------------------------------------------------------------- model
def block(params, x, live, kind: str, i: int, j: int, mix,
          cfg: KimiLinearConfig):
    """One layer on the float32 stream ``x [..., d]``: ``kind`` its letter of
    ``kinds``, ``i`` its mixer's place in the mixer's stack, ``j`` its FFN's
    in the FFN's.  ``mix(y)`` is the layer's mixer on the normed state in
    ``cfg.dtype`` (a sequence's or one decode step's: the caller's, which
    keeps what the cache needs) -> ``[..., d]`` float32; ``live`` has ``x``'s
    leading shape -> (the stream after the layer, routing counts or
    ``None``).  Every weight is taken as ``stack[i]`` where it is used (a
    layer's slice taken first is a copy of the layer)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    mixer, ff = STACKS[kind]
    with jax.named_scope("kimi.delta" if mixer == "kda" else "kimi.mla"):
        y = rmsnorm(x, blocks[mixer]["rms"][i], cfg.rms_eps)
        x = x + mix(y.astype(dt))
    if ff == "dense":
        w = blocks["dense"]
        with jax.named_scope("kimi.mlp"):
            y = rmsnorm(x, w["rms"][j], cfg.rms_eps).astype(dt)
            return x + ffn(y, w["w_gate"][j], w["w_up"][j],
                           w["w_down"][j]), None
    with jax.named_scope("kimi.moe"):
        u = rmsnorm(x, blocks["moe"]["rms"][j], cfg.rms_eps)  # float32
    y, counts = moe(u.reshape(-1, u.shape[-1]), live.reshape(-1), params, j,
                    cfg)
    with jax.named_scope("kimi.shared"):  # the sum's last term
        return x + y.reshape(x.shape), counts


def zero_counts():
    return dict.fromkeys(ROUTING_COUNTS, jnp.zeros((), jnp.int32))


def kimi_linear_forward(params, tokens, lengths, cfg: KimiLinearConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S, d]``,
    what a cache holds of it: ``latent`` ``[Lm, B, S, rkv+dr]``, ``conv`` ``[Lk,
    B, (K-1) 3 H dk]`` and ``state`` ``[Lk, B, H, dk, dv]`` at each row's TRUE
    length, counts).  Rows at or beyond the longest prompt's last query
    block carry no attention (``blocked_attention``).  A run of layers of
    one kind is ONE loop's body and a group of runs that repeats is a loop
    of those (``layers.layer_plan``; ``lax.scan`` in ``lax.scan``): the
    cell's 21 layers ``k`` + ``MKKK`` x 5 are three layer bodies."""
    blocks = params["blocks"]
    with jax.named_scope("kimi.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        live = jnp.arange(tokens.shape[1])[None] < lengths[:, None]
        longest = jnp.max(lengths)

    def one_run(carry, kind, length, at):
        """``length`` layers of one kind, the first at ``at[stack]`` of its
        stacks -> (carry, what the cache keeps of each, stacked)."""
        mixer, ff = STACKS[kind]

        def one_layer(carry, t):
            x, total = carry
            held = []

            def delta(y):
                out, *state = kda_sequence(
                    y, lengths, blocks["kda"], at["kda"] + t, cfg)
                held.extend(state)  # conv, state
                return out

            def attend(y):
                att = mla_weights(blocks, at["mla"] + t)
                q, latent = project(y, att, cfg)
                held.append(latent)
                return mla_blocked(q, latent, att, cfg, longest)

            x, counts = block(params, x, live, kind, at[mixer] + t,
                              at[ff] + t, delta if mixer == "kda" else attend,
                              cfg)
            if counts is not None:
                with jax.named_scope("kimi.moe"):
                    total = add_counts(total, counts)
            return (x, total), tuple(held)

        return scan_or_call(one_layer, carry, length)

    names = {"kda": ("conv", "state"), "mla": ("latent",)}
    kept = {"kda": [], "mla": []}
    seen = stacks_in("")
    carry = (x, zero_counts())
    for group, repeats in layer_plan(cfg.kinds):
        strides = stacks_in("".join(k * n for k, n in group))

        def one_period(carry, p):  # traced at once: the loop's values as now
            held = {"kda": [], "mla": []}
            inside = stacks_in("")
            for kind, n in group:
                carry, part = one_run(carry, kind, n, {
                    s: seen[s] + p * strides[s] + inside[s] for s in seen})
                held[STACKS[kind][0]].append(part)
                for s, more in stacks_in(kind * n).items():
                    inside[s] += more
            return carry, {
                mixer: tuple(jnp.concatenate(a) for a in zip(*parts))
                for mixer, parts in held.items() if parts}

        carry, held = scan_or_call(one_period, carry, repeats)
        for mixer, part in held.items():  # [repeats, layers of the kind, ..]
            with jax.named_scope(
                    "kimi.delta" if mixer == "kda" else "kimi.mla"):
                kept[mixer].append(tuple(
                    a.reshape((-1,) + a.shape[2:]) for a in part))
        for s in seen:
            seen[s] += repeats * strides[s]

    x, counts = carry
    with jax.named_scope("kimi.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    cache = {}
    for mixer, parts in kept.items():
        if parts:
            with jax.named_scope(
                    "kimi.delta" if mixer == "kda" else "kimi.mla"):
                for name, leaves in zip(names[mixer], zip(*parts)):
                    cache[name] = jnp.concatenate(leaves)
    bsz, s = tokens.shape
    with jax.named_scope("kimi.delta"):
        counts = dict(
            counts, delta_positions=lengths.sum().astype(jnp.int32),
            delta_chunk_positions=jnp.asarray(
                bsz * -(-s // cfg.chunk_size) * cfg.chunk_size, jnp.int32))
    return x, cache, counts


def kimi_linear_apply(params, tokens, cfg: KimiLinearConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "kimi_linear runs one chip's share of a layer; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = kimi_linear_forward(params, tokens, lengths, cfg)
    with jax.named_scope("kimi.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def kimi_linear_loss(params, tokens, cfg: KimiLinearConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = kimi_linear_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
