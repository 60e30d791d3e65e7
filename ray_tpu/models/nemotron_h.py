"""Nemotron-H-style hybrid decoder: Mamba-2 mixers, a few grouped-query
attentions and LatentMoE expert layers in one model, by a pattern string;
the expert layer is told which experts it holds.

Source of the sizes: ``huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-
A12B-BF16`` ``config.json`` (``model_type`` ``nemotron_h``).  Symbols: ``d``
d_model; Mamba-2: ``H`` heads of ``P`` channels (inner width ``H P``), ``N``
state size, ``G`` groups (head ``h`` reads group ``h // (H/G)``), ``K`` the
convolution's taps, ``Q`` the chunk; attention: ``Hq`` query / ``Hkv``
key-value heads of ``D``; experts: ``E`` routed experts, ``k`` a token,
``l`` the latent width, ``Fe`` / ``Fs`` the routed / shared expert widths,
``s`` the routed scaling factor.  No bias but the convolution's.

**Model**: every block is ``x = x + mixer(RMSNorm(x))`` (eps 1e-5); the
mixer's kind is ``layer_pattern[i]``: ``M`` Mamba-2, ``*`` attention, ``E``
experts.  ``n_layer`` layers are taken from the FRONT of ``layer_pattern``.
Then a final RMSNorm and an untied head.  The residual stream is kept in
float32 (the config's ``residual_in_fp32`` is false: bf16 there); the
matrix products read ``cfg.dtype`` and accumulate in float32, and what lies
between two products is float32, rounded once where the next product reads
it (``layers.matmul``).

**Mamba-2(u)**: ``z = u Wz``, ``xBC = u Wxbc``, ``dt = u Wdt`` (the published
``in_proj``'s columns, widths ``HP | HP + 2GN | H``); ``xBC = silu(conv_K(xBC)
+ b)``, causal and depthwise (``out_t = sum_j w_j xBC_{t-K+1+j}``), split ``x
[H, P] | B [G, N] | C [G, N]``; ``dt = softplus(dt + dt_bias)``; ``A =
-exp(A_log)`` a head.  A head ``h`` of group ``g``: ``S_t = exp(dt_t A) S_{t-1}
+ dt_t x_t (x) B_t`` (``S [P, N]``, float32), ``y_t = S_t C_t + D x_t``.  ``y =
RMSNorm_grouped(y * silu(z))`` over ``G`` groups of ``HP / G`` (the gate
before the norm), ``out = y Wout``.  A sequence runs the chunked (SSD) form,
``ssd_chunked``: inside a chunk of ``Q`` positions the recurrence unrolled
into a masked ``[Q, Q]`` product, between chunks a scan over the chunks'
end states.  A position with ``dt = 0`` neither decays nor feeds the state:
that is how padding (``t >= length``) is left out of it.  Decode
(``nemotron_h_decode.py``) runs one step of the recurrence itself.

**Attention(u)**: ``Hq`` query heads over ``Hkv`` key/value heads, causal
softmax in float32, no positional term at all (the published modelling code
applies no rotary embedding; the Mamba-2 layers carry position), ``o_proj``.

**Experts(u)** (LatentMoE): ``p = sigmoid(float32(u) Wr)`` over all ``E``;
``sel`` = the ``k`` largest of ``p + bias``; ``w_i = s p_i / sum_{j in sel}
p_j``; ``v = u Wdl`` (``d -> l``); expert ``e``: ``f_e(v) = relu(v W1_e)^2
W2_e`` (``W1 [l, Fe]``, ``W2 [Fe, l]``); ``out = (sum_{i in sel} w_i f_i(v))
Wul + relu(u Ws1)^2 Ws2``: one latent pair ``Wdl`` / ``Wul`` a layer, the
shared expert on the full hidden.  No capacity, no drop.  **The share**
(``expert_share.py``): the layer holds ``experts_held`` experts from
``expert_offset`` (``params["experts"]``, its own subtree), routes over all
``E``, sums ITS experts' part in the latent and applies ``Wul`` to that
partial sum (``Wul`` is linear: the shares of all chips add up to the whole
layer, the shared expert counted once).

Not here: the multi-token-prediction head (``num_nextn_predict_layers``), a
draft head the model's own logits do not depend on.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of layer
(``mamba``, ``attn``, ``moe``), each as long as the pattern has layers of
that kind; a model of fewer layers reads the front of each stack.  Device
operations carry ``jax.named_scope``s ``nemotron.embed`` (the token gather,
and the mask a program makes once from its inputs), ``nemotron.mamba`` and
``nemotron.attn`` (norm to residual, and what the cache keeps of them),
``nemotron.moe`` (norm, router, held and shared experts, residual, counts)
and ``nemotron.head`` (final norm + vocabulary product).  Routing is counted in the program: ``routed_total``
(choices made by live tokens), ``routed_held`` (those on experts held here)
and ``experts_touched`` (distinct held experts a layer ran, summed over
layers).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .expert_share import (LOOP_COUNT_NAMES, held_choices, held_experts,
                           loop_counts, runs_every_held_expert,
                           sigmoid_route)
from .layers import add_counts, matmul, rmsnorm
from .mamba2 import mamba_sequence

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
COUNT_NAMES = ("routed_total", "routed_held", "experts_touched",
               *LOOP_COUNT_NAMES)
# a cache leaf -> the scope of the part that keeps it
CACHE_SCOPE = {"k": "nemotron.attn", "v": "nemotron.attn",
               "conv": "nemotron.mamba", "ssm": "nemotron.mamba"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    layer_pattern: str = PUBLISHED_PATTERN
    n_layer: int = 88  # layers taken from the front of ``layer_pattern``
    d_model: int = 4096
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512  # the router's width, whatever is held
    experts_held: int = 512
    expert_offset: int = 0
    top_k: int = 22
    moe_latent_size: int = 1024
    d_expert: int = 2688
    d_shared: int = 5376
    routed_scaling_factor: float = 5.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_pattern) - set("M*E"):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             "is M (Mamba-2), * (attention) or E (experts)")
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
        """The kinds of the layers this model runs."""
        return self.layer_pattern[:self.n_layer]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def d_conv(self) -> int:
        """Channels the convolution runs over: ``x | B | C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        for key, value in dict(
                vocab_size=512, layer_pattern="MEM*EM*E", n_layer=8,
                d_model=64, mamba_num_heads=8, mamba_head_dim=8,
                ssm_state_size=16, n_groups=2, chunk_size=8, n_head=4,
                n_kv_head=2, head_dim=16, n_routed_experts=16,
                experts_held=16, top_k=4, moe_latent_size=32, d_expert=48,
                d_shared=96).items():
            kw.setdefault(key, value)
        return cls(**kw)


def nemotron_h_init(key, cfg: NemotronHConfig):
    """Random weights with every stack as long as ``layer_pattern`` has
    layers of its kind.  ``A_log = log(U(1, 16))``, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in [0.001, 0.1] (the config's
    ``time_step_min`` / ``max``; ``time_step_floor`` 1e-4 is below it), ``D =
    1``."""
    sd = {"embed": 0.02, "in": 0.02, "out": 0.02, "router": 0.02, "conv": 0.3}
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nm, na, ne = (cfg.layer_pattern.count(c) for c in "M*E")
    H, C = cfg.mamba_num_heads, cfg.d_conv
    keys = iter(jax.random.split(key, 24))

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (nm, H), minval=math.log(1e-3), maxval=math.log(0.1)))
    return {
        "wte": init((cfg.vocab_size, d), sd["embed"]),
        "blocks": {
            "mamba": {
                "rms": jnp.ones((nm, d), dt),
                "w_z": init((nm, d, cfg.d_inner), sd["in"]),
                "w_xbc": init((nm, d, C), sd["in"]),
                "w_dt": init((nm, d, H), sd["in"]),
                "conv_w": init((nm, cfg.conv_kernel, C), sd["conv"],
                               jnp.float32),
                "conv_b": jnp.zeros((nm, C), jnp.float32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (nm, H), minval=1.0, maxval=16.0)),
                "d_skip": jnp.ones((nm, H), jnp.float32),
                "norm": jnp.ones((nm, cfg.d_inner), dt),
                "w_out": init((nm, cfg.d_inner, d), sd["out"]),
            },
            "attn": {
                "rms": jnp.ones((na, d), dt),
                "wq": init((na, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "wk": init((na, d, cfg.n_kv_head, cfg.head_dim), sd["in"]),
                "wv": init((na, d, cfg.n_kv_head, cfg.head_dim), sd["in"]),
                "wo": init((na, cfg.n_head, cfg.head_dim, d), sd["out"]),
            },
            "moe": {
                "rms": jnp.ones((ne, d), dt),
                # Router and its load-balancing bias stay float32.
                "router": init((ne, d, cfg.n_routed_experts), sd["router"],
                               jnp.float32),
                "router_bias": jnp.zeros((ne, cfg.n_routed_experts),
                                         jnp.float32),
                "w_dl": init((ne, d, cfg.moe_latent_size), sd["in"]),
                "w_ul": init((ne, cfg.moe_latent_size, d), sd["out"]),
                "ws1": init((ne, d, cfg.d_shared), sd["in"]),
                "ws2": init((ne, cfg.d_shared, d), sd["out"]),
            },
        },
        "experts": {
            "w1": init((ne, cfg.experts_held, cfg.moe_latent_size,
                        cfg.d_expert), sd["in"]),
            "w2": init((ne, cfg.experts_held, cfg.d_expert,
                        cfg.moe_latent_size), sd["out"]),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), sd["in"]),
    }


def nemotron_h_param_axes():
    """Logical sharding axes (leading None = a kind's layer-stack axis)."""
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "mamba": {
                "rms": P(None, "norm"),
                "w_z": P(None, "embed", "mlp"),
                "w_xbc": P(None, "embed", "mlp"),
                "w_dt": P(None, "embed", "heads"),
                "conv_w": P(None, None, "mlp"),
                "conv_b": P(None, "mlp"),
                "dt_bias": P(None, "heads"),
                "a_log": P(None, "heads"),
                "d_skip": P(None, "heads"),
                "norm": P(None, "mlp"),
                "w_out": P(None, "mlp", "embed"),
            },
            "attn": {
                "rms": P(None, "norm"),
                "wq": P(None, "embed", "heads", "kv"),
                "wk": P(None, "embed", "heads", "kv"),
                "wv": P(None, "embed", "heads", "kv"),
                "wo": P(None, "heads", "kv", "embed"),
            },
            "moe": {
                "rms": P(None, "norm"),
                "router": P(None, "embed", None),
                "router_bias": P(None, None),
                "w_dl": P(None, "embed", None),
                "w_ul": P(None, None, "embed"),
                "ws1": P(None, "embed", "mlp"),
                "ws2": P(None, "mlp", "embed"),
            },
        },
        "experts": {
            "w1": P(None, "expert", None, "mlp"),
            "w2": P(None, "expert", "mlp", None),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# ---------------------------------------------------------------- attention
def attention_project(y, att, i: int):
    """y ``[..., d]`` -> q ``[..., Hq, D]``, k, v ``[..., Hkv, D]`` in y's
    dtype.  No positional term."""
    q = matmul("...e,ehd->...hd", y, att["wq"][i])
    k = matmul("...e,ekd->...kd", y, att["wk"][i])
    v = matmul("...e,ekd->...kd", y, att["wv"][i])
    return q.astype(y.dtype), k.astype(y.dtype), v.astype(y.dtype)


def attention_sequence(y, att, i: int, cfg: NemotronHConfig):
    """Causal grouped-query attention of ``[B, S]`` tokens over themselves;
    (``[B, S, d]`` float32, k, v ``[B, S, Hkv, D]``)."""
    bsz, s, _ = y.shape
    q, k, v = attention_project(y, att, i)
    qg = q.reshape(bsz, s, cfg.n_kv_head, -1, cfg.head_dim)
    scores = matmul("bskgd,btkd->bkgst", qg, k) / cfg.head_dim ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = matmul("bkgst,btkd->bskgd", probs.astype(y.dtype), v)
    o = o.reshape(bsz, s, cfg.n_head, cfg.head_dim).astype(y.dtype)
    return matmul("bshd,hde->bse", o, att["wo"][i]), k, v


# ------------------------------------------------------------------ experts
def relu2(x, w1, w2):
    """``relu(x W1)^2 W2``; x in ``cfg.dtype`` -> float32."""
    h = jnp.square(jax.nn.relu(matmul("...e,ef->...f", x, w1)))
    return matmul("...f,fe->...e", h.astype(x.dtype), w2)


def held_experts_dense(v, w_held, w1, w2):
    """``sum_e w_held[:, e] f_e(v)`` with EVERY held expert run on every row
    (a row that did not choose it weighs 0): two batched products over the
    layer's ``w1 [Eh, l, Fe]`` / ``w2 [Eh, Fe, l]``, no gather, no loop.  v
    ``[N, l]`` in ``cfg.dtype``, w_held ``[N, Eh]`` float32 -> ``[N, l]``
    float32.  Dropless and row by row like the loop; the weight is applied
    before the second product (which is linear), in float32."""
    h = jnp.square(jax.nn.relu(matmul("nl,elf->enf", v, w1)))
    h = (h * w_held.T[..., None]).astype(v.dtype)
    return matmul("enf,efl->nl", h, w2)


def route(u, router, bias, cfg: NemotronHConfig):
    """u ``[N, d]`` float32 -> the ``k`` experts each token chose ``[N, k]``
    and their combine weights ``s p / sum(p)`` (float32)."""
    return sigmoid_route(u, router, bias, cfg.top_k,
                         cfg.routed_scaling_factor)


def moe(u, live, params, i: int, cfg: NemotronHConfig):
    """Expert layer ``i``'s share on this chip.  ``u [N, d]`` normed tokens
    in float32 (the router reads them as they are, the products in
    ``cfg.dtype``), ``live [N]`` bool (a padded or idle row chooses nothing:
    it touches no expert and is not counted) -> (the held experts' part
    through ``Wul`` ``[N, d]`` float32, the shared expert's ``[N, d]``
    float32, counts).  The layer's output is the sum of the two.

    Which way the held experts run is read off the SHAPES, never off the
    load.  When the rows fit one chunk and would, each choosing on its own,
    touch three in four of the held experts or more (a decode step of 64
    slots, 64 x 22 / 512 = 2.75 choices an expert: 94 %, 86 % as served)
    every held expert runs on every row in two batched products
    (``held_experts_dense``), which stream the layer's experts once at the
    memory's speed: 1.91 ms a layer on the v5e whatever is live, where a
    turn of ``expert_share.held_experts``' one-chunk form costs 19 us a
    touched expert (2.10 ms with the 110 of 128 it serves, 2.44 with all;
    under the products only below 100: PERF.md, PR 54; with its sort,
    gather and scatter-add, before PR 54, 3.04 ms with all, and ~600 turns a
    step, over which PR 39's profiler could not stop).  A prefill takes the
    loop, whose cost hardly grows with the rows up to 512 (4.7 ms at 256,
    4.8 at 512; from 8,192 rows it is the rows':
    ``expert_share.held_experts``) where the products' does (3.6, 4.9, and
    an intermediate of 2.8 GB at the 2048 rung).  ``N`` is the engine's slot
    count, so a replica of 64 slots with four callers streams all its
    experts too, where the loop would take a quarter of that (PERF.md, PR 39,
    has the timings).  Choosing by the live rows (``lax.cond`` on the touched
    count) would cure that and break what the engine promises: the two ways
    round differently (2e-3 of the result), so a request's greedy ids would
    depend on who else is being served.  Each way alone computes a row from
    that row only."""
    blocks, experts = params["blocks"]["moe"], params["experts"]
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("nemotron.moe"):
        sel, w = route(u, blocks["router"][i], blocks["router_bias"][i], cfg)
        held, hit, w_held = held_choices(
            sel, w, live, cfg.expert_offset, cfg.experts_held)
        ud = u.astype(dt)
        v = matmul("ne,el->nl", ud, blocks["w_dl"][i]).astype(dt)

        dense = runs_every_held_expert(u.shape[0], cfg.top_k,
                                       cfg.n_routed_experts)
        if dense:
            latent = held_experts_dense(
                v, w_held, experts["w1"][i], experts["w2"][i])
        else:  # [i, e] inside the loop: expert_share.py
            latent = held_experts(v, hit, w_held, lambda x, e: relu2(
                x, experts["w1"][i, e], experts["w2"][i, e]))
        routed = matmul("nl,le->ne", latent.astype(dt), blocks["w_ul"][i])
        shared = relu2(ud, blocks["ws1"][i], blocks["ws2"][i])
        return routed, shared, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit, looped=not dense),
        }


# -------------------------------------------------------------------- model
def run_layers(params, x, live, mamba, attend, cfg: NemotronHConfig):
    """The blocks of ``cfg.kinds`` over the float32 stream ``x [..., d]``.
    ``mamba(i, y)`` / ``attend(i, y)`` are the mixers of the ``i``-th layer
    of their kind on the normed state in ``cfg.dtype`` (a sequence's or one
    decode step's: the caller's, which keeps what the cache needs); ``live``
    has ``x``'s leading shape.  Every weight is taken as ``stack[i]`` where
    it is used (a layer's slice taken first is a copy of the layer)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    total = dict.fromkeys(COUNT_NAMES, jnp.zeros((), jnp.int32))
    seen = dict.fromkeys("M*E", 0)
    for kind in cfg.kinds:
        i = seen[kind]
        seen[kind] += 1
        if kind == "M":
            with jax.named_scope("nemotron.mamba"):
                y = rmsnorm(x, blocks["mamba"]["rms"][i], cfg.rms_eps)
                x = x + mamba(i, y.astype(dt))
        elif kind == "*":
            with jax.named_scope("nemotron.attn"):
                y = rmsnorm(x, blocks["attn"]["rms"][i], cfg.rms_eps)
                x = x + attend(i, y.astype(dt))
        else:
            with jax.named_scope("nemotron.moe"):
                u = rmsnorm(x, blocks["moe"]["rms"][i], cfg.rms_eps)  # f32
                u, rows = u.reshape(-1, u.shape[-1]), live.reshape(-1)
            routed, shared, counts = moe(u, rows, params, i, cfg)
            with jax.named_scope("nemotron.moe"):
                x = x + (routed + shared).reshape(x.shape)
                total = add_counts(total, counts)
    return x, total


def nemotron_h_forward(params, tokens, lengths, cfg: NemotronHConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S,
    d]``, what a cache holds of it: ``k`` / ``v`` ``[A, B, S, Hkv, D]``,
    ``conv`` ``[M, B, (K-1)(HP + 2GN)]`` and ``ssm`` ``[M, B, H, P, N]`` at each
    row's TRUE length, routing counts of the positions ``< length``)."""
    blocks = params["blocks"]
    with jax.named_scope("nemotron.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        live = jnp.arange(tokens.shape[1])[None] < lengths[:, None]
    kept = {"k": [], "v": [], "conv": [], "ssm": []}

    def mamba(i, y):
        out, conv, ssm = mamba_sequence(y, lengths, blocks["mamba"], i, cfg)
        kept["conv"].append(conv)
        kept["ssm"].append(ssm)
        return out

    def attend(i, y):
        out, k, v = attention_sequence(y, blocks["attn"], i, cfg)
        kept["k"].append(k)
        kept["v"].append(v)
        return out

    x, counts = run_layers(params, x, live, mamba, attend, cfg)
    with jax.named_scope("nemotron.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    stacked = {}
    for name, v in kept.items():
        if v:
            with jax.named_scope(CACHE_SCOPE[name]):
                stacked[name] = jnp.stack(v)
    return x, stacked, counts


def nemotron_h_apply(params, tokens, cfg: NemotronHConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "nemotron_h runs one chip's share of a layer; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = nemotron_h_forward(params, tokens, lengths, cfg)
    with jax.named_scope("nemotron.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def nemotron_h_loss(params, tokens, cfg: NemotronHConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = nemotron_h_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
