"""Granite-4.0-H-style hybrid decoder: Mamba-2 mixers nine layers in ten,
position-free grouped-query attention in the tenth, a SwiGLU after EVERY
mixer, four muP multipliers and a head that is the embedding table read a
second time.

Source of the sizes: ``huggingface.co/ibm-granite/granite-4.0-h-micro``
``config.json`` (``model_type`` ``granitemoehybrid``; its plain torch path is
``transformers``' ``GraniteMoeHybridMambaLayer.torch_forward``, which
``tests/test_granite_h.py`` holds the reference to).  Symbols and the
published sizes: ``d`` 2048; Mamba-2: ``H`` 64 heads of ``P`` 64 channels,
``N`` 128 the state's size, ``G`` 1 group, ``K`` 4 taps, ``Q`` 256 the chunk;
attention: ``Hq`` 32 query / ``Hkv`` 8 key-value heads of ``D`` 64; ``F`` 8192
the MLP's width.  Everything between two matrix products is float32; the
products read ``cfg.dtype`` and accumulate in float32 (``layers.matmul``);
the residual stream is float32, as in ``nemotron_h``.

**Model**: ``x_0 = embedding_multiplier E[token]`` (12).  Layer ``i``: ``x = x
+ residual_multiplier mixer_i(RMSNorm(x; w_i, 1e-5))``, then ``x = x +
residual_multiplier MLP_i(RMSNorm(x; w'_i, 1e-5))`` (0.22, both branches).
``logits = RMSNorm(x; w_f) E^T / logits_scaling`` (8): the table ``E`` is
``params["wte"]`` both times, there is no ``lm_head`` leaf.  The mixer's kind
is ``layer_pattern[i]``: ``M`` Mamba-2, ``*`` attention; ``n_layer`` layers
are taken from the FRONT of ``layer_pattern``.

**Mamba-2(u)**: ``nemotron_h``'s, the very functions (``mamba_project``,
``split_xbc``, ``ssd_chunked``, ``mamba_sequence``, ``mamba_output`` read
their sizes off the config by field name): ``z | xBC | dt = u W_in`` (widths
``HP | HP + 2GN | H``: 4096 | 4352 | 64, the published ``in_proj``'s column
blocks), ``xBC = silu(conv_K(xBC) + b)`` causal and depthwise, split ``x [H,
P] | B [N] | C [N]`` (one group: every head reads the same ``B``, ``C``); ``dt
= softplus(dt + dt_bias)`` (the published ``time_step_limit`` is (0, inf): no
clamp); ``A = -exp(A_log)``; a head: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
(x) B_t`` (``S [P, N]`` float32), ``y_t = S_t C_t + D x_t``; ``y =
RMSNorm_HP(y silu(z)) w`` (the gate BEFORE the norm, which at one group runs
over all ``HP`` channels: the grouped norm at ``G`` = 1), ``out = y W_out``.
A sequence runs ``ssd_chunked`` at ``Q``; a position with ``dt = 0`` leaves
the state alone (padding).  Decode (``granite_h_decode.py``) runs the
recurrence.

**Attention(u)**: ``q = u Wq [Hq, D]``, ``k = u Wk [Hkv, D]``, ``v = u Wv [Hkv,
D]``, no bias, NO positional term (``position_embedding_type`` ``nope``: the
Mamba-2 layers carry position); causal ``softmax(q k^T attention_multiplier)``
in float32 (1/64, NOT ``D^-1/2``); ``out = o Wo``.  The attention entries of
this tree scale by ``D^-1/2``, so ``q`` is multiplied by
``attention_multiplier / D^-1/2`` (0.125: a power of two, exact) in float32
before it is rounded, and they run as they are.

**MLP(u)**: ``g | h = u W_in [2 F]`` (the published ``input_linear``: the first
half the gate; held as its two column blocks ``w_gate``, ``w_up``), ``out =
(silu(g) h) W_out``.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of mixer
(``mamba``, ``attn``: as long as the pattern has layers of that kind) and a
stack ``mlp`` as long as the pattern itself (layer ``i``'s MLP is ``mlp[i]``);
a model of fewer layers reads the front of each.  Device operations carry
``jax.named_scope``s ``granite.embed``, ``granite.mamba`` and ``granite.attn``
(norm to residual, and what the cache keeps of them), ``granite.mlp`` and
``granite.head`` (final norm + vocabulary product).  Counted in the program:
``ssm_positions`` (positions a prefill's scans ran that were a prompt's own;
rows a decode step served) and ``ssm_chunk_positions`` (positions of the
chunks they ran, padding included; a decode step's rows).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (blocked_attention, ffn, layer_plan, matmul, rmsnorm,
                     scan_or_call)
from .mamba2 import mamba_sequence

PUBLISHED_PATTERN = "MMMMM*" + "MMMMMMMMM*" * 3 + "MMMM"
# a kind of mixer -> its stack under params["blocks"]
STACK = {"M": "mamba", "*": "attn"}
# a kind of mixer -> the scope of its operations
SCOPE = {"M": "granite.mamba", "*": "granite.attn"}
# a cache leaf -> the scope of the part that keeps it
CACHE_SCOPE = {"k": SCOPE["*"], "v": SCOPE["*"],
               "conv": SCOPE["M"], "ssm": SCOPE["M"]}


@dataclasses.dataclass(frozen=True)
class GraniteHConfig:
    vocab_size: int = 100352
    layer_pattern: str = PUBLISHED_PATTERN
    n_layer: int = 40  # layers taken from the front of ``layer_pattern``
    d_model: int = 2048
    # the Mamba-2 mixer's sizes under ``NemotronHConfig``'s names: its
    # functions read them by name
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 256
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    d_ff: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_pattern) - set(STACK):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             "is M (Mamba-2) or * (attention)")
        if not 0 < self.n_layer <= len(self.layer_pattern):
            raise ValueError(f"n_layer {self.n_layer} of a pattern of "
                             f"{len(self.layer_pattern)} layers")

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

    @property
    def query_scale(self) -> float:
        """What ``q`` is multiplied by so that entries that scale a score by
        ``D^-1/2`` scale it by ``attention_multiplier``."""
        return self.attention_multiplier * math.sqrt(self.head_dim)

    @classmethod
    def tiny(cls, **kw) -> "GraniteHConfig":
        for key, value in dict(
                vocab_size=512, layer_pattern="MM*MMM*M", n_layer=8,
                d_model=64, mamba_num_heads=8, mamba_head_dim=16,
                ssm_state_size=16, chunk_size=8, n_head=4, n_kv_head=2,
                head_dim=16, d_ff=128, attention_multiplier=0.03125).items():
            kw.setdefault(key, value)
        return cls(**kw)


def granite_h_init(key, cfg: GraniteHConfig):
    """Random weights with every stack as long as ``layer_pattern`` has
    layers of its kind (``mlp``: as the pattern).  ``A_log = log(U(1, 16))``,
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly in [0.001,
    0.1] (the published layer's ``time_step_min`` / ``max``), ``D = 1``."""
    sd = {"embed": 0.02, "in": 0.02, "out": 0.02, "conv": 0.3}
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nm, na = (cfg.layer_pattern.count(c) for c in "M*")
    nl, H, C = len(cfg.layer_pattern), cfg.mamba_num_heads, cfg.d_conv
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
            "mlp": {
                "rms": jnp.ones((nl, d), dt),
                "w_gate": init((nl, d, cfg.d_ff), sd["in"]),
                "w_up": init((nl, d, cfg.d_ff), sd["in"]),
                "w_down": init((nl, cfg.d_ff, d), sd["out"]),
            },
        },
        "rms_f": jnp.ones((d,), dt),
    }


def granite_h_param_axes():
    """Logical sharding axes (leading None = a stack's layer axis)."""
    return {
        "wte": P("vocab", "embed"),
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
            "mlp": {
                "rms": P(None, "norm"),
                "w_gate": P(None, "embed", "mlp"),
                "w_up": P(None, "embed", "mlp"),
                "w_down": P(None, "mlp", "embed"),
            },
        },
        "rms_f": P("norm"),
    }


# ---------------------------------------------------------------- attention
def attention_project(y, att, j: int, cfg: GraniteHConfig):
    """y ``[..., d]`` -> q ``[..., Hq, D]`` times ``query_scale`` (in float32,
    before it is rounded), k, v ``[..., Hkv, D]``, in y's dtype.  No
    positional term."""
    q = matmul("...e,ehd->...hd", y, att["wq"][j]) * cfg.query_scale
    k = matmul("...e,ekd->...kd", y, att["wk"][j])
    v = matmul("...e,ekd->...kd", y, att["wv"][j])
    return q.astype(y.dtype), k.astype(y.dtype), v.astype(y.dtype)


# -------------------------------------------------------------------- model
def embed(params, tokens, cfg: GraniteHConfig):
    """tokens ``[...]`` -> the float32 stream ``[..., d]``."""
    with jax.named_scope("granite.embed"):
        return (params["wte"][tokens].astype(jnp.float32)
                * cfg.embedding_multiplier)


def head(params, x, cfg: GraniteHConfig):
    """The final normed state ``[..., d]`` in ``cfg.dtype`` -> logits ``[...,
    V]`` float32: the embedding table a second time, over
    ``logits_scaling``."""
    return matmul("...e,ve->...v", x, params["wte"]) / cfg.logits_scaling


def block(params, x, kind: str, i: int, layer, mix, cfg: GraniteHConfig):
    """Layer ``layer`` (the ``i``-th of its ``kind``) on the float32 stream
    ``x [..., d]``.  ``mix(y)`` is the layer's mixer on the normed state in
    ``cfg.dtype`` (a sequence's or one decode step's: the caller's, which
    keeps what the cache needs) -> ``[..., d]`` float32.  Every weight is
    taken as ``stack[i]`` where it is used (a layer's slice taken first is a
    copy of the layer)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    with jax.named_scope(SCOPE[kind]):
        y = rmsnorm(x, blocks[STACK[kind]]["rms"][i], cfg.rms_eps)
        x = x + cfg.residual_multiplier * mix(y.astype(dt))
    with jax.named_scope("granite.mlp"):
        w = blocks["mlp"]
        y = rmsnorm(x, w["rms"][layer], cfg.rms_eps).astype(dt)
        return x + cfg.residual_multiplier * ffn(
            y, w["w_gate"][layer], w["w_up"][layer], w["w_down"][layer])


def granite_h_forward(params, tokens, lengths, cfg: GraniteHConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S, d]``,
    what a cache holds of it: ``k`` / ``v`` ``[A, B, S, Hkv, D]``, ``conv``
    ``[M, B, (K-1)(HP + 2GN)]`` and ``ssm`` ``[M, B, H, P, N]`` at each row's
    TRUE length, counts).  Rows at or beyond the longest prompt's last query
    block carry no attention (``blocked_attention``).  A run of layers of one
    kind is ONE loop's body and a group of runs that repeats is a loop of
    those (``layer_plan``; ``lax.scan`` in ``lax.scan``, as
    ``laguna.laguna_forward``): a sequence's products are bound by compute,
    so a layer's weights may be sliced out of their stacks as they are
    needed, and a program of forty layers is as long as five."""
    blocks = params["blocks"]
    x = embed(params, tokens, cfg)
    with jax.named_scope("granite.attn"):
        longest = jnp.max(lengths)

    def one_run(x, kind, length, i0, layer0):
        """``length`` layers of one kind from the ``i0``-th of the kind,
        layer ``layer0`` -> (x, what the cache keeps of each, stacked
        ``[length, ...]``)."""
        def one_layer(x, t):
            i, held = i0 + t, []

            def mamba(y):
                out, *state = mamba_sequence(
                    y, lengths, blocks["mamba"], i, cfg)
                held.extend(state)  # conv, ssm
                return out

            def attend(y):
                q, k, v = attention_project(y, blocks["attn"], i, cfg)
                held.extend((k, v))
                o = blocked_attention(q, k, v, longest)
                return matmul("bshd,hde->bse", o.astype(y.dtype),
                              blocks["attn"]["wo"][i])

            x = block(params, x, kind, i, layer0 + t,
                      mamba if kind == "M" else attend, cfg)
            return x, tuple(held)

        return scan_or_call(one_layer, x, length)

    kept = {kind: [] for kind in STACK}
    seen, layer = dict.fromkeys(STACK, 0), 0
    for group, repeats in layer_plan(cfg.kinds):
        strides = {kind: sum(n for k, n in group if k == kind)
                   for kind in STACK}
        span = sum(n for _, n in group)

        def one_period(x, p):  # traced at once: the loop's values as now
            held = {kind: [] for kind in STACK}
            at = dict.fromkeys(STACK, 0)
            inside = 0
            for kind, n in group:
                x, part = one_run(
                    x, kind, n, seen[kind] + p * strides[kind] + at[kind],
                    layer + p * span + inside)
                held[kind].append(part)
                at[kind] += n
                inside += n
            return x, {kind: tuple(jnp.concatenate(a) for a in zip(*parts))
                       for kind, parts in held.items() if parts}

        x, held = scan_or_call(one_period, x, repeats)
        for kind, part in held.items():  # [repeats, layers of the kind, ...]
            with jax.named_scope(SCOPE[kind]):
                kept[kind].append(tuple(
                    a.reshape((-1,) + a.shape[2:]) for a in part))
        for kind in STACK:
            seen[kind] += repeats * strides[kind]
        layer += repeats * span

    with jax.named_scope("granite.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    cache = {}
    for kind, names in (("M", ("conv", "ssm")), ("*", ("k", "v"))):
        if kept[kind]:
            for name, parts in zip(names, zip(*kept[kind])):
                with jax.named_scope(CACHE_SCOPE[name]):
                    cache[name] = jnp.concatenate(parts)
    bsz, s = tokens.shape
    with jax.named_scope("granite.mamba"):
        counts = {
            "ssm_positions": lengths.sum().astype(jnp.int32),
            "ssm_chunk_positions": jnp.asarray(
                bsz * -(-s // cfg.chunk_size) * cfg.chunk_size, jnp.int32)}
    return x, cache, counts


def granite_h_apply(params, tokens, cfg: GraniteHConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``."""
    if mesh is not None:
        raise NotImplementedError("granite_h runs on one chip; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = granite_h_forward(params, tokens, lengths, cfg)
    with jax.named_scope("granite.head"):
        return head(params, x, cfg)


def granite_h_loss(params, tokens, cfg: GraniteHConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = granite_h_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
