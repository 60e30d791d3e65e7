"""Olmo-Hybrid-style decoder: gated-delta-rule linear attention (a MATRIX of
state a head, updated by a delta rule) three layers in four, beside full
softmax attention, by a pattern string.

Source of the sizes: ``huggingface.co/allenai/Olmo-Hybrid-7B`` ``config.json``
(``model_type`` ``olmo_hybrid``; its ``linear_*`` keys are ``qwen3_next``'s).
Symbols: ``d`` d_model; linear attention: ``H`` heads, keys of ``dk`` and
values of ``dv`` a head, ``K`` the convolution's taps, ``C`` the chunk; full
attention: ``Hf`` query = key-value heads of ``D``; ``F`` the MLP's width.
No bias anywhere.  ``u`` is a mixer's input; everything between two matrix
products is float32 (``layers.matmul``: the products read ``cfg.dtype`` and
accumulate in float32), and so are the residual stream and the state.

**Linear attention (gated delta net)**: ``[q~ | k~ | v~] = u Wqkv`` (widths
``H dk | H dk | H dv``: the three projections side by side), ``z = u Wg [H
dv]``, ``a = u Wa [H]``, ``b = u Wb [H]``.  ``(q, k, v) = silu(conv_K(q~ | k~
| v~))``, causal and depthwise (``out_t = sum_j w_j x_{t-K+1+j}``).  A head:
``q = q / sqrt(|q|^2 + 1e-6) * dk^-1/2``, ``k = k / sqrt(|k|^2 + 1e-6)``;
``beta = 2 sigmoid(b)`` (``allow_neg_eigval``; ``sigmoid(b)`` without): past 1
the state's eigenvalue along ``k`` is negative; ``g = -exp(A_log) softplus(a +
dt_bias)``, ``alpha = exp(g)``.  State ``S [dk, dv]`` float32: ``S' = alpha_t
S_{t-1}``; ``S_t = S' + k_t (x) beta_t (v_t - S'^T k_t)``; ``o_t = S_t^T q_t``.
``y = RMSNorm_dv(o) * w * silu(z)`` a head (the norm BEFORE the gate), ``out
= y Wo``.

A sequence runs the chunked form (``delta_rule.delta_chunked``, which
Kimi-Linear's vector gate shares).  With ``gamma_i`` the
running product of ``alpha`` inside a chunk of ``C``: ``A = strictly_lower(
diag(beta) (K K^T * gamma_i / gamma_j))``, ``[W | U] = (I + A)^-1 diag(beta)
[K * gamma | V]`` (one triangular solve a head a chunk: the WY / UT
transform; the rule's own products are float32 like the solve, not
``cfg.dtype``); then chunk by chunk against the carried state: ``V' = U - W
S``, ``O = (Q * gamma) S + lower(Q K^T * gamma_i / gamma_j) V'``, ``S <-
gamma_C S + (K * gamma_C / gamma)^T V'``.  A position with ``beta = 0`` and
``g = 0`` neither writes nor decays the state: that is how padding (``t >=
length``) is left out of it.  Decode (``olmo_hybrid_decode.py``) runs one
step of the recurrence itself.

**Full attention** (Olmo 3's): ``q = RMSNorm(u Wq)``, ``k = RMSNorm(u Wk)``,
each over the WHOLE projection ``[Hf D]``, ``v = u Wv``; causal softmax in
float32 at ``D^-1/2``, no positional term (``rope_theta`` null: the linear
layers carry position), ``Wo``.  **MLP**: ``(silu(u W1) * (u W3)) W2``.

**Model**: the layer's kind is ``layer_pattern[i]``: ``L`` linear, ``F`` full;
``n_layer`` layers are taken from the FRONT of ``layer_pattern``.  A linear
layer is pre-norm (``x = x + GDN(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``,
the Gated DeltaNet paper's wiring, arXiv:2412.06464), a full layer Olmo 3's
reordered norm (``x = x + RMSNorm(Attn(x))``, ``x = x + RMSNorm(MLP(x))``).
Then a final RMSNorm and an untied head.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of layer
(``linear``, ``full``; each with its MLP), as long as the pattern has layers
of that kind; a model of fewer layers reads the front of each stack.  Device
operations carry ``jax.named_scope``s ``olmo.embed``, ``olmo.delta`` (a linear
layer's mixer with its norm, residual and the state it leaves), ``olmo.attn``
(a full layer's, with the cache write), ``olmo.mlp`` and ``olmo.head`` (final
norm + vocabulary product).  Counted in the program: ``delta_positions`` (true positions a
prefill scanned; rows a decode step served) and ``delta_chunk_positions``
(positions of the chunks it ran; a decode step's rows).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .delta_rule import delta_chunked, pack_state, unpack_state  # noqa: F401
from .layers import (blocked_attention, conv_sequence, ffn, matmul, rmsnorm,
                     scan_or_call)

PUBLISHED_PATTERN = "LLLF" * 8
# a kind of layer -> its stack under params["blocks"]
STACK = {"L": "linear", "F": "full"}
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    layer_pattern: str = PUBLISHED_PATTERN
    n_layer: int = 32  # layers taken from the front of ``layer_pattern``
    d_model: int = 3840
    n_head: int = 30  # full attention: as many key-value heads as queries
    head_dim: int = 128
    linear_num_heads: int = 30  # key heads = value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    chunk_size: int = 32
    d_ff: int = 11008
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_pattern) - set(STACK):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             "is L (linear attention) or F (full attention)")
        if not 0 < self.n_layer <= len(self.layer_pattern):
            raise ValueError(f"n_layer {self.n_layer} of a pattern of "
                             f"{len(self.layer_pattern)} layers")

    @property
    def kinds(self) -> str:
        """The kinds of the layers this model runs."""
        return self.layer_pattern[:self.n_layer]

    @property
    def d_key(self) -> int:
        return self.linear_num_heads * self.linear_key_head_dim

    @property
    def d_value(self) -> int:
        return self.linear_num_heads * self.linear_value_head_dim

    @property
    def d_conv(self) -> int:
        """Channels the convolution runs over: ``q | k | v``."""
        return 2 * self.d_key + self.d_value

    @property
    def state_pack(self) -> int:
        """Heads whose states share a row of the cache's ``state`` leaf
        (``delta_rule.pack_state``): as many as make the row's lanes a
        multiple of the TPU's 128 (two heads of 192), one where the heads do
        not divide."""
        pack = 128 // math.gcd(self.linear_value_head_dim, 128)
        return pack if self.linear_num_heads % pack == 0 else 1

    @classmethod
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        for key, value in dict(
                vocab_size=512, layer_pattern="FLLFLL", n_layer=6, d_model=64,
                n_head=4, head_dim=16, linear_num_heads=4,
                linear_key_head_dim=8, linear_value_head_dim=64,
                chunk_size=8, d_ff=128).items():
            kw.setdefault(key, value)
        return cls(**kw)


def olmo_hybrid_init(key, cfg: OlmoHybridConfig):
    """Random weights with every stack as long as ``layer_pattern`` has
    layers of its kind.  ``A_log = log(U(1, 16))``, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in [0.001, 0.1] (the Gated
    DeltaNet's init, Mamba-2's)."""
    sd = {"embed": 0.02, "in": 0.02, "out": 0.02, "conv": 0.3}
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nl, nf = (cfg.layer_pattern.count(c) for c in "LF")
    H, F = cfg.linear_num_heads, cfg.d_ff
    keys = iter(jax.random.split(key, 24))

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def mlp(n):
        return {"rms_mlp": jnp.ones((n, d), dt),
                "w_gate": init((n, d, F), sd["in"]),
                "w_up": init((n, d, F), sd["in"]),
                "w_down": init((n, F, d), sd["out"])}

    step = jnp.exp(jax.random.uniform(
        next(keys), (nl, H), minval=math.log(1e-3), maxval=math.log(0.1)))
    return {
        "wte": init((cfg.vocab_size, d), sd["embed"]),
        "blocks": {
            "linear": {
                "rms_mix": jnp.ones((nl, d), dt),
                "w_qkv": init((nl, d, cfg.d_conv), sd["in"]),
                "w_g": init((nl, d, cfg.d_value), sd["in"]),
                "w_a": init((nl, d, H), sd["in"]),
                "w_b": init((nl, d, H), sd["in"]),
                "conv_w": init((nl, cfg.conv_kernel, cfg.d_conv), sd["conv"],
                               jnp.float32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (nl, H), minval=1.0, maxval=16.0)),
                "norm": jnp.ones((nl, cfg.linear_value_head_dim), dt),
                "w_o": init((nl, cfg.d_value, d), sd["out"]),
                **mlp(nl),
            },
            "full": {
                "rms_mix": jnp.ones((nf, d), dt),
                "wq": init((nf, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "wk": init((nf, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "wv": init((nf, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "q_norm": jnp.ones((nf, cfg.n_head, cfg.head_dim), dt),
                "k_norm": jnp.ones((nf, cfg.n_head, cfg.head_dim), dt),
                "wo": init((nf, cfg.n_head, cfg.head_dim, d), sd["out"]),
                **mlp(nf),
            },
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), sd["in"]),
    }


def olmo_hybrid_param_axes():
    """Logical sharding axes (leading None = a kind's layer-stack axis)."""
    mlp = {"rms_mlp": P(None, "norm"),
           "w_gate": P(None, "embed", "mlp"),
           "w_up": P(None, "embed", "mlp"),
           "w_down": P(None, "mlp", "embed")}
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "linear": {
                "rms_mix": P(None, "norm"),
                "w_qkv": P(None, "embed", "mlp"),
                "w_g": P(None, "embed", "mlp"),
                "w_a": P(None, "embed", "heads"),
                "w_b": P(None, "embed", "heads"),
                "conv_w": P(None, None, "mlp"),
                "dt_bias": P(None, "heads"),
                "a_log": P(None, "heads"),
                "norm": P(None, None),
                "w_o": P(None, "mlp", "embed"),
                **mlp,
            },
            "full": {
                "rms_mix": P(None, "norm"),
                "wq": P(None, "embed", "heads", "kv"),
                "wk": P(None, "embed", "heads", "kv"),
                "wv": P(None, "embed", "heads", "kv"),
                "q_norm": P(None, "heads", "kv"),
                "k_norm": P(None, "heads", "kv"),
                "wo": P(None, "heads", "kv", "embed"),
                **mlp,
            },
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# --------------------------------------------------------- linear attention
def delta_project(y, m, i: int, cfg: OlmoHybridConfig):
    """y ``[..., d]`` in ``cfg.dtype`` -> ``qkv [..., 2 H dk + H dv]`` (before
    the convolution), the gate ``z [..., H dv]``, the log of the decay ``g
    [..., H]`` (<= 0) and ``beta [..., H]``, float32."""
    qkv = matmul("...e,ef->...f", y, m["w_qkv"][i])
    z = matmul("...e,ef->...f", y, m["w_g"][i])
    a = matmul("...e,eh->...h", y, m["w_a"][i])
    b = matmul("...e,eh->...h", y, m["w_b"][i])
    g = -jnp.exp(m["a_log"][i]) * jax.nn.softplus(a + m["dt_bias"][i])
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
    return qkv, z, g, beta


def split_heads(conv, cfg: OlmoHybridConfig):
    """The convolution's output after its silu ``[..., 2 H dk + H dv]`` ->
    ``q`` (normalised, times ``dk^-1/2``), ``k`` (normalised) ``[..., H, dk]``
    and ``v [..., H, dv]``."""
    lead, h = conv.shape[:-1], cfg.linear_num_heads
    q, k, v = jnp.split(conv, [cfg.d_key, 2 * cfg.d_key], axis=-1)
    q, k, v = (a.reshape(*lead, h, -1) for a in (q, k, v))
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS)
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    return q * cfg.linear_key_head_dim ** -0.5, k, v


def delta_output(o, z, m, i: int, cfg: OlmoHybridConfig):
    """``(RMSNorm_dv(o) * w * silu(z)) Wo``: o ``[..., H, dv]`` float32, z
    ``[..., H dv]`` -> ``[..., d]`` float32."""
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps)
    o = o * m["norm"][i].astype(jnp.float32)
    y = o.reshape(z.shape) * jax.nn.silu(z)
    return matmul("...f,fe->...e", y.astype(jnp.dtype(cfg.dtype)),
                  m["w_o"][i])


def delta_sequence(y, lengths, m, i: int, cfg: OlmoHybridConfig):
    """The gated delta net over whole sequences.  y ``[B, S, d]``, lengths
    ``[B]`` -> (``[B, S, d]`` float32, the convolution's state ``[B, (K-1)(2 H
    dk + H dv)]`` = its last ``K-1`` TRUE inputs side by side, oldest first,
    the state after position ``length - 1``, packed).  Positions ``>=
    length`` change neither."""
    qkv, z, g, beta = delta_project(y, m, i, cfg)
    live = jnp.arange(y.shape[1])[None, :, None] < lengths[:, None, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    conv, conv_state = conv_sequence(qkv, lengths, m["conv_w"], i)
    q, k, v = split_heads(jax.nn.silu(conv), cfg)
    o, state = delta_chunked(q, k, v, g, beta, cfg.chunk_size)
    return (delta_output(o, z, m, i, cfg),
            conv_state.reshape(y.shape[0], -1),
            pack_state(state, cfg.state_pack))


# ----------------------------------------------------------- full attention
def attention_project(x, att, i: int, cfg: OlmoHybridConfig):
    """x ``[..., d]`` -> q, k (each RMS-normalised over its WHOLE projection
    ``[H, D]``) and v ``[..., H, D]`` in x's dtype.  No positional term."""
    def normed(a, w):
        a = a * jax.lax.rsqrt(
            (a * a).mean((-2, -1), keepdims=True) + cfg.rms_eps)
        return a * w.astype(jnp.float32)

    q = normed(matmul("...e,ehd->...hd", x, att["wq"][i]), att["q_norm"][i])
    k = normed(matmul("...e,ehd->...hd", x, att["wk"][i]), att["k_norm"][i])
    v = matmul("...e,ehd->...hd", x, att["wv"][i])
    return q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)


# -------------------------------------------------------------------- model
def block(params, x, kind: str, i: int, mix, cfg: OlmoHybridConfig):
    """Layer ``i`` of its ``kind`` on the float32 stream ``x [..., d]``.
    ``mix(y)`` is the layer's mixer on its input in ``cfg.dtype`` (a
    sequence's or one decode step's: the caller's, which keeps what the cache
    needs) -> ``[..., d]`` float32.  Every weight is taken as ``stack[i]``
    where it is used (a layer's slice taken first is a copy of the layer)."""
    w, dt = params["blocks"][STACK[kind]], jnp.dtype(cfg.dtype)

    def mlp(u):
        return ffn(u.astype(dt), w["w_gate"][i], w["w_up"][i], w["w_down"][i])

    if kind == "L":  # pre-norm
        with jax.named_scope("olmo.delta"):
            x = x + mix(rmsnorm(x, w["rms_mix"][i], cfg.rms_eps).astype(dt))
        with jax.named_scope("olmo.mlp"):
            return x + mlp(rmsnorm(x, w["rms_mlp"][i], cfg.rms_eps))
    with jax.named_scope("olmo.attn"):  # the norm on the mixer's OUTPUT
        x = x + rmsnorm(mix(x.astype(dt)), w["rms_mix"][i], cfg.rms_eps)
    with jax.named_scope("olmo.mlp"):
        return x + rmsnorm(mlp(x), w["rms_mlp"][i], cfg.rms_eps)


def layer_plan(kinds: str):
    """``kinds`` folded: ``(period, repeats, runs)`` with ``kinds == period *
    repeats`` for the SHORTEST such period, and the period's runs of one kind
    ``(kind, layers of that kind before the run in the period, length)``.
    The published 32 layers are ``LLLF x 8``, the cell's twelve ``FLLL x 3``:
    two runs, so two layer bodies in a program whatever the depth."""
    n = len(kinds)
    size = next(p for p in range(1, n + 1)
                if n % p == 0 and kinds[:p] * (n // p) == kinds)
    period, runs, seen = kinds[:size], [], dict.fromkeys(STACK, 0)
    for kind in period:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return period, n // size, [tuple(run) for run in runs]


def olmo_hybrid_forward(params, tokens, lengths, cfg: OlmoHybridConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S, d]``,
    what a cache holds of it: ``k`` / ``v`` ``[Lf, B, S, H, D]``, ``conv``
    ``[Ll, B, (K-1)(2 H dk + H dv)]`` and ``state`` ``[Ll, B, H / p, dk, p dv]``
    at each row's TRUE length, counts).  Rows at or beyond the longest
    prompt's last query block carry no attention (``blocked_attention``).  A
    run of layers of one kind is ONE loop's body and the period that repeats
    is a loop of those (``layer_plan``; ``lax.scan`` in ``lax.scan``, as
    ``laguna.laguna_forward``): a program is as long as the kinds that
    differ (four rungs are compiled a replica, and the chip's compile cache
    holds ~190 MiB for every cell's programs: written out, the top rung
    alone is 59 MB of code; folded, 12.6-16.9 MB a rung), and a layer's
    weights are sliced out of their stacks inside the product that reads
    them (no copy: ``tests/test_tpu_compile.py``)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    with jax.named_scope("olmo.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
    with jax.named_scope("olmo.attn"):
        longest = jnp.max(lengths)
    period, repeats, runs = layer_plan(cfg.kinds)

    def one_run(x, p, kind, first, length):
        """``length`` layers of one kind in period ``p`` -> (x, what the
        cache keeps of each, stacked ``[length, ...]``)."""
        def one_layer(x, t):
            i = p * period.count(kind) + first + t
            held = []

            def delta(y):
                out, *state = delta_sequence(
                    y, lengths, blocks["linear"], i, cfg)
                held.extend(state)  # conv, state
                return out

            def attend(y):
                q, k, v = attention_project(y, blocks["full"], i, cfg)
                held.extend((k, v))
                o = blocked_attention(q, k, v, longest)
                return matmul("bshd,hde->bse", o.astype(dt),
                              blocks["full"]["wo"][i])

            x = block(params, x, kind, i, delta if kind == "L" else attend,
                      cfg)
            return x, tuple(held)

        return scan_or_call(one_layer, x, length)

    def one_period(x, p):
        held = {kind: [] for kind in STACK}
        for kind, first, length in runs:
            x, kept = one_run(x, p, kind, first, length)
            held[kind].append(kept)
        return x, {kind: tuple(jnp.concatenate(part) for part in zip(*kept))
                   for kind, kept in held.items() if kept}

    x, held = scan_or_call(one_period, x, repeats)
    with jax.named_scope("olmo.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(dt)
    kept = {}  # [repeats, a period's layers of the kind, ...] -> [layers, ...]
    for kind, names in (("L", ("conv", "state")), ("F", ("k", "v"))):
        with jax.named_scope("olmo.delta" if kind == "L" else "olmo.attn"):
            for name, a in zip(names, held.get(kind, ())):
                kept[name] = a.reshape((-1,) + a.shape[2:])
    bsz, s = tokens.shape
    with jax.named_scope("olmo.delta"):
        counts = {
            "delta_positions": lengths.sum().astype(jnp.int32),
            "delta_chunk_positions": jnp.asarray(
                bsz * -(-s // cfg.chunk_size) * cfg.chunk_size, jnp.int32)}
    return x, kept, counts


def olmo_hybrid_apply(params, tokens, cfg: OlmoHybridConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``."""
    if mesh is not None:
        raise NotImplementedError("olmo_hybrid runs on one chip; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = olmo_hybrid_forward(params, tokens, lengths, cfg)
    with jax.named_scope("olmo.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def olmo_hybrid_loss(params, tokens, cfg: OlmoHybridConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = olmo_hybrid_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
