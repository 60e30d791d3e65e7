"""MiniCPM-SALA-style hybrid decoder: lightning linear attention three layers
in four, position-free SPARSE grouped-query attention in the fourth (a query
reads the 64 blocks of 64 keys it chose once its context reaches 8192), an
output gate on both mixers, a SwiGLU after every mixer and three muP scales.

Source of the sizes: ``huggingface.co/openbmb/MiniCPM-SALA`` ``config.json``
(``model_type`` ``minicpm_sala``); the sparse layers' seven sizes are
MiniCPM4's published ``sparse_config`` (InfLLM-V2), which the row's
``mixer_types: minicpm4`` names; the lightning layers' decay slopes are the
lightning-attention family's (TransNormerLLM, MiniMax-01).  Symbols and the
published sizes: ``d`` 4096; lightning: ``H`` 32 heads = 32 key-value heads of
``D`` 128; sparse: ``Hq`` 32 query / ``Hkv`` 2 key-value heads of 128 (a group
is 16 query heads); ``F`` 16384 the MLP's width; ``N`` 32 the PUBLISHED depth.
Everything between two matrix products is float32; the products read
``cfg.dtype`` and accumulate in float32 (``layers.matmul``); the residual
stream is float32, as in ``nemotron_h``.

**Model**: ``x_0 = scale_emb E[token]`` (12).  Layer ``l`` (its PUBLISHED
index, ``first_layer + `` its place here), with ``r = scale_depth / sqrt(N)``
(0.2475): ``x = x + r mixer_l(RMSNorm(x; w_l, 1e-6))``, then ``x = x + r
MLP_l(RMSNorm(x; w'_l, 1e-6))``.  ``logits = W_head (RMSNorm(x; w_f) / (d /
dim_model_base))`` (16).  The mixer's kind is ``layer_pattern[i]``: ``L``
lightning, ``S`` sparse; ``n_layer`` layers are taken from the FRONT of
``layer_pattern``.

**Lightning(u)**: ``q, k, v = u Wq, u Wk, u Wv`` as ``[H, D]``; ``q, k =
RMSNorm_D(q) w_q, RMSNorm_D(k) w_k`` (``qk_norm``: over a head's channels,
one learned ``[D]`` a layer); rotary on all ``D`` channels of ``q`` and ``k``,
base 1e4 (``layers.rope``); a head: ``S_t = lambda_h S_{t-1} + v_t (x) k_t``
from ``S = 0`` (``S [D, D]`` float32), ``o_t = S_t q_t / sqrt(D)``; ``o =
RMSNorm_D(o) w_o`` (``use_output_norm``: a head's channels, learned ``[H,
D]``); ``o = o sigmoid(u Wg)`` (``use_output_gate``); ``out = o Wo``.
``lambda_h = exp(-s_h)``, ``s_h = 2^(-8 h / H) (1 - l / (N - 1) + 1e-5)``, ``h``
= 1..H (``slopes``).  That recurrence IS Mamba-2's with ``x = v``, ``dt = 1``,
``a = -s_h``, ``B = k``, ``C = q / sqrt(D)``, no skip and as many groups as
heads: a sequence runs ``mamba2.ssd_chunked`` (``dt = 0`` beyond a row's
true length: the state passes through), a decode step
``ops/mamba_update.py``; there is no second scan and no second update in the
tree.

**Sparse(u)**: ``q = u Wq [Hq, D]``, ``k, v = u Wk, u Wv [Hkv, D]``; ``qk_norm``
as above; NO positional term (the lightning layers carry position); causal
``softmax(q k^T / sqrt(D))`` in float32 over the set ``R(t)``; ``o = o
sigmoid(u Wg)`` (``attn_use_output_gate``); ``out = o Wo``.  ``R(t)``: where
the call that computes position ``t`` spans fewer than ``dense_len``
positions (a prefill of a prompt under 8192; a decode step at a context ``t
+ 1`` under 8192), all of ``[0, t]``.  Otherwise the blocks ``choose_blocks``
picks (``chosen_blocks``: the same set as a mask, a prefill's form): pooled
keys ``Kbar_j = mean(K[stride j : stride j + kernel))`` (32 keys every 16) a
key-value head, visible to ``t`` when ``stride j + kernel - 1 <= t``; ``p = softmax_j(q . Kbar_j / sqrt(D))`` over the visible ``j`` a query
head, in float32; summed over the group's 16 query heads; a block of 64
positions scores the max over the windows that overlap it; block 0
(``init_blocks``) and the blocks that overlap the last ``window_size`` (2048)
positions score infinity; ``R(t)`` = the ``topk`` (64) highest blocks (ties to
the lower block), clipped at ``t``.

**MLP(u)**: ``(silu(u W_gate) u W_up) W_down``.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of mixer
(``sparse``, ``lightning``: as long as the pattern has layers of that kind)
and a stack ``mlp`` as long as the pattern itself; a model of fewer layers
reads the front of each.  Device operations carry ``jax.named_scope``s
``sala.embed``, ``sala.lightning`` and ``sala.attn`` (norm to residual, and
what the cache keeps of them; ``sala.select`` inside ``sala.attn``: pooled
scores, block maximum, top-k), ``sala.mlp`` and ``sala.head`` (final norm +
vocabulary product).  Counted in the program: ``lightning_positions``
(positions a prefill's scans ran that were a prompt's own; rows a decode step
served), ``lightning_chunk_positions`` (positions of the chunks they ran,
padding included), ``sparse_read_positions`` and ``sparse_live_positions``
(decode: positions of the blocks the sparse layers' rows listed, and those
rows' contexts; a prefill reads by a mask and counts its own positions as
both).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.decode_attention import NEG_INF
from .layers import (blocked_attention, ffn, layer_plan, matmul, rmsnorm, rope,
                     scan_or_call)
from .mamba2 import ssd_chunked

# a kind of mixer -> its stack under params["blocks"]
STACK = {"S": "sparse", "L": "lightning"}
# a kind of mixer -> the scope of its operations
SCOPE = {"S": "sala.attn", "L": "sala.lightning"}
# a cache leaf -> the scope of the part that keeps it
CACHE_SCOPE = {"k": SCOPE["S"], "v": SCOPE["S"], "kbar": SCOPE["S"],
               "state": SCOPE["L"]}


@dataclasses.dataclass(frozen=True)
class MinicpmSalaConfig:
    vocab_size: int = 73448
    layer_pattern: str = "SLLLLLLSSLLL"
    n_layer: int = 12  # layers taken from the front of ``layer_pattern``
    first_layer: int = 9  # the published index of ``layer_pattern[0]``
    published_layers: int = 32  # N: what ``r`` and the slopes read
    d_model: int = 4096
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    chunk_size: int = 256  # of the lightning layers' chunked scan
    d_ff: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # MiniCPM4's sparse_config (InfLLM-V2)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_pattern) - set(STACK):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a layer "
                             "is S (sparse attention) or L (lightning)")
        if not 0 < self.n_layer <= len(self.layer_pattern):
            raise ValueError(f"n_layer {self.n_layer} of a pattern of "
                             f"{len(self.layer_pattern)} layers")
        if self.first_layer + len(self.layer_pattern) > self.published_layers:
            raise ValueError("the pattern ends beyond the published depth")
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.dense_len % self.block_size
                or self.dense_len < self.topk * self.block_size):
            raise ValueError(
                "a pooled window is whole strides, a block whole strides, "
                "dense_len whole blocks and at least topk of them")

    @property
    def kinds(self) -> str:
        """The kinds of the layers this model runs."""
        return self.layer_pattern[:self.n_layer]

    @property
    def residual_scale(self) -> float:
        """``r``: what a branch is multiplied by where it joins the stream."""
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def logit_divisor(self) -> float:
        return self.d_model / self.dim_model_base

    @property
    def listed_blocks(self) -> int:
        """Entries of a decode row's list of blocks: a context under
        ``dense_len`` whole, or ``topk``; whole turns of ``topk``."""
        return -(-max(self.dense_len // self.block_size, self.topk)
                 // self.topk) * self.topk

    @classmethod
    def tiny(cls, **kw) -> "MinicpmSalaConfig":
        """The CPU tests' widths.  ``topk`` 6 where the published ratios
        would give 4: block 0 and the blocks over the last ``window_size``
        positions are 3-4 blocks here, and a selection that ranks nothing
        tests nothing (published: 34 of the 64 are forced)."""
        for key, value in dict(
                vocab_size=512, layer_pattern="SLLSSL", n_layer=6,
                first_layer=9, d_model=64, n_head=4, n_kv_head=2, head_dim=16,
                lightning_heads=4, lightning_head_dim=16, chunk_size=8,
                d_ff=128, dim_model_base=16, kernel_size=4, kernel_stride=2,
                block_size=8, topk=6, window_size=16, dense_len=64).items():
            kw.setdefault(key, value)
        return cls(**kw)


def slopes(cfg: MinicpmSalaConfig, layer):
    """``s_h [H]`` float32 of published layer ``layer`` (may be traced):
    ``2^(-8 h / H) (1 - layer / (N - 1) + 1e-5)``, ``h`` = 1..H."""
    h = jnp.arange(1, cfg.lightning_heads + 1, dtype=jnp.float32)
    depth = 1.0 - jnp.asarray(layer, jnp.float32) / (
        cfg.published_layers - 1) + 1e-5
    return jnp.exp2(-8.0 * h / cfg.lightning_heads) * depth


def minicpm_sala_init(key, cfg: MinicpmSalaConfig):
    """Random weights with every stack as long as ``layer_pattern`` has
    layers of its kind (``mlp``: as the pattern)."""
    sd = {"embed": 0.02, "in": 0.02, "out": 0.02}
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    ns, nl = (cfg.layer_pattern.count(c) for c in "SL")
    n = len(cfg.layer_pattern)
    H, D = cfg.lightning_heads, cfg.lightning_head_dim
    keys = iter(jax.random.split(key, 24))

    def init(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    return {
        "wte": init((cfg.vocab_size, d), sd["embed"]),
        "blocks": {
            "sparse": {
                "rms": jnp.ones((ns, d), dt),
                "wq": init((ns, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "wk": init((ns, d, cfg.n_kv_head, cfg.head_dim), sd["in"]),
                "wv": init((ns, d, cfg.n_kv_head, cfg.head_dim), sd["in"]),
                "wg": init((ns, d, cfg.n_head, cfg.head_dim), sd["in"]),
                "wo": init((ns, cfg.n_head, cfg.head_dim, d), sd["out"]),
                "q_norm": jnp.ones((ns, cfg.head_dim), dt),
                "k_norm": jnp.ones((ns, cfg.head_dim), dt),
            },
            "lightning": {
                "rms": jnp.ones((nl, d), dt),
                "wq": init((nl, d, H, D), sd["in"]),
                "wk": init((nl, d, H, D), sd["in"]),
                "wv": init((nl, d, H, D), sd["in"]),
                "wg": init((nl, d, H, D), sd["in"]),
                "wo": init((nl, H, D, d), sd["out"]),
                "q_norm": jnp.ones((nl, D), dt),
                "k_norm": jnp.ones((nl, D), dt),
                "o_norm": jnp.ones((nl, H, D), dt),
            },
            "mlp": {
                "rms": jnp.ones((n, d), dt),
                "w_gate": init((n, d, cfg.d_ff), sd["in"]),
                "w_up": init((n, d, cfg.d_ff), sd["in"]),
                "w_down": init((n, cfg.d_ff, d), sd["out"]),
            },
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), sd["embed"]),
    }


def minicpm_sala_param_axes():
    """Logical sharding axes (leading None = a stack's layer axis)."""
    mixer = {
        "rms": P(None, "norm"),
        "wq": P(None, "embed", "heads", "kv"),
        "wk": P(None, "embed", "heads", "kv"),
        "wv": P(None, "embed", "heads", "kv"),
        "wg": P(None, "embed", "heads", "kv"),
        "wo": P(None, "heads", "kv", "embed"),
        "q_norm": P(None, "kv"),
        "k_norm": P(None, "kv"),
    }
    return {
        "wte": P("vocab", "embed"),
        "blocks": {
            "sparse": dict(mixer),
            "lightning": dict(mixer, o_norm=P(None, "heads", "kv")),
            "mlp": {
                "rms": P(None, "norm"),
                "w_gate": P(None, "embed", "mlp"),
                "w_up": P(None, "embed", "mlp"),
                "w_down": P(None, "mlp", "embed"),
            },
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# ------------------------------------------------------------------ mixers
def project(y, w, i, cfg: MinicpmSalaConfig):
    """A mixer's four products and its ``qk_norm``: y ``[..., d]`` in
    ``cfg.dtype`` -> q ``[..., H, D]``, k, v ``[..., Hkv, D]`` and the gate's
    pre-activation ``[..., H, D]``, float32."""
    q, k, v, g = (matmul("...e,ehd->...hd", y, w[name][i])
                  for name in ("wq", "wk", "wv", "wg"))
    return (rmsnorm(q, w["q_norm"][i], cfg.rms_eps),
            rmsnorm(k, w["k_norm"][i], cfg.rms_eps), v, g)


def gated_output(o, g, w, i, dtype):
    """``(o sigmoid(g)) Wo``: o, g ``[..., H, D]`` float32 -> ``[..., d]``."""
    return matmul("...hd,hde->...e", (o * jax.nn.sigmoid(g)).astype(dtype),
                  w["wo"][i])


def lightning_project(y, w, i, positions, cfg: MinicpmSalaConfig):
    """The lightning mixer up to its recurrence: y ``[B, S, d]``, positions
    ``[B, S]`` -> ``x = v``, ``b = k`` (normed, rotated), ``c = q / sqrt(D)``
    (normed, rotated), each ``[B, S, H, D]`` float32, and the gate's
    pre-activation."""
    q, k, v, g = project(y, w, i, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return v, k, q * cfg.lightning_head_dim ** -0.5, g


def lightning_output(o, g, w, i, cfg: MinicpmSalaConfig):
    """The read-out ``o [..., H, D]`` float32 through the output norm, the
    gate and ``Wo`` -> ``[..., d]`` float32."""
    o = rmsnorm(o, w["o_norm"][i], cfg.rms_eps)
    return gated_output(o, g, w, i, jnp.dtype(cfg.dtype))


def lightning_sequence(y, lengths, w, i, layer, cfg: MinicpmSalaConfig):
    """The lightning mixer over whole sequences.  y ``[B, S, d]``, lengths
    ``[B]``, ``layer`` the published index -> (``[B, S, d]`` float32, the
    state ``[B, H, D, D]`` after position ``length - 1``: positions beyond it
    run with ``dt = 0`` and change nothing)."""
    bsz, s, _ = y.shape
    h = cfg.lightning_heads
    v, k, q, g = lightning_project(
        y, w, i, jnp.arange(s, dtype=jnp.int32)[None], cfg)
    dt = jnp.broadcast_to(
        (jnp.arange(s)[None, :, None] < lengths[:, None, None]).astype(
            jnp.float32), (bsz, s, h))
    o, state = ssd_chunked(
        v, dt, -slopes(cfg, layer), k, q, jnp.zeros((h,), jnp.float32),
        cfg.chunk_size, jnp.dtype(cfg.dtype))
    return lightning_output(o, g, w, i, cfg), state


def pooled_keys(k, lengths, cfg: MinicpmSalaConfig):
    """k ``[B, S, Hkv, D]`` (as the cache holds it) -> ``Kbar [B, ceil(S /
    stride), Hkv, D]`` float32: entry ``j`` the mean of ``k[stride j : stride
    j + kernel)``, and zero where that window reaches beyond the row's
    length (``lengths [B]``), which no position of the row can see yet."""
    stride, parts = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    bsz, s, hkv, d = k.shape
    nw = -(-s // stride)
    k = jnp.pad(k.astype(jnp.float32),
                ((0, 0), (0, (nw + parts - 1) * stride - s), (0, 0), (0, 0)))
    sums = k.reshape(bsz, nw + parts - 1, stride, hkv, d).sum(2)
    kbar = sum(sums[:, e:e + nw] for e in range(parts)) / cfg.kernel_size
    whole = jnp.arange(nw) * stride + cfg.kernel_size <= lengths[:, None]
    return jnp.where(whole[..., None, None], kbar, 0.0)


def block_scores(scores, t, cfg: MinicpmSalaConfig):
    """What THE selection ranks by.  scores ``[..., G, Q, W]`` float32: a
    group's ``G`` query heads against the ``W`` pooled keys of their
    key-value head, the softmax scale in; ``t [..., Q]`` (broadcast against
    the scores' leading axes) the queries' positions -> ``[..., Q, blocks]``
    float32: a block's score, infinity where it is read whatever it scores
    (the first ``init_blocks`` and those over the last ``window_size``
    positions), minus infinity where it starts beyond ``t``."""
    stride, block = cfg.kernel_stride, cfg.block_size
    per, extra = block // stride, cfg.kernel_size // stride - 1
    nw = scores.shape[-1]
    t = jnp.asarray(t)[..., None]
    visible = jnp.arange(nw) * stride + cfg.kernel_size - 1 <= t  # [.., Q, W]
    over = visible[..., None, :, :]
    p = jax.nn.softmax(jnp.where(over, scores, NEG_INF), axis=-1)
    w = jnp.where(over, p, 0.0).sum(-3)  # [..., Q, W]
    # block b = positions [b block, (b + 1) block) meets windows per b -
    # extra .. per b + per - 1: the maximum over them (w >= 0)
    nb = -(-nw // per)
    w = jnp.pad(w, ((0, 0),) * (w.ndim - 1) + ((extra, per * nb - nw),))
    best = w[..., 0:per * nb:per]
    for e in range(1, per + extra):
        best = jnp.maximum(best, w[..., e:e + per * nb:per])
    blk = jnp.arange(nb)
    forced = (blk < cfg.init_blocks) | (
        blk >= jnp.floor_divide(t - cfg.window_size + 1, block))
    best = jnp.where(forced, jnp.inf, best)
    return jnp.where(blk * block <= t, best, -jnp.inf)


def choose_blocks(scores, t, cfg: MinicpmSalaConfig):
    """THE selection as a LIST (a decode step's): ``block_scores``' arguments
    -> ids ``[..., Q, topk]`` int32, the blocks each query reads, highest
    first (ties to the lower block), -1 where fewer than ``topk`` blocks
    start at or before ``t``.  Needs ``topk`` blocks among the ``W``
    windows' (callers: contexts of ``dense_len`` at least)."""
    value, ids = jax.lax.top_k(block_scores(scores, t, cfg), cfg.topk)
    return jnp.where(value > -jnp.inf, ids, -1).astype(jnp.int32)


def chosen_blocks(scores, t, cfg: MinicpmSalaConfig):
    """THE selection as a MASK (a prefill's, a tile of queries at a time):
    ``block_scores``' arguments -> ``[..., Q, blocks]`` bool, true for the
    blocks ``choose_blocks`` lists.  A block is read iff fewer than ``topk``
    rank ahead of it (a higher score, or the same and a lower index): a
    count over pairs of blocks, where a top-k of 98,304 rows a 16,384 rung
    and layer is a SORT on the TPU (a tenth of the rung's time, my chip
    run, PR 62)."""
    best = block_scores(scores, t, cfg)
    blk = jnp.arange(best.shape[-1])
    mine, other = best[..., :, None], best[..., None, :]
    ahead = (other > mine) | ((other == mine) & (blk[None, :] < blk[:, None]))
    return (ahead.sum(-1) < cfg.topk) & (best > -jnp.inf)


def sparse_sequence(y, lengths, longest, w, i, cfg: MinicpmSalaConfig):
    """The sparse mixer over whole sequences.  y ``[B, S, d]``, lengths
    ``[B]``, ``longest`` their maximum -> (``[B, S, d]`` float32, k, v ``[B,
    S, Hkv, D]`` in y's dtype, ``Kbar [B, ceil(S / stride), Hkv, D]`` of each
    row's TRUE length).  A row whose length is under ``dense_len`` reads all
    of ``[0, t]``; any other reads by the selection, a tile of queries at a
    time; a batch of rows all under it never computes one."""
    q, k, v, g = project(y, w, i, cfg)
    q, k, v = (a.astype(y.dtype) for a in (q, k, v))
    kbar = pooled_keys(k, lengths, cfg).astype(y.dtype)
    s = y.shape[1]

    def select(qb, rows):
        with jax.named_scope("sala.select"):
            scores = jnp.einsum(
                "bqkgd,bwkd->bkgqw", qb.astype(jnp.float32),
                kbar.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST) * cfg.head_dim ** -0.5
            chosen = chosen_blocks(scores, rows, cfg)  # [B, Hkv, Q, blocks]
            return chosen | (lengths < cfg.dense_len)[:, None, None, None]

    if s < cfg.dense_len:
        o = blocked_attention(q, k, v, longest)
    else:
        o = jax.lax.cond(
            longest >= cfg.dense_len,
            lambda: blocked_attention(q, k, v, longest, select=select,
                                      select_block=cfg.block_size),
            lambda: blocked_attention(q, k, v, longest))
    return gated_output(o, g, w, i, y.dtype), k, v, kbar


# -------------------------------------------------------------------- model
def embed(params, tokens, cfg: MinicpmSalaConfig):
    """tokens ``[...]`` -> the float32 stream ``[..., d]``."""
    with jax.named_scope("sala.embed"):
        return params["wte"][tokens].astype(jnp.float32) * cfg.scale_emb


def head(params, x, cfg: MinicpmSalaConfig):
    """The stream ``[..., d]`` float32 -> logits ``[..., V]`` float32: final
    norm, the muP divisor (in float32, before it is rounded), the vocabulary
    product."""
    x = rmsnorm(x, params["rms_f"], cfg.rms_eps) / cfg.logit_divisor
    return matmul("...e,ve->...v", x.astype(jnp.dtype(cfg.dtype)),
                  params["lm_head"])


def block(params, x, kind: str, i, layer, mix, cfg: MinicpmSalaConfig):
    """Layer ``layer`` of this model (the ``i``-th of its ``kind``) on the
    float32 stream ``x [..., d]``.  ``mix(y)`` is the layer's mixer on the
    normed state in ``cfg.dtype`` (a sequence's or one decode step's: the
    caller's, which keeps what the cache needs) -> ``[..., d]`` float32.
    Every weight is taken as ``stack[i]`` where it is used."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    r = cfg.residual_scale
    with jax.named_scope(SCOPE[kind]):
        y = rmsnorm(x, blocks[STACK[kind]]["rms"][i], cfg.rms_eps)
        x = x + r * mix(y.astype(dt))
    with jax.named_scope("sala.mlp"):
        w = blocks["mlp"]
        y = rmsnorm(x, w["rms"][layer], cfg.rms_eps).astype(dt)
        return x + r * ffn(
            y, w["w_gate"][layer], w["w_up"][layer], w["w_down"][layer])


def minicpm_sala_forward(params, tokens, lengths, cfg: MinicpmSalaConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (the stream after the last layer
    ``[B, S, d]`` float32, what a cache holds of it: ``k`` / ``v`` ``[Ns, B, S,
    Hkv, D]``, ``kbar`` ``[Ns, B, ceil(S / stride), Hkv, D]`` and ``state``
    ``[Nl, B, H, D, D]`` at each row's TRUE length, counts).  Rows at or
    beyond the longest prompt's last query block carry no attention
    (``blocked_attention``).  A run of layers of one kind is ONE loop's body
    and a group of runs that repeats a loop of those (``layers.layer_plan``,
    ``layers.scan_or_call``): the published cut ``S L6 S2 L3`` is four
    bodies."""
    blocks = params["blocks"]
    x = embed(params, tokens, cfg)
    with jax.named_scope("sala.attn"):
        longest = jnp.max(lengths)

    def one_run(x, kind, length, i0, layer0):
        def one_layer(x, t):
            i, held = i0 + t, []

            def lightning(y):
                out, state = lightning_sequence(
                    y, lengths, blocks["lightning"], i,
                    cfg.first_layer + layer0 + t, cfg)
                held.append(state)
                return out

            def sparse(y):
                out, *kept = sparse_sequence(
                    y, lengths, longest, blocks["sparse"], i, cfg)
                held.extend(kept)  # k, v, kbar
                return out

            x = block(params, x, kind, i, layer0 + t,
                      lightning if kind == "L" else sparse, cfg)
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

    cache = {}
    for kind, names in (("L", ("state",)), ("S", ("k", "v", "kbar"))):
        if kept[kind]:
            for name, parts in zip(names, zip(*kept[kind])):
                with jax.named_scope(CACHE_SCOPE[name]):
                    cache[name] = jnp.concatenate(parts)
    bsz, s = tokens.shape
    nl, ns = (cfg.kinds.count(c) for c in "LS")
    with jax.named_scope("sala.lightning"):
        own = lengths.sum().astype(jnp.int32)
        counts = {
            "lightning_positions": own * nl,
            "lightning_chunk_positions": jnp.asarray(
                nl * bsz * -(-s // cfg.chunk_size) * cfg.chunk_size,
                jnp.int32),
            "sparse_read_positions": own * ns,
            "sparse_live_positions": own * ns}
    return x, cache, counts


def minicpm_sala_apply(params, tokens, cfg: MinicpmSalaConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``: ONE call over ``S``
    positions, so every position reads by the rule of a prompt of ``S``.  One
    chip's program: ``mesh`` is accepted for the family's signature and must
    be ``None``."""
    if mesh is not None:
        raise NotImplementedError("minicpm_sala runs on one chip; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = minicpm_sala_forward(params, tokens, lengths, cfg)
    with jax.named_scope("sala.head"):
        return head(params, x, cfg)


def minicpm_sala_loss(params, tokens, cfg: MinicpmSalaConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = minicpm_sala_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
