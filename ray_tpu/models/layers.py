"""The layers that more than one family is built from, and no family owns.

A family's two files (``<family>.py``, ``<family>_decode.py``) import from
here, from the other shared modules (``mla.py``, ``mamba2.py``,
``expert_share.py``, ``sampling.py``), from ``..ops`` and ``..parallel``,
and from each other; never from another family's file
(``tests/test_models_imports.py`` holds the rule, with the list of shared
modules).  A shared module imports no family: what a function here needs of
a config it is handed as numbers, or reads by field name.

- ``rmsnorm``, ``layernorm``, ``matmul`` (every product between two
  roundings), ``ffn`` (SwiGLU), ``add_counts``;
- rotary: ``rope`` (interleaved pairs), ``rope_half`` (the ``rotate_half``
  pairing, part of a head), ``yarn_correction_range`` / ``yarn_inv_freq``;
- the tiled prefill attention: ``blocked_attention`` with its
  ``QUERY_BLOCK`` / ``KEY_BLOCK`` (Mistral-4's latent attention, Laguna,
  Olmo-Hybrid, Granite-4.0-H and MiniCPM-SALA run it), and ``ring_of``,
  what a prefill leaves in a window layer's ring;
- ``conv_sequence``: the causal depthwise convolution in front of a
  recurrent mixer, with the window a cache keeps of it;
- a stack of layers as a program: ``layer_plan`` (a pattern's runs, what
  repeats folded) and ``scan_or_call``.

None enters a ``jax.named_scope``: the scope is the caller's, a family's
own name for the layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.decode_attention import NEG_INF, attend_blocks, ring_held

# A prefill's scores exist a tile of this many queries by this many keys at a
# time: 32 heads x 512 x 512 float32 = 32 MB at Mistral-Small-4's published
# sizes, which the v5e's compiler keeps in its fast memory from the scores'
# product to the values' (16,384 positions, one layer, my chip runs, PR 48:
# 23.2 ms at 512 x 512, 22.0 at 1024 x 512, 86.2 at 1024 x 1024, whose tile
# goes through the main memory; 34.2 at 256 x 256).  512 is also the step a
# decode reads by.
QUERY_BLOCK = 512
KEY_BLOCK = 512


# ------------------------------------------------- norm, products, FFN
def rmsnorm(x, g, eps: float):
    """``x / rms(x) * g`` over the last axis, in float32, rounded to
    ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * g.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, g, b, eps=1e-5):
    """Layer norm over the last axis with gain and bias, in float32, rounded
    to ``x``'s dtype.  The default ``eps`` is GPT-2's; ViT passes its own."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def matmul(spec, x, w):
    """A matrix product that reads ``cfg.dtype`` operands and gives a
    float32 result: what lies between two products (norm, rope, softmax,
    silu, the residual sum) is done in float32 and rounded once, where the
    next product reads it."""
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


def ffn(u, w_gate, w_up, w_down):
    """SwiGLU; ``u [..., d]`` in ``cfg.dtype`` -> ``[..., d]`` float32."""
    gate = jax.nn.silu(matmul("...e,ef->...f", u, w_gate))
    up = matmul("...e,ef->...f", u, w_up)
    return matmul("...f,fe->...e", (gate * up).astype(u.dtype), w_down)


def add_counts(total, counts):
    return counts if total is None else jax.tree.map(jnp.add, total, counts)


# ------------------------------------------------------------------- rotary
def rope(x, positions, theta: float, inv_freq=None):
    """Rotary embedding.  x: [B, S, H, D]; positions: [B, S] or [S].
    ``inv_freq`` ``[D/2]``: a family's own frequencies a pair (scaled rotary:
    ``yarn_inv_freq``) in place of ``theta ** (-2i / D)``."""
    d = x.shape[-1]
    freqs = (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
             if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    if positions.ndim == 1:
        positions = positions[None]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def rope_half(x, positions, theta: float, rotary_dim: int, inv_freq=None,
              factor=None):
    """Rotate the first ``rotary_dim`` dimensions of every head in the
    ``rotate_half`` pairing (dimension ``j`` with ``j + rotary_dim / 2``).  x
    ``[..., heads, D]`` float32, positions of x's leading shape (or one that
    broadcasts to it) -> float32.  ``inv_freq`` ``[rotary_dim / 2]``: a
    family's own frequencies a pair (scaled rotary) in place of ``theta ** (-2j
    / rotary_dim)``; ``factor``: what multiplies cos and sin (YaRN's
    attention factor).  Both absent, the program is what it was."""
    half = rotary_dim // 2
    freqs = (theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
             if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    angles = positions[..., None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float):
    """``(low, high)`` of the ``dim / 2`` rotary pairs: those below ``low``
    keep their frequency, those from ``high`` on are slowed by the factor, a
    linear ramp between (floor / ceil of the pair that turns ``beta_fast`` /
    ``beta_slow`` times over the ``original_max`` trained positions, clipped
    to the pairs there are).  The numbers, not a config: Laguna's full
    layers read the same table off other fields."""

    def pair_turning(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` YaRN-scaled rotary frequencies, float32 (a constant
    of the program)."""
    half = dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast,
                                      beta_slow)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return ((1 - ramp) * f + ramp * f / factor).astype(np.float32)


# ------------------------------------- a causal convolution over sequences
def conv_sequence(x, lengths, conv_w, i):
    """A causal depthwise convolution over whole sequences, and what a cache
    keeps of it.  x ``[B, S, C]`` float32, lengths ``[B]``, ``conv_w [layers,
    K, C]`` of which layer ``i``'s taps are read where they are used ->
    (``out_t = sum_j w_j x_{t-K+1+j}`` ``[B, S, C]``, before any bias or
    activation; the window ``[B, K-1, C]``: the last ``K-1`` TRUE inputs,
    oldest first, zeros before a sequence's start, whatever lies at or
    beyond ``length``).  The Mamba-2 mixer's and both delta rules'."""
    k, s = conv_w.shape[1], x.shape[1]
    idx = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None]  # [B, K-1]
    window = jnp.where(
        (idx >= 0)[..., None],
        jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1), 0.0)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * conv_w[i, j] for j in range(k)), window


# ------------------------------------------------------ prefill attention
def blocked_attention(q, k, v, longest=None, query_block: int = QUERY_BLOCK,
                      key_block: int = KEY_BLOCK, window=None, select=None,
                      select_block=None):
    """Causal attention of ``[B, S]`` tokens over themselves, a tile of
    ``query_block`` queries by ``key_block`` keys at a time.  q ``[B, S, H,
    D]``, k ``[B, S, Hkv, D]`` (the softmax scale ``D^-0.5``; what else scales
    a score is in ``q``), v ``[B, S, Hkv, Dv]`` -> ``[B, S, H, Dv]`` float32.
    Query block ``c`` sees the key blocks up to its own last row and no
    further (an online softmax over them: ``attend_blocks``); ``longest``
    (traced; the longest prompt of the batch) bounds the query blocks, and
    the rows of those wholly beyond it come out zero: nothing reads them.  A
    sequence that the blocks do not divide is padded with keys no query
    sees.

    Where ``Hkv`` divides ``H`` (grouped queries: query head ``h`` reads
    key-value head ``h // (H / Hkv)``) a tile's products carry the group as
    an axis of the queries, and the keys and values are never repeated.
    ``window`` (static): query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``: the BAND, whose key blocks have a lower bound too, so a query
    block of ``window`` rows meets two key blocks whatever ``S`` is.

    ``select`` (with ``select_block``, static, which the padded length is
    then made a multiple of): a query reads a key only where ``select``
    lets it, besides causality.  ``select(qb, rows)`` is called once a
    query tile with the tile's queries as the products read them (``[B,
    query_block, H, D]``, grouped ``[B, query_block, Hkv, G, D]``) and
    their positions ``[query_block]``, and returns ``[B, Hkv or H,
    query_block, n]`` bool: entry ``j`` stands for keys ``[j select_block,
    (j + 1) select_block)``, and entries it does not give are not read.  The
    mask exists a tile of queries at a time (MiniCPM-SALA's block
    selection).  With equal head counts, no window and no selection the
    program is what it was."""
    bsz, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    fit = -(-s // select_block) * select_block if select_block else s
    query_block, key_block = min(query_block, fit), min(key_block, fit)
    whole = math.lcm(query_block, key_block, select_block or 1)
    padded = -(-s // whole) * whole
    if padded > s:
        q, k, v = (jnp.pad(a, ((0, 0), (0, padded - s), (0, 0), (0, 0)))
                   for a in (q, k, v))
    scale = d ** -0.5
    # a tile's products and its result's shape, heads equal or grouped
    if hkv == h:
        to_scores, to_values = "bqhd,bkhd->bhqk", "bhqk,bkhv->bhqv"
        tile = (bsz, h, query_block, dv)
    else:
        to_scores, to_values = "bqkgd,btkd->bkgqt", "bkgqt,btkv->bkgqv"
        tile = (bsz, hkv, h // hkv, query_block, dv)

    def query_rows(c, out):
        first = c * query_block
        qb = jax.lax.dynamic_slice_in_dim(q, first, query_block, axis=1)
        if hkv != h:
            qb = qb.reshape(bsz, query_block, hkv, h // hkv, d)
        rows = first + jnp.arange(query_block)
        if select is not None:
            chosen = select(qb, rows)[..., :padded // select_block]
            chosen = jnp.pad(chosen, ((0, 0),) * 3 + (
                (0, padded // select_block - chosen.shape[-1]),))
            if hkv != h:  # a group's query heads read what their head does
                chosen = chosen[:, :, None]

        def keys(start):
            kb = jax.lax.dynamic_slice_in_dim(k, start, key_block, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, key_block, axis=1)
            scores = matmul(to_scores, qb, kb) * scale
            seen = rows[:, None] >= start + jnp.arange(key_block)[None]
            if window is not None:
                seen &= (rows[:, None] - start
                         - jnp.arange(key_block)[None]) < window
            if select is not None:
                seen = seen & jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    chosen, start // select_block, key_block // select_block,
                    axis=-1), select_block, axis=-1)
            return jnp.where(seen, scores, NEG_INF), lambda p: matmul(
                to_values, p.astype(q.dtype), vb)

        last = (first + query_block + key_block - 1) // key_block
        if window is None:
            o = attend_blocks(keys, last, key_block, tile)
        else:  # the first key block that a row of this query block sees
            low = jnp.maximum(first - window + 1, 0) // key_block
            o = attend_blocks(lambda start: keys(low * key_block + start),
                              last - low, key_block, tile)
        o = (o.transpose(0, 2, 1, 3) if hkv == h else
             o.transpose(0, 3, 1, 2, 4).reshape(bsz, query_block, h, dv))
        return jax.lax.dynamic_update_slice_in_dim(out, o, first, axis=1)

    blocks = padded // query_block
    if longest is not None:
        blocks = jnp.minimum((longest + query_block - 1) // query_block,
                             blocks)
    out = jax.lax.fori_loop(
        0, blocks, query_rows, jnp.zeros((bsz, padded, h, dv), jnp.float32))
    return out[:, :s]


def ring_of(a, lengths, window: int):
    """What a ring of ``window`` slots holds of a sequence's keys (or values)
    once its first ``lengths[b]`` positions are in: slot ``r`` the newest
    position ``p < length`` with ``p = r mod window``, zeros where there is
    none yet.  a ``[B, S, Hkv, X]``, lengths ``[B]`` -> ``[B, Hkv, window,
    X]``, whatever ``S`` is padded to."""
    held = ring_held(lengths[:, None] - 1, window)
    taken = jnp.take_along_axis(
        a, jnp.clip(held, 0, a.shape[1] - 1)[:, :, None, None], axis=1)
    return jnp.where((held >= 0)[:, :, None, None], taken, 0).transpose(
        0, 2, 1, 3)


# --------------------------------------------------------- a stack's plan
def layer_plan(kinds: str):
    """``kinds`` as runs of one kind, what repeats folded: a list of ``(group,
    repeats)``, a group being consecutive runs ``(kind, length)`` whose kinds
    and lengths come again right after it.  The published forty layers are
    ``[M5]``, ``[*1, M9] x 3``, ``[*1]`` and ``[M4]``: five layer bodies in a
    program of forty layers (three Mamba-2, two attention)."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    runs = [tuple(run) for run in runs]
    plan, r = [], 0
    while r < len(runs):
        best = (1, 1)  # (runs in the group, repeats), most runs folded
        for g in range(1, (len(runs) - r) // 2 + 1):
            c = 1
            while runs[r + c * g:r + (c + 1) * g] == runs[r:r + g]:
                c += 1
            if c > 1 and g * c > best[0] * best[1]:
                best = (g, c)
        plan.append((runs[r:r + best[0]], best[1]))
        r += best[0] * best[1]
    return plan


def scan_or_call(body, carry, times: int):
    """``lax.scan(body, carry, arange(times))``; once, the body itself with a
    Python 0 for its counter, its outputs stacked as a scan's would be."""
    if times > 1:
        return jax.lax.scan(body, carry, jnp.arange(times))
    carry, out = body(carry, 0)
    return carry, jax.tree.map(lambda a: a[None], out)
