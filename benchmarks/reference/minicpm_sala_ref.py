"""MiniCPM-SALA forward, plain: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no cache, no chunks, no kernels:
the lightning layers by their RECURRENCE (a ``lax.scan`` over positions), the
sparse layers by dense masked softmax with the selection written out query
by query.  Imports nothing of the program under test.  The only concession
to size: a sparse layer's scores exist ``row_block`` queries at a time, so
that 9k positions at the published widths fit a chip.

Follows ``config.json`` of ``openbmb/MiniCPM-SALA`` (``model_type``
``minicpm_sala``) and, for what that file names and does not carry,
MiniCPM4's published ``sparse_config`` (InfLLM-V2) and the lightning-attention
family's decay (TransNormerLLM, MiniMax-01):

``x_0 = scale_emb E[token]``; a layer ``l`` (published index) is ``x = x + r
mixer_l(RMSNorm(x))`` then ``x = x + r MLP_l(RMSNorm(x))`` with ``r =
scale_depth / sqrt(N)``, ``N`` the published depth; ``logits = W_head
(RMSNorm(x) / (d / dim_model_base))``.

Lightning: ``q, k, v`` as ``H`` heads of ``D``; ``RMSNorm`` over a head's
channels on ``q`` and ``k`` (learned ``[D]``); rotary on all ``D`` channels of
both; ``S_t = exp(-s_h) S_{t-1} + v_t (x) k_t``, ``o_t = S_t q_t / sqrt(D)``,
``s_h = 2^(-8 h / H) (1 - l / (N - 1) + 1e-5)``, ``h`` = 1..H; ``RMSNorm`` over
a head's channels on ``o`` (learned ``[H, D]``); ``o sigmoid(u Wg)``; ``Wo``.

Sparse: ``q`` ``Hq`` heads, ``k, v`` ``Hkv`` heads of ``D``; the same ``qk``
norm; no rotary; ``softmax(q k^T / sqrt(D))`` over ``R(t)``; ``o sigmoid(u
Wg)``; ``Wo``.  ``R(t)`` depends on the CALL that computes ``t``: positions
``t < prompt_len`` belong to one prefill of ``prompt_len`` positions, every
later one to a decode step at context ``t + 1``; a call of fewer than
``dense_len`` positions reads all of ``[0, t]``.  Otherwise: pooled keys
``Kbar_j = mean(K[stride j : stride j + kernel))``, visible to ``t`` when the
window ends at or before ``t``; ``p = softmax_j(q . Kbar_j / sqrt(D))`` over the
visible ones a query head; summed over the query heads of a key-value head;
a block of ``block_size`` positions scores the largest ``p`` of the visible
windows that overlap it (0 where none is visible yet: such a block lies in
the last ``kernel`` positions); the first ``init_blocks`` blocks and those
that overlap the last ``window_size`` positions ``[t - window_size + 1, t]``
score infinity; the ``topk`` highest blocks that start at or before ``t``
are read (ties to the lower block), up to ``t``.

Departures from the published modelling code, the program's and followed
here (``assumed`` in the configuration file): the rotary pairs channels ``(2i,
2i + 1)`` (``llama.rope``'s convention; the published code pairs ``(i, i + D /
2)``: a fixed permutation of a head's channels, the same model under
permuted ``Wq`` / ``Wk`` columns); the residual stream and the state are
float32; the selection ranks by float32 scores against pooled keys held in
the cache's dtype (here: exact).  Weights are the program's pytree (one stack
a kind of mixer, one MLP stack as long as the model), upcast matrix by
matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NAMES = {"S": "sparse", "L": "lightning"}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def head_norm(x, g, eps):
    """``RMSNorm`` over a head's channels: x ``[..., H, D]``, g ``[D]`` or
    ``[H, D]`` (``qk_norm``; the lightning layers' output norm)."""
    return _rms(x, g, eps)


def gate(o, g):
    """The output gate: ``o sigmoid(g)``, g the gate's pre-activation."""
    return o * jax.nn.sigmoid(g)


def rotary(x, theta):
    """x [B, S, H, D] at positions 0..S-1: channel pairs (2i, 2i + 1) turned
    by ``t theta^(-2i / D)``."""
    s, d = x.shape[1], x.shape[-1]
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]  # [S, 1, D/2]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def lightning(u, w, sizes, layer: int):
    """u [B, S, d] normed -> [B, S, d]; ``layer``: the published index."""
    h, d = sizes["lightning_heads"], sizes["lightning_head_dim"]
    eps = sizes["rms_eps"]
    q, k, v, g = (jnp.einsum("bse,ehd->bshd", u, _f32(w[n]))
                  for n in ("wq", "wk", "wv", "wg"))
    q = rotary(head_norm(q, w["q_norm"], eps), sizes["rope_theta"])
    k = rotary(head_norm(k, w["k_norm"], eps), sizes["rope_theta"])
    slope = (2.0 ** (-8.0 * np.arange(1, h + 1) / h)
             * (1.0 - layer / (sizes["published_layers"] - 1) + 1e-5))
    keep = jnp.exp(-_f32(slope))[None, :, None, None]

    def step(state, inp):  # state [B, H, Dv, Dk]
        q_t, k_t, v_t = inp
        state = keep * state + v_t[..., :, None] * k_t[..., None, :]
        return state, (state * q_t[..., None, :]).sum(-1) / np.sqrt(d)

    _, o = jax.lax.scan(
        step, jnp.zeros((u.shape[0], h, d, d), jnp.float32),
        tuple(a.swapaxes(0, 1) for a in (q, k, v)))
    o = gate(head_norm(o.swapaxes(0, 1), w["o_norm"], eps), g)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"]))


def selection(q, k, rows, sizes):
    """Which positions each query reads by the block selection.  q ``[B, R,
    Hkv, G, D]``: the queries at positions ``rows`` (numpy ``[R]``), grouped
    by key-value head; k ``[B, S, Hkv, D]`` -> ``[B, Hkv, R, S]`` bool."""
    kernel, stride = sizes["kernel_size"], sizes["kernel_stride"]
    block, topk = sizes["block_size"], sizes["topk"]
    s, d = k.shape[1], k.shape[-1]
    nw = (s - kernel) // stride + 1  # the windows that lie inside [0, S)
    nb = -(-s // block)
    first = np.arange(nw) * stride
    kbar = k[:, first[:, None] + np.arange(kernel)[None]].mean(2)  # [B,W,K,D]
    score = jnp.einsum("brkgd,bwkd->bkgrw", q, kbar) / np.sqrt(d)
    visible = jnp.asarray(first[None] + kernel - 1 <= rows[:, None])  # [R, W]
    top = jnp.max(jnp.where(visible, score, -jnp.inf), -1, keepdims=True)
    e = jnp.where(visible, jnp.exp(score - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(2)  # [B,K,R,W]
    starts = np.arange(nb) * block
    overlap = ((first[None] < starts[:, None] + block)
               & (first[None] + kernel > starts[:, None]))  # [nb, W]
    meets = jnp.asarray(overlap)[None] & visible[:, None]  # [R, nb, W]
    best = jnp.max(jnp.where(meets, p[:, :, :, None], 0.0), -1)  # [B,K,R,nb]
    t = rows[:, None]
    forced = (np.arange(nb)[None] < sizes["init_blocks"]) | (
        (starts[None] + block - 1 >= t - sizes["window_size"] + 1)
        & (starts[None] <= t))
    best = jnp.where(jnp.asarray(forced), jnp.inf, best)
    best = jnp.where(jnp.asarray(starts[None] <= t), best, -jnp.inf)
    order = jnp.argsort(-best, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = (rank < topk) & (best > -jnp.inf)  # [B, K, R, nb]
    return jnp.repeat(chosen, block, axis=-1)[..., :s]


def sparse(u, w, sizes, prompt_len: int, row_block: int):
    """u [B, S, d] normed -> [B, S, d]: grouped-query, causal, no positional
    term, each query over its ``R(t)``."""
    s = u.shape[1]
    hq, hkv, d = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    eps, dense_len = sizes["rms_eps"], sizes["dense_len"]
    q, g = (jnp.einsum("bse,ehd->bshd", u, _f32(w[n])) for n in ("wq", "wg"))
    k, v = (jnp.einsum("bse,ekd->bskd", u, _f32(w[n])) for n in ("wk", "wv"))
    q = head_norm(q, w["q_norm"], eps).reshape(
        u.shape[0], s, hkv, hq // hkv, d)
    k = head_norm(k, w["k_norm"], eps)
    out = []
    for r0 in range(0, s, row_block):
        rows = np.arange(r0, min(r0 + row_block, s))
        qb = q[:, rows]
        sc = jnp.einsum("brkgd,btkd->bkgrt", qb, k) / np.sqrt(d)
        reads = jnp.asarray(np.arange(s)[None] <= rows[:, None])  # [R, S]
        # the call that computes a row: the prefill, or its own decode step
        span = np.where(rows < prompt_len, prompt_len, rows + 1)
        if (span >= dense_len).any():
            by_rule = selection(qb, k, rows, sizes)  # [B, K, R, S]
            reads = reads & (by_rule
                             | jnp.asarray(span < dense_len)[:, None])
            reads = reads[:, :, None]
        sc = jnp.where(reads, sc, -jnp.inf)
        out.append(jnp.einsum("bkgrt,btkd->brkgd", jax.nn.softmax(sc, -1), v))
    o = jnp.concatenate(out, 1).reshape(u.shape[0], s, hq, d)
    return jnp.einsum("bshd,hde->bse", gate(o, g), _f32(w["wo"]))


def mlp(u, w):
    return (jax.nn.silu(u @ _f32(w["w_gate"])) * (u @ _f32(w["w_up"]))
            ) @ _f32(w["w_down"])


def ref_embed(params, tokens, sizes: dict):
    return _f32(params["wte"][tokens]) * sizes["scale_emb"]


def ref_layer(x, kind: str, w, w_mlp, sizes: dict, layer: int,
              prompt_len: int, row_block: int = 512):
    """One layer on the float32 stream ``x [B, S, d]``: ``kind`` is the
    pattern's letter, ``w`` that mixer's weights, ``w_mlp`` the layer's
    MLP's, ``layer`` its PUBLISHED index."""
    eps = sizes["rms_eps"]
    r = sizes["scale_depth"] / np.sqrt(sizes["published_layers"])
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["rms"], eps)
        x = x + r * (lightning(u, w, sizes, layer) if kind == "L"
                     else sparse(u, w, sizes, prompt_len, row_block))
        return x + r * mlp(_rms(x, w_mlp["rms"], eps), w_mlp)


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"]) / (
            sizes["d_model"] / sizes["dim_model_base"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def layer_weights(params, kinds: str):
    """For each layer of ``kinds``: (kind, its mixer's weights from the front
    of the kind's stack, its MLP's from the MLP stack at the layer's own
    index)."""
    seen = dict.fromkeys(NAMES, 0)
    for layer, kind in enumerate(kinds):
        i = seen[kind]
        seen[kind] += 1
        yield (kind,
               {k: v[i] for k, v in params["blocks"][NAMES[kind]].items()},
               {k: v[layer] for k, v in params["blocks"]["mlp"].items()})


def minicpm_sala_ref_logits(params, tokens, sizes: dict, kinds: str,
                            prompt_len=None, row_block: int = 512):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: the configuration's fields by name; ``kinds``: the letters of
    the layers to run (the first is published layer ``first_layer``);
    ``prompt_len``: positions before it were computed by ONE prefill of that
    many, each later one by a decode step (default: all ``S`` by one
    call)."""
    if prompt_len is None:
        prompt_len = tokens.shape[1]
    x = ref_embed(params, tokens, sizes)
    for j, (kind, w, w_mlp) in enumerate(layer_weights(params, kinds)):
        x = ref_layer(x, kind, w, w_mlp, sizes, sizes["first_layer"] + j,
                      prompt_len, row_block)
    return ref_head(x, params, sizes)
