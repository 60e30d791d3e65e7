"""Mistral-Small-4 forward, plain: float32 ``jax.numpy``, one full causal
forward, no cache, no kernels, no blocks: per-head keys and values expanded
from the latent, dense ``[S, S]`` scores, a Python loop over layers and over
the experts it is given.

Follows ``config.json`` of ``mistralai/Mistral-Small-4-119B-2603``
(``model_type`` ``mistral4``) and the equations in
``ray_tpu/models/mistral4.py``'s docstring: a block is ``x = x +
MLA(N(x)); x = x + MoE(N(x))``.  MLA keeps a normed key-value latent ``ckv``
(rank 256) and one rotary key for all heads; per-head keys and values are
``ckv Wkb`` / ``ckv Wvb``; ``score = a(i) (qn . kn + qr . kr) (dn+dr)^-0.5
m^2``.  RoPE_yarn, ``m`` and ``a`` are written out below from
``rope_parameters``.  The router is a softmax over all routed experts, the
``k`` largest, renormalised over the chosen; a shared expert is added for
every token.

Departures and conventions, the program's and followed here (``assumed`` in
the configuration file): rope rotates interleaved pairs (``rope_interleave``
true); the softmax scale carries ``m^2`` (``mscale_all_dim``, DeepSeek-V3's
convention) and cos / sin carry ``g(mscale) / g(mscale_all_dim)`` = 1;
``a(pos) = 1 + beta ln(1 + floor(pos / L0))``; YaRN's bounds are truncated
(floor / ceil); the router has no bias and no group limit; untied head.  The
share: given ``expert_offset`` and the ``held`` experts in
``params["experts"]``, routed experts outside ``[offset, offset + held)`` add
nothing, as in the program; with every expert held it is the uncut model.
Weights are the program's pytree, upcast matrix by matrix.

``sizes`` may switch a mechanism off, for the controls that a comparison
must fail: ``yarn`` False (plain rotary at ``rope_theta``), ``rope_mscale_all_dim``
0 (no ``m^2``), ``query_scale_beta`` 0, ``shared`` False (no shared expert),
``renormalise`` False (the chosen experts weigh ``p``, not ``p / sum p``).
``query_block``: the dense scores are computed for that many query rows at a
time against ALL keys (the same arithmetic, row by row, for sequences whose
``[H, S, S]`` does not fit).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def inv_freq(sizes):
    """RoPE_yarn's frequencies a pair; plain ``theta^(-2i/dr)`` when ``yarn``
    is off."""
    dr, theta = sizes["qk_rope_head_dim"], sizes["rope_theta"]
    f = [theta ** (-2 * i / dr) for i in range(dr // 2)]
    if not sizes.get("yarn", True):
        return _f32(f)

    def pair_turning(turns):
        return dr * math.log(sizes["rope_original_max"]
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(sizes["rope_beta_fast"])), 0)
    high = min(math.ceil(pair_turning(sizes["rope_beta_slow"])), dr - 1)
    out = []
    for i, f_i in enumerate(f):
        r = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append((1 - r) * f_i + r * f_i / sizes["rope_factor"])
    return _f32(out)


def mscale(sizes):
    if sizes["rope_factor"] <= 1 or not sizes["rope_mscale_all_dim"]:
        return 1.0
    return 0.1 * sizes["rope_mscale_all_dim"] * math.log(
        sizes["rope_factor"]) + 1.0


def query_scale(positions, sizes):
    return 1.0 + sizes["query_scale_beta"] * jnp.log(
        1.0 + jnp.floor(_f32(positions) / sizes["rope_original_max"]))


def _rope(x, freqs):
    """x [B, S, H, D], positions 0..S-1, interleaved pairs."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def mla(x, w, sizes):
    """x [B, S, d] normed -> attention output [B, S, d]."""
    rkv, dn, eps = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                    sizes["rms_eps"])
    s = x.shape[1]
    freqs = inv_freq(sizes)
    cq = _rms(x @ _f32(w["wq_a"]), w["rms_q"], eps)
    q = jnp.einsum("bsr,rhd->bshd", cq, _f32(w["wq_b"]))
    qn, qr = q[..., :dn], _rope(q[..., dn:], freqs)
    kv = x @ _f32(w["wkv_a"])
    ckv = _rms(kv[..., :rkv], w["rms_kv"], eps)
    kr = _rope(kv[..., None, rkv:], freqs)[:, :, 0]
    kn = jnp.einsum("bsc,chd->bshd", ckv, _f32(w["wk_b"]))
    v = jnp.einsum("bsc,chd->bshd", ckv, _f32(w["wv_b"]))
    scale = q.shape[-1] ** -0.5 * mscale(sizes) ** 2
    a = query_scale(jnp.arange(s), sizes)  # [S], the query's position
    step = sizes.get("query_block") or s
    out = []
    for first in range(0, s, step):
        rows = slice(first, min(first + step, s))
        sc = (jnp.einsum("bshd,bthd->bhst", qn[:, rows], kn)
              + jnp.einsum("bshd,btd->bhst", qr[:, rows], kr))
        sc = sc * scale * a[rows][None, None, :, None]
        causal = jnp.arange(s)[rows][:, None] >= jnp.arange(s)[None]
        sc = jnp.where(causal, sc, -jnp.inf)
        out.append(jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v))
    return jnp.einsum("bshd,hde->bse", jnp.concatenate(out, 1), _f32(w["wo"]))


def moe(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] -> (the held experts' part + the shared expert [B, S, d],
    chosen experts [B, S, k])."""
    p = jax.nn.softmax(u @ _f32(w["router"]), -1)
    chosen, sel = jax.lax.top_k(p, sizes["top_k"])
    if sizes.get("renormalise", True):
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weight = sizes["routed_scaling_factor"] * chosen
    y = jnp.zeros_like(u)
    for e in range(experts["w_gate"].shape[0]):
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(u, experts["w_gate"][e], experts["w_up"][e],
                              experts["w_down"][e])
    if sizes.get("shared", True):
        y = y + _swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return y, sel


def ref_layer(x, w, experts, sizes: dict, expert_offset: int = 0):
    """One block on the float32 stream x [B, S, d]: ``w`` / ``experts`` are
    the layer's slices of ``params["blocks"]`` / ``params["experts"]``."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + mla(_rms(x, w["rms_attn"], eps), w, sizes)
        y, _ = moe(_rms(x, w["rms_ffn"], eps), w, experts, sizes,
                   expert_offset)
        return x + y


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def mistral4_ref_logits(params, tokens, sizes: dict, n_layer: int,
                        expert_offset: int = 0, with_routing: bool = False):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: the fields of ``Mistral4Config`` (and the switches above).
    ``with_routing``: also the experts every token chose, [L, B, S, k]."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][tokens])
        chosen = []
        for l in range(n_layer):
            w = {k: v[l] for k, v in params["blocks"].items()}
            experts = {k: v[l] for k, v in params["experts"].items()}
            x = x + mla(_rms(x, w["rms_attn"], eps), w, sizes)
            y, sel = moe(_rms(x, w["rms_ffn"], eps), w, experts, sizes,
                         expert_offset)
            chosen.append(sel)
            x = x + y
        x = _rms(x, params["rms_f"], eps)
        logits = jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))
    return (logits, jnp.stack(chosen)) if with_routing else logits
