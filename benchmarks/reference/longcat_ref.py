"""LongCat-Flash forward, plain: float32 ``jax.numpy``, one full causal
forward, no cache, no kernels, expanded (not absorbed) attention, a Python
loop over layers and over experts.

Follows ``config.json`` of ``meituan-longcat/LongCat-Flash-Chat`` and the
equations in ``ray_tpu/models/longcat.py``'s docstring: a double layer is
``a0 = h + MLA0(N(h))``, ``u0 = N(a0)``, ``m = MoE(u0)``, ``b0 = a0 +
FFN0(u0)``, ``a1 = b0 + MLA1(N(b0))``, ``out = a1 + FFN1(N(a1)) + m``.  MLA
keeps a normed, scaled key-value latent ``ckv`` (rank 512) and one rotary key
for all heads; per-head keys and values are ``ckv Wkvb``.  The router is a
softmax over routed + identity experts, top-k of ``p + bias``, weights
``s * p`` not renormalised; an identity expert adds ``w u``.

Departures, the program's and followed here (``assumed`` in the
configuration file): rope rotates interleaved pairs; ``aq = sqrt(d / rq)``,
``akv = sqrt(d / rkv)``; untied head.  The share: given ``expert_offset`` and
the ``held`` experts in ``params["experts"]``, routed experts outside
``[offset, offset + held)`` add nothing, as in the program; with every expert
held it is the uncut model.  Weights are the program's pytree, upcast matrix
by matrix (whole, two double layers at the published widths are 10 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _rope(x, theta):
    """x [B, S, H, D], positions 0..S-1, interleaved pairs."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def mla(x, w, j, sizes):
    """x [B, S, d] normed -> attention output [B, S, d]."""
    rkv, dn, theta, eps = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                           sizes["rope_theta"], sizes["rms_eps"])
    d, rq = x.shape[-1], w["wq_a"].shape[-1]
    s = x.shape[1]
    cq = _rms(x @ _f32(w["wq_a"][j]), w["rms_q"][j], eps)
    q = (d / rq) ** 0.5 * jnp.einsum("bsr,rhd->bshd", cq, _f32(w["wq_b"][j]))
    qn, qr = q[..., :dn], _rope(q[..., dn:], theta)
    kv = x @ _f32(w["wkv_a"][j])
    ckv = (d / rkv) ** 0.5 * _rms(kv[..., :rkv], w["rms_kv"][j], eps)
    kr = _rope(kv[..., None, rkv:], theta)[:, :, 0]
    kvh = jnp.einsum("bsc,chd->bshd", ckv, _f32(w["wkv_b"][j]))
    kn, v = kvh[..., :dn], kvh[..., dn:]
    sc = (jnp.einsum("bshd,bthd->bhst", qn, kn)
          + jnp.einsum("bshd,btd->bhst", qr, kr)) / jnp.sqrt(
              float(q.shape[-1]))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"][j]))


def moe(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] -> (the expert layer's share [B, S, d], chosen experts
    [B, S, k])."""
    n_routed, top_k = sizes["n_routed_experts"], sizes["top_k"]
    p = jax.nn.softmax(u @ _f32(w["router"]), -1)
    _, sel = jax.lax.top_k(p + _f32(w["router_bias"]), top_k)
    weight = sizes["routed_scaling_factor"] * jnp.take_along_axis(p, sel, -1)
    y = jnp.zeros_like(u)
    for e in range(experts["w_gate"].shape[0]):
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(u, experts["w_gate"][e], experts["w_up"][e],
                              experts["w_down"][e])
    w_zero = (weight * (sel >= n_routed)).sum(-1, keepdims=True)
    return y + w_zero * u, sel


def longcat_ref_logits(params, tokens, sizes: dict, n_layer: int,
                       expert_offset: int = 0, with_routing: bool = False):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: ``kv_lora_rank``, ``qk_nope_head_dim``, ``rope_theta``,
    ``rms_eps``, ``n_routed_experts``, ``top_k``, ``routed_scaling_factor``.
    ``with_routing``: also the experts every token chose, [L, B, S, k]."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][tokens])
        chosen = []
        for l in range(n_layer):
            w = {k: v[l] for k, v in params["blocks"].items()}
            experts = {k: v[l] for k, v in params["experts"].items()}
            a0 = x + mla(_rms(x, w["rms_attn"][0], eps), w, 0, sizes)
            u0 = _rms(a0, w["rms_ffn"][0], eps)
            m, sel = moe(u0, w, experts, sizes, expert_offset)
            chosen.append(sel)
            b0 = a0 + _swiglu(u0, w["w_gate"][0], w["w_up"][0], w["w_down"][0])
            a1 = b0 + mla(_rms(b0, w["rms_attn"][1], eps), w, 1, sizes)
            u1 = _rms(a1, w["rms_ffn"][1], eps)
            x = a1 + _swiglu(u1, w["w_gate"][1], w["w_up"][1],
                             w["w_down"][1]) + m
        x = _rms(x, params["rms_f"], eps)
        logits = jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))
    return (logits, jnp.stack(chosen)) if with_routing else logits
