"""MiMo-V2 forward, plain: float32 ``jax.numpy``, one full causal forward, no
cache, no ring, no blocks, no batching tricks: dense ``[S, S]`` scores in
every attention, the window as a MASK over them and the sink as one more
column of the softmax; every expert it is given run on every token and
weighed by the router's choice.

Follows ``config.json`` of ``XiaomiMiMo/MiMo-V2.5`` (``model_type``
``mimo_v2``) and the equations in ``ray_tpu/models/mimo_v2.py``'s docstring:
every block is ``x + attn(RMSNorm(x))`` then ``x + mlp(RMSNorm(x))``, the
kinds by the two patterns' letters.  Attention: ``q = u Wq`` (64 heads of
192), ``k = u Wk``, ``v = 0.707 u Wv`` (heads of 128), rotary on the first 64
dimensions of ``q`` and ``k`` (base 1e7 in a full layer, 1e4 in a window
layer), scores over ``sqrt(192)``, causal; a window layer sees key ``j`` from
query ``i`` iff ``0 <= i - j < 128`` and adds its head's sink logit to the
softmax, whose probability is dropped.  Experts: sigmoid scores, top 8 of
``score + bias``, weights renormalised over the chosen, gated SwiGLU, no
shared expert.

Departures from the published description, the program's and followed here
(``assumed`` in the configuration file): the fused ``qkv`` projection is
stored as its three matrices (the same product); rotary pairs dimension
``j`` with ``j + 32`` (``rotate_half``) on the FIRST 64 dimensions;
``attention_value_scale`` multiplies ``v``; ``attention_chunk_size`` is read
by nothing; no multi-token-prediction layers, no vision or audio tower.
The share: given ``expert_offset`` and the held experts in
``params["experts"]``, routed experts outside ``[offset, offset + held)`` add
nothing, as in the program; with every expert held it is the uncut model.
Weights are the program's pytree (one stack a kind of layer), upcast matrix
by matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def _rope(x, theta, rotary_dim):
    """x [B, S, heads, D]: positions 0..S-1, the first ``rotary_dim``
    dimensions, ``rotate_half``."""
    half = rotary_dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S, half]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rot = x[..., :rotary_dim]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate(
        [rot * cos + turned * sin, x[..., rotary_dim:]], -1)


def attention(u, w, sizes, theta, window=None, with_sink=True):
    """u [B, S, d] normed -> [B, S, d]; ``w``: one layer's weights;
    ``window``: ``None`` in a full layer."""
    s = u.shape[1]
    q = jnp.einsum("bse,ehd->bshd", u, _f32(w["wq"]))
    k = jnp.einsum("bse,ekd->bskd", u, _f32(w["wk"]))
    v = sizes["value_scale"] * jnp.einsum("bse,ekd->bskd", u, _f32(w["wv"]))
    q = _rope(q, theta, sizes["rotary_dim"])
    k = _rope(k, theta, sizes["rotary_dim"])
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(float(q.shape[-1]))
    behind = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    seen = behind >= 0
    if window is not None:
        seen = seen & (behind < window)
    sc = jnp.where(seen, sc, -jnp.inf)
    if window is not None and with_sink:
        sink = jnp.broadcast_to(_f32(w["sink"])[None, :, None, None],
                                sc.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([sc, sink], -1), -1)[..., :-1]
    else:
        p = jax.nn.softmax(sc, -1)
    o = jnp.einsum("bhst,bthd->bshd", p, v)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"]))


def experts_layer(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] -> (the expert layer's share [B, S, d], chosen experts
    [B, S, k])."""
    score = jax.nn.sigmoid(u @ _f32(w["router"]))
    _, sel = jax.lax.top_k(score + _f32(w["router_bias"]), sizes["top_k"])
    chosen = jnp.take_along_axis(score, sel, -1)
    weight = chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(u)
    for e in range(experts["w_gate"].shape[0]):
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(u, experts["w_gate"][e], experts["w_up"][e],
                              experts["w_down"][e])
    return y, sel


def layer_weights(params, attn_kinds: str, mlp_kinds: str):
    """(attention kind, MLP kind, the attention's weights, the MLP's, its
    held experts or ``None``) of every layer that runs, each taken from the
    front of its kind's stack."""
    blocks, seen = params["blocks"], dict.fromkeys("FWDE", 0)
    names = {"F": "full", "W": "window", "D": "dense", "E": "moe"}
    for attn_kind, mlp_kind in zip(attn_kinds, mlp_kinds):
        i, j = seen[attn_kind], seen[mlp_kind]
        seen[attn_kind] += 1
        seen[mlp_kind] += 1
        yield (attn_kind, mlp_kind,
               {k: v[i] for k, v in blocks[names[attn_kind]].items()},
               {k: v[j] for k, v in blocks[names[mlp_kind]].items()},
               {k: v[j] for k, v in params["experts"].items()}
               if mlp_kind == "E" else None)


def ref_layer(x, attn, mlp, experts, *, attn_kind: str, mlp_kind: str,
              sizes: dict, expert_offset: int = 0, with_sink: bool = True):
    """One block on the stream ``x [B, S, d]``, float32, highest
    precision."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        u = _rms(x, attn["rms"], eps)
        if attn_kind == "F":
            x = x + attention(u, attn, sizes, sizes["rope_theta"])
        else:
            x = x + attention(u, attn, sizes, sizes["rope_theta_window"],
                              sizes["window"], with_sink)
        u = _rms(x, mlp["rms"], eps)
        if mlp_kind == "D":
            return x + _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        return x + experts_layer(u, mlp, experts, sizes, expert_offset)[0]


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def mimo_v2_ref_logits(params, tokens, sizes: dict, attn_kinds: str,
                       mlp_kinds: str, expert_offset: int = 0,
                       with_sink: bool = True):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: ``rms_eps``, ``rotary_dim``, ``rope_theta``,
    ``rope_theta_window``, ``window``, ``value_scale``, ``top_k``;
    ``attn_kinds`` / ``mlp_kinds``: a letter a layer that runs (``F`` / ``W``,
    ``D`` / ``E``).  ``with_sink=False`` is a control: the softmax without
    its sink column."""
    x = _f32(params["wte"][tokens])
    for attn_kind, mlp_kind, attn, mlp, experts in layer_weights(
            params, attn_kinds, mlp_kinds):
        x = ref_layer(x, attn, mlp, experts, attn_kind=attn_kind,
                      mlp_kind=mlp_kind, sizes=sizes,
                      expert_offset=expert_offset, with_sink=with_sink)
    return ref_head(x, params, sizes)
