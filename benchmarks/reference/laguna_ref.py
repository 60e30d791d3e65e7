"""Laguna forward, plain: float32 ``jax.numpy``, one full causal forward, no
cache, no ring, no tiles: dense ``[S, S]`` scores in every attention, the
window as a MASK over them, keys and values repeated to the query heads,
both rotary tables written out from ``rope_parameters``, every expert it is
given run on every token and weighed by the router's choice.

Follows ``config.json`` of ``poolside/Laguna-S-2.1`` (``model_type``
``laguna``) and the equations in ``ray_tpu/models/laguna.py``'s docstring:
every block is ``x + attn(RMSNorm(x))`` then ``x + ff(RMSNorm(x))``, the kinds
by the two patterns' letters.  Attention: ``q = u Wq`` (48 heads of 128 in a
full layer, 72 in a window layer), ``k = u Wk``, ``v = u Wv`` (8 heads), ``q``
and ``k`` RMS-normalised over the head, rotary by kind, scores over
``sqrt(128)``, causal; a window layer sees key ``j`` from query ``i`` iff ``0
<= i - j < 512``; every head's output times ``sigmoid(u Wg)_h`` before ``Wo``.
Experts: sigmoid scores, top 10 of ``score + bias``, weights renormalised
over the chosen times 2.5, gated SwiGLU, plus one shared expert.

What the config leaves open, the program's choices and followed here, each
noted at its line (``assumed`` in the configuration file): (1) the norm on q
and k; (2) the gate is a sigmoid of the layer's normed input; (3) the router
scores by sigmoid with a correction bias; (4) the shared expert has no gate
of its own; (5) YaRN's attention factor multiplies cos and sin of the
ROTATED part only.  The share: given ``expert_offset`` and the held experts
in ``params["experts"]``, routed experts outside ``[offset, offset + held)``
add nothing, as in the program; with every expert held it is the uncut
model.  Weights are the program's pytree (one stack a kind of layer),
upcast matrix by matrix.

``sizes`` (the fields of ``LagunaConfig``) may switch a mechanism off or
wrong, for the controls that a comparison must fail: ``gate`` False (no gate
on the heads), ``window_heads`` n (a window layer reads the first n of its
heads' weights), ``yarn`` False (the full layers rotate at ``rope_theta``
unscaled, factor 1), ``factor_on_scores`` True (``m^2`` on the whole score in
place of cos and sin), ``window_table`` "full" (a window layer rotates by the
full layers' table), ``shared`` False (no shared expert); ``window`` and
``routed_scaling_factor`` are plain fields.  ``query_block``: the dense
scores are computed for that many query rows at a time against ALL keys (the
same arithmetic, row by row, for sequences whose ``[H, S, S]`` does not fit).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def full_inv_freq(sizes):
    """The full layers' frequencies a pair: YaRN over the ``rotary_dim / 2``
    pairs (``rope_parameters.full_attention``), plain ``theta^(-2i/R)`` when
    ``yarn`` is off."""
    dim, theta = sizes["rotary_dim"], sizes["rope_theta"]
    f = [theta ** (-2 * i / dim) for i in range(dim // 2)]
    if not sizes.get("yarn", True):
        return _f32(f)

    def pair_turning(turns):
        return dim * math.log(sizes["rope_original_max"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(sizes["rope_beta_fast"])), 0)
    high = min(math.ceil(pair_turning(sizes["rope_beta_slow"])), dim - 1)
    out = []
    for i, f_i in enumerate(f):
        r = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append((1 - r) * f_i + r * f_i / sizes["rope_factor"])
    return _f32(out)


def _rope(x, inv, factor):
    """x [B, S, heads, D]: positions 0..S-1, the first ``2 len(inv)``
    dimensions, ``rotate_half``; cos and sin times ``factor``."""
    half = inv.shape[0]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S, half]
    cos = factor * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = factor * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    rot = x[..., :2 * half]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + turned * sin, x[..., 2 * half:]], -1)


def rotary_table(kind: str, sizes):
    """(frequencies, what multiplies cos and sin, what multiplies the whole
    score) of a layer of ``kind``."""
    if kind == "W" and sizes.get("window_table") != "full":
        dim = sizes["head_dim"]  # partial_rotary_factor 1, rope_type default
        return _f32([sizes["rope_theta_window"] ** (-2 * i / dim)
                     for i in range(dim // 2)]), 1.0, 1.0
    m = sizes["rope_attention_factor"] if sizes.get("yarn", True) else 1.0
    if sizes.get("factor_on_scores"):  # the control: DeepSeek-V3's place
        return full_inv_freq(sizes), 1.0, m * m
    return full_inv_freq(sizes), m, 1.0  # assumed (5): on cos and sin


def attention(u, w, kind: str, sizes):
    """u [B, S, d] normed -> [B, S, d]; ``w``: one layer's weights."""
    s, eps = u.shape[1], sizes["rms_eps"]
    heads = w["wq"].shape[1]
    if kind == "W":
        heads = sizes.get("window_heads", heads)
    q = jnp.einsum("bse,ehd->bshd", u, _f32(w["wq"][:, :heads]))
    k = jnp.einsum("bse,ekd->bskd", u, _f32(w["wk"]))
    v = jnp.einsum("bse,ekd->bskd", u, _f32(w["wv"]))
    # assumed (1): q and k normed over the head, Qwen3-MoE's attention
    q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    inv, factor, on_scores = rotary_table(kind, sizes)
    q, k = _rope(q, inv, factor), _rope(k, inv, factor)
    groups = heads // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scale = on_scores / math.sqrt(q.shape[-1])
    step = sizes.get("query_block") or s
    out = []
    for first in range(0, s, step):
        rows = jnp.arange(s)[first:first + step]
        sc = jnp.einsum("bshd,bthd->bhst", q[:, first:first + step], k) * scale
        behind = rows[:, None] - jnp.arange(s)[None]
        seen = behind >= 0
        if kind == "W":
            seen = seen & (behind < sizes["window"])
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        out.append(jnp.einsum("bhst,bthd->bshd", p, v))
    o = jnp.concatenate(out, 1)
    if sizes.get("gate", True):
        # assumed (2): a sigmoid gate a head from the layer's normed input
        o = o * jax.nn.sigmoid(u @ _f32(w["wg"][:, :heads]))[..., None]
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"][:heads]))


def experts_layer(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] -> (the held experts' part + the shared expert [B, S, d],
    chosen experts [B, S, k])."""
    # assumed (3): sigmoid scores, the k largest of score + bias
    score = jax.nn.sigmoid(u @ _f32(w["router"]))
    _, sel = jax.lax.top_k(score + _f32(w["router_bias"]), sizes["top_k"])
    chosen = jnp.take_along_axis(score, sel, -1)
    weight = sizes["routed_scaling_factor"] * chosen / chosen.sum(
        -1, keepdims=True)
    y = jnp.zeros_like(u)
    for e in range(experts["w_gate"].shape[0]):
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(u, experts["w_gate"][e], experts["w_up"][e],
                              experts["w_down"][e])
    if sizes.get("shared", True):
        # assumed (4): the shared expert ungated and unscaled
        y = y + _swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
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
              sizes: dict, expert_offset: int = 0):
    """One block on the stream ``x [B, S, d]``, float32, highest
    precision."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + attention(_rms(x, attn["rms"], eps), attn, attn_kind, sizes)
        u = _rms(x, mlp["rms"], eps)
        if mlp_kind == "D":
            return x + _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        return x + experts_layer(u, mlp, experts, sizes, expert_offset)[0]


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def laguna_ref_logits(params, tokens, sizes: dict, attn_kinds: str,
                      mlp_kinds: str, expert_offset: int = 0):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: the fields of ``LagunaConfig`` (and the switches above);
    ``attn_kinds`` / ``mlp_kinds``: a letter a layer that runs (``F`` / ``W``,
    ``D`` / ``E``)."""
    x = _f32(params["wte"][tokens])
    for attn_kind, mlp_kind, attn, mlp, experts in layer_weights(
            params, attn_kinds, mlp_kinds):
        x = ref_layer(x, attn, mlp, experts, attn_kind=attn_kind,
                      mlp_kind=mlp_kind, sizes=sizes,
                      expert_offset=expert_offset)
    return ref_head(x, params, sizes)
