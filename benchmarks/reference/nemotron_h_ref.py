"""Nemotron-H forward, plain: float32 ``jax.numpy``, one full causal forward,
no cache, no chunks, no batching tricks: Mamba-2 by its RECURRENCE (a
``lax.scan`` over positions), dense attention scores, every expert it is
given run on every token and weighed by the router's choice.

Follows ``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``
(``model_type`` ``nemotron_h``) and the equations in
``ray_tpu/models/nemotron_h.py``'s docstring: every block is ``x + mixer(
RMSNorm(x))``, the mixer by the pattern's letter.  Mamba-2: ``[z | xBC | dt]
= u W_in``; ``xBC = silu(conv(xBC) + b)`` split ``x | B | C``; ``dt =
softplus(dt + dt_bias)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``; ``y_t
= S_t C_t + D x_t``; gated grouped RMSNorm; ``W_out``.  Experts: sigmoid
scores, top-k of ``score + bias``, weights renormalised and scaled, experts
``relu(v W1)^2 W2`` in the latent ``v = u W_dl``, ``W_ul``, plus the shared
expert.

Departures from the published modelling code, the program's and followed
here (``assumed`` in the configuration file): ``in_proj`` is stored as its
three column blocks ``w_z | w_xbc | w_dt``; attention has no rotary term (the
published ``nemotron_h`` attention applies none); one latent pair ``W_dl`` /
``W_ul`` a layer; no multi-token-prediction head.  The share: given
``expert_offset`` and the held experts in ``params["experts"]``, routed
experts outside ``[offset, offset + held)`` add nothing, as in the program;
with every expert held it is the uncut model.  Weights are the program's
pytree (one stack a kind of layer), upcast matrix by matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _relu2(u, w1, w2):
    return jnp.square(jax.nn.relu(u @ _f32(w1))) @ _f32(w2)


def mamba2(u, w, sizes):
    """u [B, S, d] normed -> [B, S, d]; ``w``: one layer's weights."""
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    k = w["conv_w"].shape[0]
    bsz, s, _ = u.shape
    z = u @ _f32(w["w_z"])
    xbc = u @ _f32(w["w_xbc"])
    dt = jax.nn.softplus(u @ _f32(w["w_dt"]) + w["dt_bias"])  # [B, S, H]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + s] * w["conv_w"][j]
                          for j in range(k)) + w["conv_b"])
    x = xbc[..., :h * p].reshape(bsz, s, h, p)
    b = xbc[..., h * p:h * p + g * n].reshape(bsz, s, g, n)
    c = xbc[..., h * p + g * n:].reshape(bsz, s, g, n)
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))  # a head's group
    a = -jnp.exp(w["a_log"])

    def step(state, inp):  # state [B, H, P, N]
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return state, (state * c_t[:, :, None]).sum(-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        tuple(v.swapaxes(0, 1) for v in (x, b, c, dt)))
    y = y.swapaxes(0, 1) + w["d_skip"][:, None] * x  # [B, S, H, P]
    y = (y.reshape(bsz, s, h * p) * jax.nn.silu(z)).reshape(bsz, s, g, -1)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + sizes["rms_eps"])
    return (y.reshape(bsz, s, h * p) * _f32(w["norm"])) @ _f32(w["w_out"])


def attention(u, w, sizes):
    """u [B, S, d] normed -> [B, S, d]: grouped-query, causal, no rope."""
    s = u.shape[1]
    groups = sizes["n_head"] // sizes["n_kv_head"]
    q = jnp.einsum("bse,ehd->bshd", u, _f32(w["wq"]))
    k = jnp.repeat(jnp.einsum("bse,ekd->bskd", u, _f32(w["wk"])), groups, 2)
    v = jnp.repeat(jnp.einsum("bse,ekd->bskd", u, _f32(w["wv"])), groups, 2)
    sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(float(q.shape[-1]))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"]))


def latent_moe(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] normed -> (the held experts' part through W_ul, the
    shared expert's part, the chosen experts [B, S, k])."""
    p = jax.nn.sigmoid(u @ _f32(w["router"]))
    _, sel = jax.lax.top_k(p + _f32(w["router_bias"]), sizes["top_k"])
    chosen = jnp.take_along_axis(p, sel, -1)
    weight = sizes["routed_scaling_factor"] * chosen / chosen.sum(
        -1, keepdims=True)
    v = u @ _f32(w["w_dl"])

    def add_expert(y, inp):
        e, w1, w2 = inp
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        return y + w_e * _relu2(v, w1, w2), None

    held = experts["w1"].shape[0]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(v),
                        (jnp.arange(held), experts["w1"], experts["w2"]))
    return y @ _f32(w["w_ul"]), _relu2(u, w["ws1"], w["ws2"]), sel


def ref_layer(x, kind: str, w, experts, sizes: dict, expert_offset: int = 0):
    """One block on the float32 stream ``x [B, S, d]``: ``kind`` is the
    pattern's letter, ``w`` that layer's weights (``experts`` its held
    experts, for ``E``)."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["rms"], sizes["rms_eps"])
        if kind == "M":
            return x + mamba2(u, w, sizes)
        if kind == "*":
            return x + attention(u, w, sizes)
        routed, shared, _ = latent_moe(u, w, experts, sizes, expert_offset)
        return x + routed + shared


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def layer_weights(params, kinds: str):
    """For each layer of ``kinds``: (kind, its weights, its experts or
    None), each taken from the front of its kind's stack."""
    names = {"M": "mamba", "*": "attn", "E": "moe"}
    seen = dict.fromkeys(names, 0)
    for kind in kinds:
        i = seen[kind]
        seen[kind] += 1
        w = {k: v[i] for k, v in params["blocks"][names[kind]].items()}
        experts = ({k: v[i] for k, v in params["experts"].items()}
                   if kind == "E" else None)
        yield kind, w, experts


def nemotron_h_ref_logits(params, tokens, sizes: dict, kinds: str,
                          expert_offset: int = 0):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: ``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``,
    ``ssm_state_size``, ``n_head``, ``n_kv_head``, ``top_k``,
    ``routed_scaling_factor``, ``rms_eps``; ``kinds``: the letters of the
    layers to run."""
    x = _f32(params["wte"][tokens])
    for kind, w, experts in layer_weights(params, kinds):
        x = ref_layer(x, kind, w, experts, sizes, expert_offset)
    return ref_head(x, params, sizes)
