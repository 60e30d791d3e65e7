"""Granite-4.0-H forward, plain: float32 ``jax.numpy``, one full causal
forward, no cache, no chunks, no batching tricks: Mamba-2 by its RECURRENCE
(a ``lax.scan`` over positions), dense attention scores.  Imports nothing of
the program under test.

Follows ``config.json`` of ``ibm-granite/granite-4.0-h-micro`` (``model_type``
``granitemoehybrid``) and ``transformers``' plain torch path for the family
(``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridRMSNormGated``,
``GraniteMoeHybridMLP``, ``GraniteMoeHybridDecoderLayer.forward``), which
``tests/test_granite_h.py`` holds this file to with the same weights copied
in: ``x_0 = embedding_multiplier E[token]``; a layer is ``x = x +
residual_multiplier mixer(RMSNorm(x))`` then ``x = x + residual_multiplier
MLP(RMSNorm(x))``; ``logits = RMSNorm(x) E^T / logits_scaling`` with the SAME
table ``E``.  Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) +
b)`` split ``x | B | C``; ``dt = softplus(dt + dt_bias)`` (no clamp); ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``; ``y_t = S_t C_t + D x_t``;
``RMSNorm(y silu(z))`` over a group's channels, the gate BEFORE the norm;
``W_out``.  Attention: grouped queries, no positional term, causal softmax of
``q k^T attention_multiplier``.  MLP: ``(silu(g) h) W_out`` with ``g | h = u
W_in``.

Departures from the published modelling code, the program's and followed
here (``assumed`` in the configuration file): ``in_proj`` is stored as its
three column blocks ``w_z | w_xbc | w_dt`` and the MLP's ``input_linear`` as
its two, ``w_gate | w_up`` (the same products); the residual stream and the
state are float32.  Weights are the program's pytree (one stack a kind of
mixer, one MLP stack as long as the model), upcast matrix by matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


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
    """u [B, S, d] normed -> [B, S, d]: grouped-query, causal, no positional
    term, scores times ``attention_multiplier``."""
    s = u.shape[1]
    groups = sizes["n_head"] // sizes["n_kv_head"]
    q = jnp.einsum("bse,ehd->bshd", u, _f32(w["wq"]))
    k = jnp.repeat(jnp.einsum("bse,ekd->bskd", u, _f32(w["wk"])), groups, 2)
    v = jnp.repeat(jnp.einsum("bse,ekd->bskd", u, _f32(w["wv"])), groups, 2)
    sc = jnp.einsum("bshd,bthd->bhst", q, k) * sizes["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"]))


def mlp(u, w):
    return (jax.nn.silu(u @ _f32(w["w_gate"])) * (u @ _f32(w["w_up"]))
            ) @ _f32(w["w_down"])


def ref_embed(params, tokens, sizes: dict):
    return _f32(params["wte"][tokens]) * sizes["embedding_multiplier"]


def ref_layer(x, kind: str, w, w_mlp, sizes: dict):
    """One layer on the float32 stream ``x [B, S, d]``: ``kind`` is the
    pattern's letter, ``w`` that mixer's weights, ``w_mlp`` the layer's
    MLP's."""
    eps, scale = sizes["rms_eps"], sizes["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["rms"], eps)
        x = x + scale * (mamba2(u, w, sizes) if kind == "M"
                         else attention(u, w, sizes))
        return x + scale * mlp(_rms(x, w_mlp["rms"], eps), w_mlp)


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["wte"])
                          ) / sizes["logits_scaling"]


def layer_weights(params, kinds: str):
    """For each layer of ``kinds``: (kind, its mixer's weights from the front
    of the kind's stack, its MLP's from the MLP stack at the layer's own
    index)."""
    names = {"M": "mamba", "*": "attn"}
    seen = dict.fromkeys(names, 0)
    for layer, kind in enumerate(kinds):
        i = seen[kind]
        seen[kind] += 1
        yield (kind,
               {k: v[i] for k, v in params["blocks"][names[kind]].items()},
               {k: v[layer] for k, v in params["blocks"]["mlp"].items()})


def granite_h_ref_logits(params, tokens, sizes: dict, kinds: str):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: ``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``,
    ``ssm_state_size``, ``n_head``, ``n_kv_head``, the four multipliers,
    ``rms_eps``; ``kinds``: the letters of the layers to run."""
    x = ref_embed(params, tokens, sizes)
    for kind, w, w_mlp in layer_weights(params, kinds):
        x = ref_layer(x, kind, w, w_mlp, sizes)
    return ref_head(x, params, sizes)
