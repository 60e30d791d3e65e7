"""Olmo-Hybrid forward, plain: float32 ``jax.numpy``, one full causal forward,
no cache, no chunks, no batching tricks: the gated delta rule by its
RECURRENCE (a ``lax.scan`` over positions), dense attention scores.

Follows ``config.json`` of ``allenai/Olmo-Hybrid-7B`` (``model_type``
``olmo_hybrid``; the ``linear_*`` keys are ``qwen3_next``'s, whose plain
``torch_recurrent_gated_delta_rule`` ``gated_delta_rule`` below is held to by
``tests/test_olmo_hybrid.py``) and the equations in
``ray_tpu/models/olmo_hybrid.py``'s docstring.  Linear attention: ``[q~ | k~
| v~] = u Wqkv``, ``z = u Wg``, ``a = u Wa``, ``b = u Wb``; ``(q, k, v) =
silu(conv(q~ | k~ | v~))``; ``q``, ``k`` L2-normalised a head, ``q`` times
``dk^-1/2``; ``beta = 2 sigmoid(b)`` (``sigmoid(b)`` without
``allow_neg_eigval``); ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``;
``S' = alpha_t S_{t-1}``, ``S_t = S' + k_t (x) beta_t (v_t - S'^T k_t)``, ``o_t =
S_t^T q_t``; ``RMSNorm_dv(o) * w * silu(z)``; ``Wo``.  Full attention: ``q``,
``k`` RMS-normalised over the whole projection, causal softmax at ``D^-1/2``,
``Wo``.  MLP: SwiGLU.

What the config does not say, the program's choice and followed here
(``assumed`` in the configuration file): no rotary term in the full layers
(``rope_theta`` null); a linear layer is pre-norm (``x + GDN(RMSNorm(x))``, ``x
+ MLP(RMSNorm(x))``), a full layer Olmo 3's reordered norm (``x +
RMSNorm(Attn(x))``, ``x + RMSNorm(MLP(x))``); no convolution bias; ``q``,
``k``, ``v`` projected by one matrix whose column blocks are the three.
Weights are the program's pytree (one stack a kind of layer), upcast matrix
by matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _mlp(u, w):
    return (jax.nn.silu(u @ _f32(w["w_gate"])) * (u @ _f32(w["w_up"]))
            ) @ _f32(w["w_down"])


def gated_delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, token by token.  q, k ``[B, S, H, dk]`` (already
    normalised and scaled), v ``[B, S, H, dv]``, g = ``log alpha`` and beta
    ``[B, S, H]`` -> (o ``[B, S, H, dv]``, the last state ``[B, H, dk, dv]``)."""
    bsz, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)

    def step(s, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        s = jnp.exp(g_t)[..., None, None] * s
        kv = (s * k_t[..., None]).sum(-2)  # S'^T k  [B, H, dv]
        s = s + k_t[..., None] * (beta_t[..., None] * (v_t - kv))[:, :, None]
        return s, (s * q_t[..., None]).sum(-2)

    state, o = jax.lax.scan(
        step, state, tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), state


def gated_delta_net(u, w, sizes):
    """u [B, S, d] normed -> [B, S, d]; ``w``: one linear layer's weights."""
    h, dk = sizes["linear_num_heads"], sizes["linear_key_head_dim"]
    dv, taps = sizes["linear_value_head_dim"], w["conv_w"].shape[0]
    bsz, s, _ = u.shape
    qkv = u @ _f32(w["w_qkv"])
    z = u @ _f32(w["w_g"])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(u @ _f32(w["w_a"])
                                               + w["dt_bias"])
    beta = jax.nn.sigmoid(u @ _f32(w["w_b"]))
    if sizes["allow_neg_eigval"]:
        beta = 2.0 * beta
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * w["conv_w"][j]
                          for j in range(taps)))
    q = qkv[..., :h * dk].reshape(bsz, s, h, dk)
    k = qkv[..., h * dk:2 * h * dk].reshape(bsz, s, h, dk)
    v = qkv[..., 2 * h * dk:].reshape(bsz, s, h, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / jnp.sqrt(
        float(dk))
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    o, _ = gated_delta_rule(q, k, v, g, beta)
    o = _rms(o, w["norm"], sizes["rms_eps"])  # over dv, a head
    return (o.reshape(bsz, s, h * dv) * jax.nn.silu(z)) @ _f32(w["w_o"])


def attention(x, w, sizes):
    """x [B, S, d] (a full layer's input: the stream itself) -> [B, S, d]:
    30 heads each with its own keys, causal, no rope."""
    bsz, s, _ = x.shape
    h, d = sizes["n_head"], sizes["head_dim"]

    def whole_norm(a, g):  # over the whole projection [H D]
        return _rms(a.reshape(bsz, s, h * d), _f32(g).reshape(h * d),
                    sizes["rms_eps"]).reshape(bsz, s, h, d)

    q = whole_norm(jnp.einsum("bse,ehd->bshd", x, _f32(w["wq"])), w["q_norm"])
    k = whole_norm(jnp.einsum("bse,ehd->bshd", x, _f32(w["wk"])), w["k_norm"])
    v = jnp.einsum("bse,ehd->bshd", x, _f32(w["wv"]))
    sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(float(d))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bshd,hde->bse", o, _f32(w["wo"]))


def ref_layer(x, kind: str, w, sizes: dict):
    """One block on the float32 stream ``x [B, S, d]``: ``kind`` is the
    pattern's letter, ``w`` that layer's weights."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        if kind == "L":
            x = x + gated_delta_net(_rms(x, w["rms_mix"], eps), w, sizes)
            return x + _mlp(_rms(x, w["rms_mlp"], eps), w)
        x = x + _rms(attention(x, w, sizes), w["rms_mix"], eps)
        return x + _rms(_mlp(x, w), w["rms_mlp"], eps)


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def layer_weights(params, kinds: str):
    """For each layer of ``kinds``: (kind, its weights), each taken from the
    front of its kind's stack."""
    names = {"L": "linear", "F": "full"}
    seen = dict.fromkeys(names, 0)
    for kind in kinds:
        i = seen[kind]
        seen[kind] += 1
        yield kind, {k: v[i] for k, v in params["blocks"][names[kind]].items()}


def olmo_hybrid_ref_logits(params, tokens, sizes: dict, kinds: str):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: ``n_head``, ``head_dim``, ``linear_num_heads``,
    ``linear_key_head_dim``, ``linear_value_head_dim``, ``allow_neg_eigval``,
    ``rms_eps``; ``kinds``: the letters of the layers to run."""
    x = _f32(params["wte"][tokens])
    for kind, w in layer_weights(params, kinds):
        x = ref_layer(x, kind, w, sizes)
    return ref_head(x, params, sizes)
