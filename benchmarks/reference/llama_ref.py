"""Mistral / Llama-style decoder forward, plain: float32 ``jax.numpy``, one
full causal forward, no cache, no kernels, no scan.  RMSNorm, rotary
positions, grouped-query attention, SwiGLU, untied output head (Jiang et al.
2023, "Mistral 7B"; no sliding window in v0.3).

Departure, the program's and followed here: rotary embedding rotates
interleaved pairs ``(x[2i], x[2i+1])`` as ``ray_tpu.models.llama.rope`` does;
the published code rotates halves ``(x[i], x[i + d/2])``.  The two are the
same function up to a fixed permutation of each head's q/k columns.
Weights are the program's layer-stacked pytree, upcast to float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, S, H, D], positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def llama_ref_logits(params, tokens, n_head: int, n_kv_head: int,
                     rope_theta: float, rms_eps: float):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    groups = n_head // n_kv_head
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[1]
        x = p["wte"][tokens]
        mask = jnp.tril(jnp.ones((s, s), bool))
        blocks = p["blocks"]
        for l in range(blocks["wq"].shape[0]):
            w = {k: v[l] for k, v in blocks.items()}
            y = _rms(x, w["rms1"], rms_eps)
            q = _rope(jnp.einsum("bse,ehd->bshd", y, w["wq"]), rope_theta)
            k = _rope(jnp.einsum("bse,ekd->bskd", y, w["wk"]), rope_theta)
            v = jnp.einsum("bse,ekd->bskd", y, w["wv"])
            k, v = jnp.repeat(k, groups, 2), jnp.repeat(v, groups, 2)
            sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(
                float(q.shape[-1]))
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
            x = x + jnp.einsum("bshd,hde->bse", o, w["wo"])
            y = _rms(x, w["rms2"], rms_eps)
            gate = jax.nn.silu(jnp.einsum("bse,ef->bsf", y, w["w_gate"]))
            up = jnp.einsum("bse,ef->bsf", y, w["w_up"])
            x = x + jnp.einsum("bsf,fe->bse", gate * up, w["w_down"])
        x = _rms(x, p["rms_f"], rms_eps)
        return jnp.einsum("bse,ve->bsv", x, p["lm_head"])
