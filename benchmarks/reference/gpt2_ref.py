"""GPT-2 forward and loss, plain: float32 ``jax.numpy``, no kernels, no
remat, no scan, no sharding.  Follows Radford et al. 2019 / the published
``modeling_gpt2``: pre-LN blocks, learned positions, tied unembedding.

Departures, all the program's and followed here so that the two compute the
same function: GELU is ``jax.nn.gelu``'s tanh approximation (the published
``gelu_new``); the vocabulary is padded to 50304 rows; weights are the
program's layer-stacked pytree (``gpt2_init``), upcast to float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gpt2_ref_logits(params, tokens, n_head: int):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        x = p["wte"][tokens] + p["wpe"][:s][None]
        blocks = p["blocks"]
        mask = jnp.tril(jnp.ones((s, s), bool))
        for l in range(blocks["wqkv"].shape[0]):
            w = {k: v[l] for k, v in blocks.items()}
            y = _ln(x, w["ln1_g"], w["ln1_b"])
            qkv = jnp.einsum("bse,ethd->bsthd", y, w["wqkv"]) + w["bqkv"]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            d = q.shape[-1]
            sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(float(d))
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
            x = x + jnp.einsum("bshd,hde->bse", o, w["wo"]) + w["bo"]
            y = _ln(x, w["ln2_g"], w["ln2_b"])
            hdn = jax.nn.gelu(jnp.einsum("bse,ef->bsf", y, w["wi"]) + w["bi"])
            x = x + jnp.einsum("bsf,fe->bse", hdn, w["wo2"]) + w["bo2"]
        x = _ln(x, p["lnf_g"], p["lnf_b"])
        return jnp.einsum("bse,ve->bsv", x, p["wte"])


def gpt2_ref_loss(params, tokens, n_head: int):
    """Mean next-token cross-entropy; tokens [B, S+1]."""
    logits = gpt2_ref_logits(params, tokens[:, :-1], n_head)
    logp = jax.nn.log_softmax(logits, -1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -gold.mean()
