"""KV-cache inference path for GPT-2: prefill + single-token decode.

The serving analog of the training forward in ``gpt2.py`` (reference role:
the model runner inside the vLLM engine the reference wraps, ray
``python/ray/llm/_internal/serve/engines/vllm/``).  TPU-first decisions:

  - the KV cache is a pair of layer-stacked **head-major** arrays
    ``[L, B, H, T_max, D]`` living in HBM across steps — this layout means
    neither prefill writes nor decode reads ever transpose the cache on
    the hot path;
  - cache writes are **deferred**: each layer's current-token k/v is merged
    into attention analytically (``k_self``/``v_self`` in
    ``ops/decode_attention.py``) and all 2L writes collapse into one
    ``write_token_to_cache`` a cache array at the end of the step, which
    updates the one tile of rows that holds each slot's position, in the
    donated cache;
  - per-slot positions make the batch *ragged*: each sequence attends only
    to its own ``[0, pos]`` prefix;
  - the layer loop is a Python loop (static layer indices; L compile-time
    bodies are fine for decoders).

Device operations carry ``jax.named_scope``s ``gpt2.embed``, ``gpt2.attn``
(norm, projections, attention, residual, the cache write), ``gpt2.mlp`` and
``gpt2.head`` (final norm + vocabulary product), as ``gpt2.py``'s.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .gpt2 import GPT2Config
from .layers import layernorm


def gpt2_init_cache(cfg: GPT2Config, batch: int, max_len: int):
    shape = (cfg.n_layer, batch, cfg.n_head, max_len, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _qkv(x, layer):
    qkv = jnp.einsum("bse,ethd->bsthd", x, layer["wqkv"]) + layer["bqkv"]
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _masked_attention(q, k, v, mask):
    """q [B,S,H,D] over k/v [B,S,H,D] with bool mask [B,S,S]."""
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / (q.shape[-1] ** 0.5)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def gpt2_prefill(
    params, tokens, lengths, cache, cfg: GPT2Config
) -> Tuple[jnp.ndarray, dict]:
    """Run the prompt through the model, filling the cache.

    tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with positions [0, S) written).
    """
    b, s = tokens.shape
    with jax.named_scope("gpt2.embed"):
        x = params["wte"][tokens] + params["wpe"][:s][None]
        x = x.astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("gpt2.attn"):
        causal = jnp.tril(jnp.ones((s, s), bool))[None]  # [1, S, S]

    def body(x, layer):
        with jax.named_scope("gpt2.attn"):
            y = layernorm(x, layer["ln1_g"], layer["ln1_b"])
            q, k, v = _qkv(y, layer)
            o = _masked_attention(q, k, v, causal)
            x = x + (
                jnp.einsum("bshd,hde->bse", o, layer["wo"]) + layer["bo"]
            ).astype(x.dtype)
        with jax.named_scope("gpt2.mlp"):
            y = layernorm(x, layer["ln2_g"], layer["ln2_b"])
            h = jax.nn.gelu(
                jnp.einsum("bse,ef->bsf", y, layer["wi"]) + layer["bi"])
            x = x + (
                jnp.einsum("bsf,fe->bse", h, layer["wo2"]) + layer["bo2"]
            ).astype(x.dtype)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    with jax.named_scope("gpt2.attn"):  # the cache write is attention's
        # ks/vs: [L, B, S, H, D] → head-major [L, B, H, S, D].
        ks = ks.transpose(0, 1, 3, 2, 4).astype(cache["k"].dtype)
        vs = vs.transpose(0, 1, 3, 2, 4).astype(cache["v"].dtype)
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], ks, (0, 0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vs, (0, 0, 0, 0, 0)),
        }
    with jax.named_scope("gpt2.head"):
        x = layernorm(x, params["lnf_g"], params["lnf_b"])
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        logits = jnp.einsum("be,ve->bv", last, params["wte"])
        return logits.astype(jnp.float32), cache


def gpt2_decode_step(
    params, tokens, pos, cache, cfg: GPT2Config
) -> Tuple[jnp.ndarray, dict]:
    """One generation step for a ragged batch.

    tokens: [B] the most recent token per slot; pos: [B] its position.
    Writes k/v at ``pos`` and attends each slot to its own ``[0, pos]``.
    Returns (logits [B, V], updated cache).
    """
    from ..ops.decode_attention import (decode_attention,
                                        write_token_to_cache)

    b = tokens.shape[0]
    with jax.named_scope("gpt2.embed"):
        x = params["wte"][tokens] + params["wpe"][pos]
        x = x.astype(jnp.dtype(cfg.dtype))  # [B, E]
    ck, cv = cache["k"], cache["v"]
    new_ks, new_vs = [], []

    for l in range(cfg.n_layer):
        # A layer's slices are read by both parts; attention's come first.
        with jax.named_scope("gpt2.attn"):
            layer = jax.tree.map(lambda a: a[l], params["blocks"])
            y = layernorm(x, layer["ln1_g"], layer["ln1_b"])
            qkv = (jnp.einsum("be,ethd->bthd", y, layer["wqkv"])
                   + layer["bqkv"])
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [B, H, D]
            new_ks.append(k.astype(ck.dtype))
            new_vs.append(v.astype(cv.dtype))
            # Deferred-scatter protocol: the cache holds [0, pos-1]; the
            # current token's k/v are one more column of the softmax, and
            # written once below for all layers.
            o = decode_attention(
                q, ck, cv, pos, l, k_self=new_ks[-1], v_self=new_vs[-1]
            )  # [B, H, D]
            x = x + (
                jnp.einsum("bhd,hde->be", o.astype(y.dtype), layer["wo"])
                + layer["bo"]
            ).astype(x.dtype)
        with jax.named_scope("gpt2.mlp"):
            y = layernorm(x, layer["ln2_g"], layer["ln2_b"])
            h = jax.nn.gelu(
                jnp.einsum("be,ef->bf", y, layer["wi"]) + layer["bi"])
            x = x + (
                jnp.einsum("bf,fe->be", h, layer["wo2"]) + layer["bo2"]
            ).astype(x.dtype)

    with jax.named_scope("gpt2.attn"):  # the cache write is attention's
        ck = write_token_to_cache(ck, jnp.stack(new_ks), pos, axis=3)
        cv = write_token_to_cache(cv, jnp.stack(new_vs), pos, axis=3)
    with jax.named_scope("gpt2.head"):
        x = layernorm(x, params["lnf_g"], params["lnf_b"])
        logits = jnp.einsum("be,ve->bv", x, params["wte"])
        return logits.astype(jnp.float32), {"k": ck, "v": cv}
