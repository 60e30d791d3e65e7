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

from .gpt2 import GPT2Config, _layernorm


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
            y = _layernorm(x, layer["ln1_g"], layer["ln1_b"])
            q, k, v = _qkv(y, layer)
            o = _masked_attention(q, k, v, causal)
            x = x + (
                jnp.einsum("bshd,hde->bse", o, layer["wo"]) + layer["bo"]
            ).astype(x.dtype)
        with jax.named_scope("gpt2.mlp"):
            y = _layernorm(x, layer["ln2_g"], layer["ln2_b"])
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
        x = _layernorm(x, params["lnf_g"], params["lnf_b"])
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
            y = _layernorm(x, layer["ln1_g"], layer["ln1_b"])
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
            y = _layernorm(x, layer["ln2_g"], layer["ln2_b"])
            h = jax.nn.gelu(
                jnp.einsum("be,ef->bf", y, layer["wi"]) + layer["bi"])
            x = x + (
                jnp.einsum("bf,fe->be", h, layer["wo2"]) + layer["bo2"]
            ).astype(x.dtype)

    with jax.named_scope("gpt2.attn"):  # the cache write is attention's
        ck = write_token_to_cache(ck, jnp.stack(new_ks), pos, axis=3)
        cv = write_token_to_cache(cv, jnp.stack(new_vs), pos, axis=3)
    with jax.named_scope("gpt2.head"):
        x = _layernorm(x, params["lnf_g"], params["lnf_b"])
        logits = jnp.einsum("be,ve->bv", x, params["wte"])
        return logits.astype(jnp.float32), {"k": ck, "v": cv}


def sample_logits(logits, key, temperature, top_k: int = 0, top_p: float = 1.0):
    """Temperature / top-k / top-p sampling on [B, V] logits (greedy when
    temperature == 0)."""
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)
    scaled = logits / temp
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    if top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Smallest set with cumulative prob >= top_p; find the cutoff logit.
        cutoff_idx = jnp.argmax(cum >= top_p, axis=-1)
        cutoff = jnp.take_along_axis(
            sorted_logits, cutoff_idx[:, None], axis=-1
        )
        scaled = jnp.where(scaled < cutoff, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_logits_rows(logits, key, temperature, top_k, top_p):
    """``sample_logits`` with one set of parameters PER ROW, as ``[B]``
    arrays (float32, int32, float32) and not static arguments: one compiled
    program serves every mix of parameters.  Returns ``(tokens[B] int32,
    key')``: the key is split in here, so a caller dispatches nothing else.

    Row ``i`` is distributed as ``sample_logits(logits[i:i+1], key,
    temperature[i], top_k[i], top_p[i])``: ``argmax`` where
    ``temperature <= 0``; else one descending sort of the scaled row gives
    the k-th value (top-k, where ``top_k > 0``) and, on the sorted softmax,
    the smallest set reaching ``top_p`` (where ``top_p < 1``)."""
    key, sub = jax.random.split(key)
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    ranked = jnp.sort(scaled, axis=-1, descending=True)
    kth = jnp.take_along_axis(
        ranked, (jnp.clip(top_k, 1, vocab) - 1)[:, None], axis=-1)
    kth = jnp.where((top_k > 0)[:, None], kth, -jnp.inf)
    # Masking the sorted row keeps it sorted: no second sort for top-p.
    ranked = jnp.where(ranked < kth, -1e30, ranked)
    cum = jnp.cumsum(jax.nn.softmax(ranked, axis=-1), axis=-1)
    cutoff_idx = jnp.argmax(cum >= top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(ranked, cutoff_idx[:, None], axis=-1)
    cutoff = jnp.where((top_p < 1.0)[:, None], cutoff, -jnp.inf)
    # The cutoff is one of the values top-k kept, or nothing: one mask.
    scaled = jnp.where(scaled < jnp.maximum(kth, cutoff), -1e30, scaled)
    sampled = jax.random.categorical(sub, scaled, axis=-1)
    tokens = jnp.where(temperature <= 0.0, greedy, sampled)
    return tokens.astype(jnp.int32), key


def sample_logits_greedy(logits):
    """``sample_logits_rows`` when every row has ``temperature <= 0``:
    ``argmax`` alone, no sort and no key.  ``[B, V] -> [B]`` int32."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
