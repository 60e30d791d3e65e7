"""Sampling a token a row from ``[B, V]`` logits: no family's, the engine's.

``llm/engine.py`` jits ``sample_logits_rows`` and ``sample_logits_greedy``,
``llm/disagg.py`` ``sample_logits``.  THE NAMES ARE READ:
``benchmarks/lib/host_spans.py`` finds the samplers in a device trace as
``^jit_sample_logits``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
