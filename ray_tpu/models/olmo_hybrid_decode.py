"""Olmo-Hybrid prefill + decode through a cache whose leaves are of two kinds.

``{"k", "v": [Lf, B, H, T, D]}`` are the full layers' keys (after their norm)
and values, with a position axis, as the Llama family's; ``{"conv": [Ll, B,
(K-1)(2 H dk + H dv)], "state": [Ll, B, H / p, dk, p dv]}`` are the linear
layers' state, float32, with NO position axis: the convolution's last ``K-1``
inputs (oldest first, side by side, as Nemotron-H's) and the delta rule's
matrix ``S [dk, dv]`` a head after the slot's last token, ``p`` heads side by
side on the lanes (``olmo_hybrid.pack_state``: two heads of 192 are three
tiles of 128, one alone would be padded to two).  The slot axis is axis 1 of
every leaf, which is all ``llm/engine.py`` knows: ``init_cache(cfg, 1, rung)``
gives a one-slot row whose state leaves do not depend on the rung, and
``splice_row`` writes it over the slot's, so an admission replaces a slot's
state WHOLE while its keys and values beyond the rung keep what the last
tenant left (decode reads nothing at or beyond ``pos``).

Prefill runs the chunked scan over the padded prompt with ``beta = 0`` and
``g = 0`` at positions ``>= length``: the state it returns is the state at
the prompt's TRUE length, whatever the rung, and the convolution's state is
its last ``K-1`` true inputs.  Decode runs one step of the recurrence for all
slots, in float32, in ONE pass over ``S`` (``ops.delta_update``: on a TPU a
Pallas kernel that holds a slot's rows in fast memory for ``S^T k``, ``S^T q``
and ``alpha S + k (x) delta``; ``o_t = alpha S^T q + (k . q) delta`` needs no
reading of the new state), written over the layer's blocks of the stacked
leaf where they lie: the whole leaf goes through the nine calls, aliased (the
engine donates the cache; ``tests/test_tpu_compile.py`` reads the compiled
step for a copy or a slice); the ``conv`` leaf goes through the same nine
layers whole (``ops.conv_update``: a layer's window read out of the leaf,
the shifted window written over the layer it was read from).  Attention
goes by the
deferred-scatter protocol of ``llama_decode.py``: the cache holds ``[0,
pos-1]``, the current key and value are merged as a last score, and all are
written at the step's end by ``write_token_to_cache``.

A decode row at position 0 is an idle slot (a prompt has at least one
token).  Its state is computed like any other's and stays finite: ``k`` is
normalised and ``beta < 2``, so a step's map on ``S`` never expands.  Both
return ``(logits, cache)``; with ``with_counts=True`` (the family's
``*_counted`` twins, which the engine runs) ``(logits, cache, counts)``: the
counts of ``olmo_hybrid.py`` as int32 scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.conv_update import conv_update
from ..ops.decode_attention import decode_attention, write_token_to_cache
from ..ops.delta_update import delta_update
from .layers import matmul, rmsnorm
from .olmo_hybrid import (STACK, OlmoHybridConfig, attention_project, block,
                          delta_output, delta_project, olmo_hybrid_forward,
                          split_heads)


def olmo_hybrid_init_cache(cfg: OlmoHybridConfig, batch: int, max_len: int):
    nl, nf = cfg.kinds.count("L"), cfg.kinds.count("F")
    kv = (nf, batch, cfg.n_head, max_len, cfg.head_dim)
    dt, p = jnp.dtype(cfg.dtype), cfg.state_pack
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((nl, batch, (cfg.conv_kernel - 1) * cfg.d_conv),
                          jnp.float32),
        "state": jnp.zeros(
            (nl, batch, cfg.linear_num_heads // p, cfg.linear_key_head_dim,
             p * cfg.linear_value_head_dim), jnp.float32),
    }


def olmo_hybrid_prefill(
    params, tokens, lengths, cache, cfg: OlmoHybridConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with keys and values of positions
    [0, S) written and the state after position ``length - 1`` in place of
    the slot's, counts of the positions scanned)."""
    x, kept, counts = olmo_hybrid_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        full = name in ("k", "v")
        with jax.named_scope("olmo.attn" if full else "olmo.delta"):
            if full:  # [Lf, B, S, H, D] -> head-major
                new = new.transpose(0, 1, 3, 2, 4)
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("olmo.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def delta_step_at(y, conv_leaf, leaf, at: int, m, i: int,
                  cfg: OlmoHybridConfig):
    """One token a row through linear layer ``i``, whose state is layer ``at``
    of the two stacked leaves ``conv_leaf [layers, B, (K-1)(2 H dk + H dv)]``
    and ``leaf [layers, B, H / p, dk, p dv]``.  y ``[B, d]`` -> (``[B, d]``
    float32, the two leaves with layer ``at`` updated where it lies:
    ``ops.conv_update`` and ``ops.delta_update``)."""
    qkv, z, g, beta = delta_project(y, m, i, cfg)
    conv, conv_leaf = conv_update(conv_leaf, at, qkv, m["conv_w"][i])
    q, k, v = split_heads(jax.nn.silu(conv), cfg)  # [B, H, dk], [B, H, dv]
    o, leaf = delta_update(leaf, at, q, k, v, jnp.exp(g)[..., None],
                           beta[..., None])
    return delta_output(o, z, m, i, cfg), conv_leaf, leaf


def delta_step(y, conv_state, state, m, i: int, cfg: OlmoHybridConfig):
    """``delta_step_at`` on ONE layer's window ``[B, (K-1)(2 H dk + H dv)]``
    and state ``[B, H / p, dk, p dv]`` (stacks of one: the same kernel) ->
    (``[B, d]`` float32, the two after the token, in the dtypes they came
    in)."""
    out, conv, leaf = delta_step_at(
        y, conv_state[None], state[None], 0, m, i, cfg)
    return out, conv[0], leaf[0]


def olmo_hybrid_decode_step(
    params, tokens, pos, cache, cfg: OlmoHybridConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    with jax.named_scope("olmo.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
    cache = dict(cache)
    new_k, new_v = [], []
    seen = dict.fromkeys(STACK, 0)
    for kind in cfg.kinds:
        i = seen[kind]
        seen[kind] += 1

        def delta(y):
            out, cache["conv"], cache["state"] = delta_step_at(
                y, cache["conv"], cache["state"], i, blocks["linear"], i, cfg)
            return out

        def attend(y):
            q, k, v = attention_project(y, blocks["full"], i, cfg)
            new_k.append(k.astype(cache["k"].dtype))
            new_v.append(v.astype(cache["v"].dtype))
            o = decode_attention(q, cache["k"], cache["v"], pos, i,
                                 k_self=new_k[-1], v_self=new_v[-1])
            return matmul("bhd,hde->be", o.astype(y.dtype),
                          blocks["full"]["wo"][i])

        x = block(params, x, kind, i, delta if kind == "L" else attend, cfg)
    if new_k:
        with jax.named_scope("olmo.attn"):  # the cache write is attention's
            cache["k"] = write_token_to_cache(
                cache["k"], jnp.stack(new_k), pos, axis=3)
            cache["v"] = write_token_to_cache(
                cache["v"], jnp.stack(new_v), pos, axis=3)
    with jax.named_scope("olmo.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, cache)
    with jax.named_scope("olmo.delta"):
        counts = {"delta_positions": (pos > 0).sum().astype(jnp.int32),
                  "delta_chunk_positions": jnp.asarray(
                      pos.shape[0], jnp.int32)}
    return (*out, counts) if with_counts else out
