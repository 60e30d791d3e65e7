"""MiMo-V2 prefill + decode through a cache whose position-bearing leaves
have TWO extents.

``{"k": [Lf, B, Hf, T, D], "v": [Lf, B, Hf, T, Dv]}`` are the full layers'
keys and values over all ``T`` positions served, as the Llama family's.
``{"k_win": [Lw, B, Hw, w, D], "v_win": [Lw, B, Hw, w, Dv]}`` are the window
layers': a RING of ``w`` slots whatever ``T`` is, position ``p`` at ``p mod
w``, because a window layer never reads further back.  (At the published
sizes, 64 slots x 4096 positions, five window layers at full extent would
be 6.7 GB; their rings are 0.21.)  The slot axis is axis 1 of every leaf,
which is all ``llm/engine.py`` knows: ``init_cache(cfg, 1, rung)`` gives a
one-slot row whose rings do not depend on the rung, and ``splice_row``
writes it over the slot's, so an admission replaces a slot's rings WHOLE
while its full keys and values beyond the rung keep what the last tenant
left (decode reads nothing at or beyond ``pos``).

Prefill leaves in a ring the LAST ``w`` TRUE positions of the prompt
(``layers.ring_of``: a gather by ``lengths``, not the rung's tail), zeros
in the slots no position has reached.  Decode never trusts a slot's
content: slot ``r`` is attended iff the position it must hold by now, the
newest ``p < pos`` with ``p = r mod w``, is ``>= 0`` and inside the window
(``ops.decode_attention.ring_held`` / ``ring_positions``).  The current
token's key and value ride beside the cache and are merged as a last score
(the deferred write of ``llama_decode.py``); at the step's end one
``write_token_to_cache`` a leaf writes them, the rings' at ``pos mod w``,
over the slot that held ``pos - w``.  The engine donates the cache.

A decode row at position 0 is an idle slot (a prompt has at least one
token): it chooses no expert and is not counted.  Both return ``(logits,
cache)``; with ``with_counts=True`` (the family's ``*_counted`` twins, which
the engine runs) ``(logits, cache, counts)``: the routing counts of
``mimo_v2.py`` as int32 scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import decode_attention, write_token_to_cache
from .layers import matmul, rmsnorm
from .mimo_v2 import (STACK, MimoV2Config, attention_project, leaf_scope,
                      mimo_v2_forward, run_layers)

# a kind of attention -> its cache leaves
LEAVES = {"F": ("k", "v"), "W": ("k_win", "v_win")}


def mimo_v2_init_cache(cfg: MimoV2Config, batch: int, max_len: int):
    dt = jnp.dtype(cfg.dtype)
    cache = {}
    for kind, extent in (("F", max_len), ("W", cfg.window)):
        lead = (cfg.attn_kinds.count(kind), batch, cfg.kv_heads(kind), extent)
        for leaf, width in zip(LEAVES[kind], (cfg.head_dim, cfg.v_head_dim)):
            cache[leaf] = jnp.zeros(lead + (width,), dt)
    return cache


def mimo_v2_prefill(
    params, tokens, lengths, cache, cfg: MimoV2Config, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with the full layers' keys and values
    of positions [0, S) written and the window layers' rings as they are
    after position ``length - 1``, routing counts of the positions <
    length)."""
    x, kept, counts = mimo_v2_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        with jax.named_scope(leaf_scope(name)):  # its cache write
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("mimo.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def mimo_v2_decode_step(
    params, tokens, pos, cache, cfg: MimoV2Config, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    with jax.named_scope("mimo.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    cache = dict(cache)
    new = {leaf: [] for leaf in cache}

    def attend(kind, i, y):
        att = blocks[STACK[kind]]
        k_leaf, v_leaf = LEAVES[kind]
        q, k, v = attention_project(y, att, i, pos, kind, cfg)
        new[k_leaf].append(k.astype(cache[k_leaf].dtype))
        new[v_leaf].append(v.astype(cache[v_leaf].dtype))
        ring = {} if kind == "F" else {
            "window": cfg.window, "sink": att["sink"][i]}
        return decode_attention(
            q, cache[k_leaf], cache[v_leaf], pos, i, k_self=new[k_leaf][-1],
            v_self=new[v_leaf][-1], **ring)

    x, counts = run_layers(params, x, live, attend, cfg)
    with jax.named_scope("mimo.attn_window"):
        ring_at = pos % cfg.window
    for kind, at in (("F", pos), ("W", ring_at)):
        for leaf in LEAVES[kind]:
            if new[leaf]:
                with jax.named_scope(leaf_scope(leaf)):  # its cache write
                    cache[leaf] = write_token_to_cache(
                        cache[leaf], jnp.stack(new[leaf]), at, axis=3)
    with jax.named_scope("mimo.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out
