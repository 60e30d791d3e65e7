"""Laguna prefill + decode through a cache whose position-bearing leaves have
TWO extents, as MiMo-V2's (``mimo_v2_decode.py`` has the long form).

``{"k", "v": [Lf, B, Hkv, T, D]}`` are the full layers' keys (after norm and
rotary) and values over all ``T`` positions served; ``{"k_win", "v_win": [Lw,
B, Hkv, w, D]}`` are the window layers': a RING of ``w`` = 512 slots whatever
``T`` is, position ``p`` at ``p mod w``.  (At the published sizes, 32 slots x
16,384 positions, six window layers at full extent would be 12.9 GB; their
rings are 0.40.)  The query heads differ by kind (48 / 72) and the cache
does not know: both kinds keep 8 key-value heads of 128.  The slot axis is
axis 1 of every leaf, which is all ``llm/engine.py`` knows: an admission
replaces a slot's rings WHOLE, while its full keys and values beyond the
rung keep what the last tenant left (decode reads nothing at or beyond
``pos``).

Prefill at a rung ``S`` >= the prompt's length ``n`` writes the full layers'
keys and values of ``[0, S)``, leaves in a ring the LAST ``w`` TRUE positions
(``layers.ring_of``: a gather by ``lengths``), and takes the logits at ``n -
1``.  Decode: the current token's key and value ride beside the cache and
are merged as a last score (the deferred write every family uses); a full
layer reads its slice in blocks of 512 positions up to the batch's longest
context, a window layer its ring in one softmax, a slot attended iff the
position it must hold by now is inside the window
(``ops.decode_attention.decode_attention``); the gate multiplies ``[B, H,
D]`` before ``Wo`` (``laguna.block``); at the step's end one
``write_token_to_cache`` a leaf writes the new keys and values, the rings' at
``pos mod w``.  The engine donates the cache.

A decode row at position 0 is an idle slot (a prompt has at least one
token): it chooses no expert and is not counted.  Both return ``(logits,
cache)``; with ``with_counts=True`` (the family's ``*_counted`` twins, which
the engine runs) ``(logits, cache, counts)``: the routing counts of
``laguna.py`` as int32 scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import decode_attention, write_token_to_cache
from .laguna import (COUNT_NAMES, LEAVES, STACK, LagunaConfig,
                     attention_project, block, laguna_forward)
from .layers import add_counts, matmul, rmsnorm


def laguna_init_cache(cfg: LagunaConfig, batch: int, max_len: int):
    dt = jnp.dtype(cfg.dtype)
    cache = {}
    for kind, extent in (("F", max_len), ("W", cfg.window)):
        shape = (cfg.attn_kinds.count(kind), batch, cfg.n_kv_head, extent,
                 cfg.head_dim)
        for leaf in LEAVES[kind]:
            cache[leaf] = jnp.zeros(shape, dt)
    return cache


def laguna_prefill(
    params, tokens, lengths, cache, cfg: LagunaConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with the full layers' keys and values
    of positions [0, S) written and the window layers' rings as they are
    after position ``length - 1``, routing counts of the positions <
    length)."""
    x, kept, counts = laguna_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for kind, names in LEAVES.items():
        with jax.named_scope("laguna.attn_" + STACK[kind]):
            for name in names:
                if name in kept:
                    cache[name] = jax.lax.dynamic_update_slice(
                        cache[name], kept[name].astype(cache[name].dtype),
                        (0,) * kept[name].ndim)
    with jax.named_scope("laguna.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def laguna_decode_step(
    params, tokens, pos, cache, cfg: LagunaConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    with jax.named_scope("laguna.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    cache = dict(cache)
    new = {leaf: [] for leaf in cache}
    total = dict.fromkeys(COUNT_NAMES, jnp.zeros((), jnp.int32))
    seen = dict.fromkeys("FWDE", 0)
    for kinds in zip(cfg.attn_kinds, cfg.mlp_kinds):
        i, j = seen[kinds[0]], seen[kinds[1]]
        k_leaf, v_leaf = LEAVES[kinds[0]]

        def attend(att, y):
            q, k, v = attention_project(y, att, i, pos, kinds[0], cfg)
            new[k_leaf].append(k.astype(cache[k_leaf].dtype))
            new[v_leaf].append(v.astype(cache[v_leaf].dtype))
            return decode_attention(
                q, cache[k_leaf], cache[v_leaf], pos, i,
                k_self=new[k_leaf][-1], v_self=new[v_leaf][-1],
                window=None if kinds[0] == "F" else cfg.window)

        x, counts = block(params, x, live, kinds, i, j, attend, cfg)
        if counts is not None:
            with jax.named_scope("laguna.moe"):
                total = add_counts(total, counts)
        for kind in kinds:
            seen[kind] += 1
    with jax.named_scope("laguna.attn_window"):
        ring_at = pos % cfg.window
    for kind, at in (("F", pos), ("W", ring_at)):
        with jax.named_scope("laguna.attn_" + STACK[kind]):  # its cache write
            for leaf in LEAVES[kind]:
                if new[leaf]:
                    cache[leaf] = write_token_to_cache(
                        cache[leaf], jnp.stack(new[leaf]), at, axis=3)
    with jax.named_scope("laguna.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, cache)
    return (*out, total) if with_counts else out
