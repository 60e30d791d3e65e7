"""MiniCPM-SALA prefill + decode through a cache whose leaves are of THREE
kinds: ``{"k", "v": [Ns, B, Hkv, T, D]}`` the sparse layers' keys (after
``qk_norm``; no rotary) and values, with a position axis; ``{"kbar": [Ns, B,
Hkv, T // stride, D]}`` their POOLED keys, the selection's small cache beside
the main one, whose axis 3 counts windows of 32 positions every 16 (entry
``j`` = the mean of keys ``[16 j, 16 j + 32)``, there once position ``16 j +
31`` is); ``{"state": [Nl, B, H, D, D]}`` the lightning layers' ``S``, float32,
with NO position axis.  The slot axis is axis 1 of every leaf, which is all
``llm/engine.py`` knows: an admission replaces a slot's state WHOLE, while
its keys, values and pooled keys beyond the rung keep what the last tenant
left: decode reads no key at or beyond ``pos`` and no pooled key whose
window reaches beyond it, and every window that completes later is written
before it is read.

Prefill runs the chunked scan over the padded prompt with ``dt = 0`` at
positions ``>= length`` and pools no window that reaches past it
(``minicpm_sala.pooled_keys``): state, keys and pooled keys are those of the
prompt's TRUE length, whatever the rung.

Decode, a sparse layer.  A position ``pos`` that completes a window (``pos =
stride j + kernel - 1``) pools it from the cache's last ``kernel - 1`` keys
and its own, scores it with the rest and hands it to the step's end, where
one ``write_token_to_cache`` a leaf writes the new keys, values and (for the
rows that completed one) pooled keys.  A row whose context ``pos + 1`` is
under ``dense_len`` LISTS the blocks of its whole context; any other the
``topk`` blocks ``minicpm_sala.choose_blocks`` picks; one program reads
both (``ops.decode_attention.attend_listed_blocks``): a turn of ``topk``
listed blocks a row and key-value head is gathered out of the stacked cache
where it lies, and as many turns run as the longest list needs (one, once
every row is past ``dense_len``: 4096 positions a row whatever its context).
A cache shorter than ``dense_len`` can hold no such row and is read by
``decode_attention``, as any family's.

Decode, a lightning layer: ``ops/mamba_update.py`` with ``x = v``, ``dt = 1``,
``keep = exp(-s_h)``, ``b = k``, ``c = q / sqrt(D)`` over the stacked ``state``
leaf, one group a head.  The layers of a decode step are written out, not
looped (``granite_h_decode.py`` says why), and the new keys and values pass
one ``optimization_barrier`` with the stream so that the cache is written at
the step's end.

A decode row at position 0 is an idle slot: its state stays finite (every
step decays it by ``exp(-s_h) < 1`` and adds a bounded term).  Both return
``(logits, cache)``; with ``with_counts=True`` ``(logits, cache, counts)``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import (attend_listed_blocks, decode_attention,
                                    write_token_to_cache)
from ..ops.mamba_update import mamba_update
from .minicpm_sala import (CACHE_SCOPE, SCOPE, MinicpmSalaConfig, block,
                           choose_blocks, embed, gated_output, head,
                           lightning_output, lightning_project,
                           minicpm_sala_forward, project, slopes)


def minicpm_sala_init_cache(cfg: MinicpmSalaConfig, batch: int, max_len: int):
    """Any ``max_len``; one that reaches ``dense_len`` is rounded up to whole
    blocks (a list of blocks is read out of such a cache)."""
    if max_len >= cfg.dense_len:
        max_len = -(-max_len // cfg.block_size) * cfg.block_size
    ns, nl = cfg.kinds.count("S"), cfg.kinds.count("L")
    dt = jnp.dtype(cfg.dtype)
    kv = (ns, batch, cfg.n_kv_head, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "kbar": jnp.zeros((ns, batch, cfg.n_kv_head,
                           max_len // cfg.kernel_stride, cfg.head_dim), dt),
        "state": jnp.zeros((nl, batch, cfg.lightning_heads,
                            cfg.lightning_head_dim, cfg.lightning_head_dim),
                           jnp.float32),
    }


def minicpm_sala_prefill(
    params, tokens, lengths, cache, cfg: MinicpmSalaConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with keys and values of positions
    [0, S) and the pooled keys of the windows inside each prompt written and
    the state after position ``length - 1`` in place of the slot's,
    counts)."""
    x, kept, counts = minicpm_sala_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        with jax.named_scope(CACHE_SCOPE[name]):
            if name != "state":  # [Ns, B, S, Hkv, D] -> head-major
                new = new.transpose(0, 1, 3, 2, 4)[
                    :, :, :, :cache[name].shape[3]]
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("sala.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = head(params, last, cfg)
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def completed_window(k_cache, layer: int, k_self, pos, cfg: MinicpmSalaConfig):
    """The pooled key of the window that position ``pos`` completes, where it
    completes one: k_cache ``[Ns, B, Hkv, T, D]`` (positions before ``pos``),
    k_self ``[B, Hkv, D]`` -> (``[B, Hkv, D]`` float32: the mean of the last
    ``kernel - 1`` cached keys and ``k_self``, anything where none
    completes; the window's index ``[B]``, -1 where none completes)."""
    before = cfg.kernel_size - 1
    _, b, hkv, t, d = k_cache.shape
    at = pos[:, None] - before + jnp.arange(before)[None]  # [B, kernel - 1]
    keys = k_cache[layer, jnp.arange(b)[:, None, None],
                   jnp.arange(hkv)[None, :, None],
                   jnp.clip(at, 0, t - 1)[:, None, :]]  # [B, Hkv, kernel-1, D]
    mean = (keys.astype(jnp.float32).sum(2)
            + k_self.astype(jnp.float32)) / cfg.kernel_size
    done = (pos >= before) & ((pos - before) % cfg.kernel_stride == 0)
    return mean, jnp.where(done, (pos - before) // cfg.kernel_stride, -1)


def listed_blocks(q, kbar_cache, layer: int, fresh, fresh_at, pos,
                  cfg: MinicpmSalaConfig):
    """The blocks each (row, key-value head) of a decode step reads: ids
    ``[B, Hkv, listed_blocks]`` int32, the listed ones first and -1 after
    them.  q ``[B, H, D]`` as the attention reads it; kbar_cache ``[Ns, B,
    Hkv, W, D]``; ``fresh [B, Hkv, D]`` float32 the pooled key this step
    completed, of window ``fresh_at [B]`` (-1: none), which the cache does
    not hold yet."""
    b, h, d = q.shape
    hkv, n = cfg.n_kv_head, cfg.listed_blocks
    # a context under dense_len: every block that starts before pos
    blk = jnp.arange(n)
    whole = jnp.where(blk[None] * cfg.block_size < pos[:, None], blk[None], -1)
    with jax.named_scope("sala.select"):
        qg = q.reshape(b, hkv, h // hkv, d).astype(jnp.float32)
        scale = d ** -0.5
        scores = jnp.einsum(
            "bkgd,bkwd->bkgw", qg, kbar_cache[layer].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST) * scale
        # the window this step completed is scored as the cache will hold it
        own = jnp.einsum(
            "bkgd,bkd->bkg", qg,
            fresh.astype(kbar_cache.dtype).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST) * scale
        scores = jnp.where(
            jnp.arange(scores.shape[-1]) == fresh_at[:, None, None, None],
            own[..., None], scores)
        ids = choose_blocks(scores[:, :, :, None], pos[:, None, None],
                            cfg)[:, :, 0]  # [B, Hkv, topk]
        ids = jnp.pad(ids, ((0, 0), (0, 0), (0, n - cfg.topk)),
                      constant_values=-1)
    return jnp.where((pos + 1 >= cfg.dense_len)[:, None, None], ids,
                     whole[:, None, :])


def minicpm_sala_decode_step(
    params, tokens, pos, cache, cfg: MinicpmSalaConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    x = embed(params, tokens, cfg)  # [B, d]
    cache = dict(cache)
    selects = cache["k"].shape[3] >= cfg.dense_len
    new_k, new_v, new_kbar = [], [], []
    kbar_at = listed = None
    seen = dict.fromkeys(SCOPE, 0)
    for layer, kind in enumerate(cfg.kinds):
        i = seen[kind]
        seen[kind] += 1

        def lightning(y):
            w = blocks["lightning"]
            v, k, q, g = (a[:, 0] for a in lightning_project(
                y[:, None], w, i, pos[:, None], cfg))
            keep = jnp.broadcast_to(
                jnp.exp(-slopes(cfg, cfg.first_layer + layer)), v.shape[:2])
            o, cache["state"] = mamba_update(
                cache["state"], i, v, jnp.ones_like(keep), keep, k, q)
            return lightning_output(o, g, w, i, cfg)

        def sparse(y):
            nonlocal kbar_at, listed
            w = blocks["sparse"]
            q, k, v, g = project(y, w, i, cfg)
            q = q.astype(y.dtype)
            new_k.append(k.astype(cache["k"].dtype))
            new_v.append(v.astype(cache["v"].dtype))
            if selects:
                fresh, kbar_at = completed_window(
                    cache["k"], i, new_k[-1], pos, cfg)
                new_kbar.append(fresh.astype(cache["kbar"].dtype))
                ids = listed_blocks(
                    q, cache["kbar"], i, fresh, kbar_at, pos, cfg)
                listed = (ids[:, 0] >= 0).sum(-1)  # the same of every head
                o = attend_listed_blocks(
                    q, cache["k"], cache["v"], ids, pos, i,
                    block_size=cfg.block_size, turn=cfg.topk,
                    k_self=new_k[-1], v_self=new_v[-1])
            else:  # no row of this cache reaches dense_len
                o = decode_attention(q, cache["k"], cache["v"], pos, i,
                                     k_self=new_k[-1], v_self=new_v[-1])
            return gated_output(o.astype(jnp.float32), g, w, i, y.dtype)

        x = block(params, x, kind, i, layer,
                  lightning if kind == "L" else sparse, cfg)
    if new_k:
        with jax.named_scope(SCOPE["S"]):  # the cache write is attention's
            # at the step's END, after the last layer's reads
            x, new_k, new_v, new_kbar = jax.lax.optimization_barrier(
                (x, new_k, new_v, new_kbar))
            cache["k"] = write_token_to_cache(
                cache["k"], jnp.stack(new_k), pos, axis=3)
            cache["v"] = write_token_to_cache(
                cache["v"], jnp.stack(new_v), pos, axis=3)
            if new_kbar:
                cache["kbar"] = write_token_to_cache(
                    cache["kbar"], jnp.stack(new_kbar), kbar_at, axis=3)
    with jax.named_scope("sala.head"):
        logits = head(params, x, cfg)
    out = (logits, cache)
    nl, ns = (cfg.kinds.count(c) for c in "LS")
    with jax.named_scope(SCOPE["S"]):
        live = pos > 0
        read = (jnp.where(live, listed * cfg.block_size, 0).sum()
                if listed is not None else pos.sum())
        counts = {
            "lightning_positions": (live.sum() * nl).astype(jnp.int32),
            "lightning_chunk_positions": jnp.asarray(
                pos.shape[0] * nl, jnp.int32),
            "sparse_read_positions": (read * ns).astype(jnp.int32),
            "sparse_live_positions": (pos.sum() * ns).astype(jnp.int32)}
    return (*out, counts) if with_counts else out
