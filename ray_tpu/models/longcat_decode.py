"""LongCat prefill + decode through the latent (MLA) cache.

The cache is ONE leaf, ``{"latent": [2L, B, T, rkv+dr]}``: for every
attention (two a double layer) and slot, each token's ``[ckv | kr]`` after
norm, scale and rope: 576 values a token an attention at the published
sizes, where per-head keys and values would be 64 x (192 + 128).  The slot
axis is axis 1, as for every family's cache (``llm/engine.py`` splices rows
there and knows nothing else of the layout).

Prefill runs the expanded form (per-head keys and values rebuilt from the
latent, dense causal scores); decode the absorbed form: ``Wkvb``'s key half
is folded into the query and its value half applied after the weighted sum
of latents, so a step reads ``T x 576`` values a slot an attention and never
builds a key or a value.  Deferred-scatter protocol as in ``gpt2_decode.py``:
the cache holds ``[0, pos-1]``, the current token's latent is merged as a
last score, and all ``2L`` latents are written at the step's end by the
families' one ``write_token_to_cache`` (the tile of rows that holds each
slot's position, in place).  An attention reads its slice of the cache in
blocks of 512 positions up to the batch's longest context, in ONE pipelined
pass over the stacked leaf where it lies (``mla_absorbed``; on a TPU
``ops.latent_attention``'s kernel, which fetches block ``j + 1`` while block
``j`` is scored: ``tests/test_tpu_compile.py``).

Both return ``(logits, cache)`` as every family's do; with
``with_counts=True`` (the family's ``*_counted`` twins, which the engine
runs) ``(logits, cache, counts)``: the routing counts of ``longcat.py`` as
int32 scalars.  A decode row at position 0 is an idle slot (a prompt has at
least one token, so a live row's position is >= 1): it chooses no expert and
is not counted.  Prefill counts positions ``< length``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import write_token_to_cache
from .layers import add_counts, matmul, rmsnorm
from .longcat import LongcatConfig, double_layer, longcat_forward
from .mla import mla_absorbed, mla_project


def longcat_init_cache(cfg: LongcatConfig, batch: int, max_len: int):
    shape = (2 * cfg.n_layer, batch, max_len, cfg.latent_dim)
    return {"latent": jnp.zeros(shape, jnp.dtype(cfg.dtype))}


def longcat_prefill(
    params, tokens, lengths, cache, cfg: LongcatConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with positions [0, S) written,
    routing counts of the positions < length)."""
    s = tokens.shape[1]
    with jax.named_scope("longcat.embed"):
        live = jnp.arange(s)[None] < lengths[:, None]
    x, latents, counts = longcat_forward(params, tokens, live, cfg)
    with jax.named_scope("longcat.mla"):  # the cache write is attention's
        cache = {"latent": jax.lax.dynamic_update_slice(
            cache["latent"], latents.astype(cache["latent"].dtype),
            (0, 0, 0, 0))}
    with jax.named_scope("longcat.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def longcat_decode_step(
    params, tokens, pos, cache, cfg: LongcatConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    with jax.named_scope("longcat.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    latent_cache = cache["latent"]
    new, total = [], None

    def attend(att, y):
        q, latent = mla_project(y[:, None], att, pos[:, None], cfg)
        new.append(latent[:, 0].astype(latent_cache.dtype))
        return mla_absorbed(q[:, 0], new[-1], latent_cache, pos, att, cfg,
                            layer=len(new) - 1)

    for layer in range(cfg.n_layer):
        x, counts = double_layer(x, params, layer, live, attend, cfg)
        with jax.named_scope("longcat.moe"):
            total = add_counts(total, counts)
    with jax.named_scope("longcat.mla"):  # the cache write is attention's
        latent_cache = write_token_to_cache(
            latent_cache, jnp.stack(new), pos, axis=2)
    with jax.named_scope("longcat.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, {"latent": latent_cache})
    return (*out, total) if with_counts else out
