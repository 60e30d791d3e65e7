"""Mistral-4 prefill + decode through the latent (MLA) cache.

The cache is ONE leaf, ``{"latent": [L, B, T, rkv+dr]}``: for every layer and
slot, each token's ``[ckv | kr]`` after norm and rope: 320 values a token a
layer at the published sizes (640 bytes), where per-head keys and values
would be 32 x (128 + 128).  The slot axis is axis 1, as for every family's
cache (``llm/engine.py`` splices rows there and knows nothing else of the
layout); ``T`` is whatever the engine serves (16,384 in the benchmark's
cell), and nothing here depends on it but the cache's shape.

Prefill at a rung ``S`` >= the prompt's length ``n`` writes the latents of
``[0, S)`` (positions ``>= n`` hold what padding gives and are never read:
decode masks by ``pos``), expands the latent to per-head keys and values
once a layer and scores them in blocks (``layers.blocked_attention``: no
array grows with ``S^2``, nothing above the diagonal or beyond ``n``'s block
is computed), and takes the logits at ``n - 1``.  Decode runs the absorbed
form, LongCat's (``mla.mla_absorbed``): the current token's latent
rides beside the cache and is merged as a last score, a layer reads its
slice of the cache in blocks of 512 positions up to the batch's longest
context in ONE pipelined pass over the stacked leaf where it lies (on a
TPU ``ops.latent_attention``'s kernel: block ``j + 1`` is fetched while
block ``j`` is scored; nine calls of one lowered kernel), and all
``L`` latents are written at the step's end by the families' one
``write_token_to_cache``.  The query's scale ``a(pos) m^2`` is folded into the
query in ``mistral4.project``, so both forms score with ``(dn+dr)^-0.5``
alone.  The engine donates the cache.

Both return ``(logits, cache)`` as every family's do; with
``with_counts=True`` (the family's ``*_counted`` twins, which the engine
runs) ``(logits, cache, counts)``: the routing counts of ``mistral4.py`` as
int32 scalars.  A decode row at position 0 is an idle slot (a prompt has at
least one token): it chooses no expert and is not counted.  Prefill counts
positions ``< length``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import write_token_to_cache
from .layers import add_counts, matmul, rmsnorm
from .mistral4 import Mistral4Config, layer, mistral4_forward, project
from .mla import mla_absorbed


def mistral4_init_cache(cfg: Mistral4Config, batch: int, max_len: int):
    shape = (cfg.n_layer, batch, max_len, cfg.latent_dim)
    return {"latent": jnp.zeros(shape, jnp.dtype(cfg.dtype))}


def mistral4_prefill(
    params, tokens, lengths, cache, cfg: Mistral4Config, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with positions [0, S) written,
    routing counts of the positions < length)."""
    x, latents, counts = mistral4_forward(params, tokens, lengths, cfg)
    with jax.named_scope("mistral4.mla"):  # the cache write is attention's
        cache = {"latent": jax.lax.dynamic_update_slice(
            cache["latent"], latents.astype(cache["latent"].dtype),
            (0, 0, 0, 0))}
    with jax.named_scope("mistral4.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def mistral4_decode_step(
    params, tokens, pos, cache, cfg: Mistral4Config, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    with jax.named_scope("mistral4.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    latent_cache = cache["latent"]
    new, counts = [], None
    for i in range(cfg.n_layer):
        def attend(att, y):
            q, latent = project(y[:, None], att, pos[:, None], cfg)
            latent = latent[:, 0].astype(latent_cache.dtype)
            return mla_absorbed(q[:, 0], latent, latent_cache, pos, att, cfg,
                                layer=i), latent

        x, latent, layer_counts = layer(params, x, live, i, attend, cfg)
        new.append(latent)
        with jax.named_scope("mistral4.moe"):
            counts = add_counts(counts, layer_counts)
    with jax.named_scope("mistral4.mla"):  # the cache write is attention's
        latent_cache = write_token_to_cache(
            latent_cache, jnp.stack(new), pos, axis=2)
    with jax.named_scope("mistral4.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, {"latent": latent_cache})
    return (*out, counts) if with_counts else out
