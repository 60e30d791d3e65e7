"""Kimi-Linear prefill + decode through a cache whose three leaves are of two
kinds.

``{"latent": [Lm, B, T, rkv+dr]}`` holds, for every latent-attention layer
and slot, each token's ``[ckv | kr]`` (576 values at the published sizes, in
``cfg.dtype``), with a position axis, as Mistral-4's; ``{"conv": [Lk, B, (K-1)
3 H dk], "state": [Lk, B, H, dk, dv]}`` are the KDA layers' state, float32,
with NO position axis: the convolution's last ``K-1`` inputs (oldest first,
side by side, as Olmo-Hybrid's) and the delta rule's matrix ``S [dk, dv]`` a
head after the slot's last token (``dk = dv = 128``: a head is a whole row
of tiles, nothing is packed or padded).  The slot axis is axis 1 of every
leaf, which is all ``llm/engine.py`` knows: ``init_cache(cfg, 1, rung)``
gives a one-slot row whose state leaves do not depend on the rung, and
``splice_row`` writes it over the slot's, so an admission replaces a slot's
state WHOLE while its latents beyond the rung keep what the last tenant left
(decode reads nothing at or beyond ``pos``).

Prefill runs the chunked scan over the padded prompt with ``beta = 0`` and
``g = 0`` at positions ``>= length``: the state it returns is the state at
the prompt's TRUE length, whatever the rung, and the convolution's state is
its last ``K-1`` true inputs.  Decode runs one step of the recurrence for
all slots, in float32, in ONE pass over ``S`` (``ops.delta_update`` with the
decay a column beside ``q`` and ``k``: on a TPU the Pallas kernel, state
aliased in place, the layer a prefetched operand, sixteen calls of one
lowered kernel), the ``conv`` leaf through the same layers whole
(``ops.conv_update``).  Latent attention goes by the deferred-scatter
protocol: the cache holds ``[0, pos-1]``, the current token's latent rides
beside it and is merged as a last score (``mla.mla_absorbed``: on a TPU the
family's second kind of kernel, ``ops.latent_attention``, ONE pipelined pass
over the live blocks of the stacked leaf where it lies, five calls of one
lowered kernel), and all
``Lm`` latents are written at the step's END by ``write_token_to_cache``
(behind an ``optimization_barrier`` with the stream, as Granite-4.0-H's keys
and values are: no write may move ahead of a later layer's read).

A decode row at position 0 is an idle slot (a prompt has at least one
token): it chooses no expert and is not counted; its state is computed like
any other's and stays finite (``k`` is normalised, ``beta < 1`` and ``alpha <
1``: a step's map on ``S`` never expands).  Both return ``(logits, cache)``;
with ``with_counts=True`` (the family's ``*_counted`` twins, which the engine
runs) ``(logits, cache, counts)``: the counts of ``kimi_linear.py`` as int32
scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.conv_update import conv_update
from ..ops.decode_attention import write_token_to_cache
from ..ops.delta_update import delta_update
from .kimi_linear import (STACKS, KimiLinearConfig, block, kda_output,
                          kda_project, kimi_linear_forward, mla_weights,
                          project, split_heads, stacks_in, zero_counts)
from .layers import add_counts, matmul, rmsnorm
from .mla import mla_absorbed

SCOPE = {"latent": "kimi.mla", "conv": "kimi.delta", "state": "kimi.delta"}


def kimi_linear_init_cache(cfg: KimiLinearConfig, batch: int, max_len: int):
    n = stacks_in(cfg.kinds)
    h, dk = cfg.linear_num_heads, cfg.linear_head_dim
    return {
        "latent": jnp.zeros((n["mla"], batch, max_len, cfg.latent_dim),
                            jnp.dtype(cfg.dtype)),
        "conv": jnp.zeros(
            (n["kda"], batch, (cfg.conv_kernel - 1) * cfg.d_conv),
            jnp.float32),
        "state": jnp.zeros((n["kda"], batch, h, dk, dk), jnp.float32),
    }


def kimi_linear_prefill(
    params, tokens, lengths, cache, cfg: KimiLinearConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with the latents of positions [0, S)
    written and the state after position ``length - 1`` in place of the
    slot's, counts of the positions scanned and routed)."""
    x, kept, counts = kimi_linear_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        with jax.named_scope(SCOPE[name]):
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("kimi.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def kda_step_at(y, conv_leaf, leaf, at: int, m, i: int,
                cfg: KimiLinearConfig):
    """One token a row through KDA layer ``i``, whose state is layer ``at`` of
    the two stacked leaves ``conv_leaf [layers, B, (K-1) 3 H dk]`` and ``leaf
    [layers, B, H, dk, dv]``.  y ``[B, d]`` -> (``[B, d]`` float32, the two
    leaves with layer ``at`` updated where it lies: ``ops.conv_update`` and
    ``ops.delta_update``, the decay ``[B, H, dk]`` a vector a head)."""
    qkv, z, g, beta = kda_project(y, m, i, cfg)
    conv, conv_leaf = conv_update(conv_leaf, at, qkv, m["conv_w"][i])
    q, k, v = split_heads(jax.nn.silu(conv), cfg)  # [B, H, dk] each
    o, leaf = delta_update(leaf, at, q, k, v, jnp.exp(g), beta[..., None])
    return kda_output(o, z, m, i, cfg), conv_leaf, leaf


def kimi_linear_decode_step(
    params, tokens, pos, cache, cfg: KimiLinearConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    with jax.named_scope("kimi.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    cache = dict(cache)
    new, counts = [], zero_counts()
    seen = stacks_in("")
    for kind in cfg.kinds:
        mixer, ff = STACKS[kind]
        i, j = seen[mixer], seen[ff]
        seen[mixer] += 1
        seen[ff] += 1

        def delta(y):
            out, cache["conv"], cache["state"] = kda_step_at(
                y, cache["conv"], cache["state"], i, blocks["kda"], i, cfg)
            return out

        def attend(y):
            att = mla_weights(blocks, i)
            q, latent = project(y[:, None], att, cfg)
            new.append(latent[:, 0].astype(cache["latent"].dtype))
            return mla_absorbed(q[:, 0], new[-1], cache["latent"], pos, att,
                                cfg, layer=i)

        x, layer_counts = block(params, x, live, kind, i, j,
                                delta if mixer == "kda" else attend, cfg)
        if layer_counts is not None:
            with jax.named_scope("kimi.moe"):
                counts = add_counts(counts, layer_counts)
    if new:
        with jax.named_scope("kimi.mla"):  # the cache write is attention's
            # at the step's END, after the last layer's reads: the docstring
            x, new = jax.lax.optimization_barrier((x, new))
            cache["latent"] = write_token_to_cache(
                cache["latent"], jnp.stack(new), pos, axis=2)
    with jax.named_scope("kimi.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, cache)
    with jax.named_scope("kimi.delta"):
        counts = dict(
            counts, delta_positions=live.sum().astype(jnp.int32),
            delta_chunk_positions=jnp.asarray(pos.shape[0], jnp.int32))
    return (*out, counts) if with_counts else out
