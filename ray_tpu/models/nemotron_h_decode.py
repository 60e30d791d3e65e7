"""Nemotron-H prefill + decode through a cache whose leaves are of two kinds.

``{"k", "v": [A, B, Hkv, T, D]}`` are the attentions' keys and values, with a
position axis, as the Llama family's; ``{"conv": [M, B, (K-1)(HP + 2GN)],
"ssm": [M, B, H, P, N]}`` are the Mamba-2 layers' state, float32, with NO
position axis: the convolution's last ``K-1`` inputs (oldest first, side by
side: three rows of 10240 as an axis of their own would be padded to the
TPU's tile of eight) and the state ``S`` after the slot's last token.  The
slot axis is axis 1 of every leaf, which is all ``llm/engine.py`` knows:
``init_cache(cfg, 1, rung)`` gives a one-slot row whose state leaves do not
depend on the rung, and ``splice_row`` writes it over the slot's, so an
admission replaces a slot's state WHOLE while its keys and values beyond the
rung keep what the last tenant left (decode reads nothing at or beyond
``pos``).

Prefill runs the chunked scan over the padded prompt with ``dt = 0`` at
positions ``>= length``: the state it returns is the state at the prompt's
TRUE length, whatever the rung, and the convolution's state is its last
``K-1`` true inputs.  Decode runs one step of the recurrence for all slots:
``S <- exp(dt A) S + dt x (x) B`` and ``y = S C + D x`` in float32, by
``ops/mamba_update.py``: the WHOLE stacked ``ssm`` leaf goes through every
Mamba-2 layer's call and comes back with that layer stepped, on a TPU by one
kernel that reads a slot's heads once, steps them, reads ``y`` out of what
it holds and writes them where they lay (the engine donates the cache;
``tests/test_tpu_compile.py`` reads the compiled step: one kernel a layer
and nothing else touches an array of the leaf's shape; off a TPU, and at the
toy widths of the CPU tests, the XLA formulation: a slice updated in place
and read again); the ``conv`` leaf goes through the same layers whole, as
``ssm`` does (``ops/conv_update.py``: a layer's window read out of the leaf,
its taps lane slices of it, the shifted window written over the layer it
was read from: one slice and one in-place update a layer in the compiled
step, none a clone, where a ``jnp.stack`` of the new windows into the
donated buffer made the v5e compiler copy the leaf's slices out eleven
times over at Granite-4.0-H's sizes, PR 63); and
attention by the deferred-scatter protocol of ``llama_decode.py``: the
cache holds ``[0, pos-1]``, the current key and value are merged as a last
score, and all are written at the step's end by ``write_token_to_cache``.

A decode row at position 0 is an idle slot (a prompt has at least one token):
it chooses no expert and is not counted.  Its state is computed like any
other's and stays finite: every step decays it by ``exp(dt A) < 1`` and adds
a bounded term.  Both return ``(logits, cache)``; with ``with_counts=True``
(the family's ``*_counted`` twins, which the engine runs) ``(logits, cache,
counts)``: the routing counts of ``nemotron_h.py`` as int32 scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import decode_attention, write_token_to_cache
from .layers import matmul, rmsnorm
from .mamba2 import mamba_step
from .nemotron_h import (CACHE_SCOPE, NemotronHConfig, attention_project,
                         nemotron_h_forward, run_layers)


def nemotron_h_init_cache(cfg: NemotronHConfig, batch: int, max_len: int):
    nm, na = cfg.kinds.count("M"), cfg.kinds.count("*")
    kv = (na, batch, cfg.n_kv_head, max_len, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((nm, batch, (cfg.conv_kernel - 1) * cfg.d_conv),
                          jnp.float32),
        "ssm": jnp.zeros((nm, batch, cfg.mamba_num_heads, cfg.mamba_head_dim,
                          cfg.ssm_state_size), jnp.float32),
    }


def nemotron_h_prefill(
    params, tokens, lengths, cache, cfg: NemotronHConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with keys and values of positions
    [0, S) written and the state after position ``length - 1`` in place of
    the slot's, routing counts of the positions < length)."""
    x, kept, counts = nemotron_h_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        with jax.named_scope(CACHE_SCOPE[name]):
            if name in ("k", "v"):  # [A, B, S, Hkv, D] -> head-major
                new = new.transpose(0, 1, 3, 2, 4)
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("nemotron.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = matmul("be,ve->bv", last, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def nemotron_h_decode_step(
    params, tokens, pos, cache, cfg: NemotronHConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    with jax.named_scope("nemotron.embed"):
        x = params["wte"][tokens].astype(jnp.float32)  # [B, d]
        live = pos > 0
    cache = dict(cache)
    new_k, new_v = [], []

    def mamba(i, y):
        out, cache["conv"], cache["ssm"] = mamba_step(
            y, cache["conv"], cache["ssm"], blocks["mamba"], i, cfg)
        return out

    def attend(i, y):
        q, k, v = attention_project(y, blocks["attn"], i)
        new_k.append(k.astype(cache["k"].dtype))
        new_v.append(v.astype(cache["v"].dtype))
        o = decode_attention(q, cache["k"], cache["v"], pos, i,
                             k_self=new_k[-1], v_self=new_v[-1])
        return matmul("bhd,hde->be", o.astype(y.dtype), blocks["attn"]["wo"][i])

    x, counts = run_layers(params, x, live, mamba, attend, cfg)
    if new_k:
        with jax.named_scope("nemotron.attn"):  # its cache write
            cache["k"] = write_token_to_cache(
                cache["k"], jnp.stack(new_k), pos, axis=3)
            cache["v"] = write_token_to_cache(
                cache["v"], jnp.stack(new_v), pos, axis=3)
    with jax.named_scope("nemotron.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = matmul("be,ve->bv", x, params["lm_head"])
    out = (logits, cache)
    return (*out, counts) if with_counts else out
