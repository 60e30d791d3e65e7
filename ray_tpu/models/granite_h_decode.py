"""Granite-4.0-H prefill + decode through a cache whose leaves are of two
kinds, ``nemotron_h_decode``'s: ``{"k", "v": [A, B, Hkv, T, D]}`` the four
attentions' keys and values, with a position axis; ``{"conv": [M, B, (K-1)(HP
+ 2GN)], "ssm": [M, B, H, P, N]}`` the Mamba-2 layers' state, float32, with
NO position axis (the convolution's last ``K-1`` inputs, oldest first, side
by side; the state ``S`` after the slot's last token).  At the published
sizes and 64 slots the ``ssm`` leaf is 4.83 GB, the largest thing on the
chip after the weights.  The slot axis is axis 1 of every leaf, which is all
``llm/engine.py`` knows: ``init_cache(cfg, 1, rung)`` gives a one-slot row
whose state leaves do not depend on the rung, and ``splice_row`` writes it
over the slot's (77 MB of state an admission, where it lies), so an
admission replaces a slot's state WHOLE while its keys and values beyond the
rung keep what the last tenant left (decode reads nothing at or beyond
``pos``).

Prefill runs the chunked scan over the padded prompt with ``dt = 0`` at
positions ``>= length`` (``mamba2.mamba_sequence``): the state it returns
is the state at the prompt's TRUE length, whatever the rung.  Decode runs one
step of the recurrence for all slots (``mamba2.mamba_step``, the
same function): the WHOLE stacked ``ssm`` leaf goes through thirty-six calls
of ``ops/mamba_update.py`` and comes back with every layer stepped, on a TPU
by ONE lowered kernel that reads a slot's heads once and writes them where
they lay, two crossings of the 4.83 GB a step and no copy of it
(``tests/test_tpu_compile.py`` reads the compiled step); the 120 MB ``conv``
leaf goes through the same thirty-six layers whole (``ops/conv_update.py``:
one slice into the fast memory and one in-place scatter a layer, the leaf
read once and written once a step); attention goes by the deferred-scatter
protocol of ``llama_decode.py``.  The forty layers of
a decode step are written out, not looped: a loop would slice each layer's
weights out of their stacks by a traced index, and a step that is bound by
the memory's speed cannot afford a product that copies its weight first;
what forty bodies cost a replica's start is in ``PERF.md`` (PR 60).  Three
things the v5e compiler did at 64 slots (12.4 GB of arguments; not at 32)
and what stands against each.  While a layer's update was an XLA fusion
that wrote its slice of the donated leaf in place, the compiler
rematerialised layer 0's update for each of its two readers and the
compiled step stepped layer 0's state twice (logits 14 % off the reference
on the chip, PR 60; the all-layers script found it, the two-layer check
could not): a kernel's result has ONE reader of the leaf, the next kernel,
and is not cloned, so the ``optimization_barrier`` a layer that cured it is
gone (PR 61: thirty-six calls with and without it, the all-layers script
``ok`` without).  And with the kernels in, the compiler moved the step's
write of the new VALUES into the cache ahead of the last attention layer's
read of the old ones and paid for it with two copies of the 0.54 GB ``v``
leaf a step: the new keys and values now pass one ``optimization_barrier``
together with the stream after the last layer, so the cache is written at
the step's end, as the protocol says, and nothing copies it.  And while the
step took ``cache["conv"][i]`` a layer and built the leaf anew with a
``jnp.stack`` at its end, the stack was written into the donated buffer the
slices were read from: the compiler copied every slice out before the first
write and then rematerialised the copies, 38 fusions with 414 outputs of
``[64, 13056]`` float32 a step, 2.8 GB moved where 0.24 must be (3.8 ms of
a 31 ms step on the chip, PR 63).  Threaded through the layers whole, the
leaf is cut once a layer; ``conv_update`` holds its result behind an
``optimization_barrier``, because an in-place scatter is an XLA fusion with
two readers and layer 0's was cloned like the ``ssm`` updates of old.

A decode row at position 0 is an idle slot (a prompt has at least one
token): its state is computed like any other's and stays finite, every step
decays it by ``exp(dt A) < 1`` and adds a bounded term.  Both return
``(logits, cache)``; with ``with_counts=True`` (the family's ``*_counted``
twins, which the engine runs) ``(logits, cache, counts)``: the counts of
``granite_h.py`` as int32 scalars.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import decode_attention, write_token_to_cache
from .granite_h import (CACHE_SCOPE, SCOPE, GraniteHConfig,
                        attention_project, block, embed, granite_h_forward,
                        head)
from .layers import matmul, rmsnorm
from .mamba2 import mamba_step


def granite_h_init_cache(cfg: GraniteHConfig, batch: int, max_len: int):
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


def granite_h_prefill(
    params, tokens, lengths, cache, cfg: GraniteHConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with keys and values of positions
    [0, S) written and the state after position ``length - 1`` in place of
    the slot's, counts of the positions scanned)."""
    x, kept, counts = granite_h_forward(params, tokens, lengths, cfg)
    cache = dict(cache)
    for name, new in kept.items():
        with jax.named_scope(CACHE_SCOPE[name]):
            if name in ("k", "v"):  # [A, B, S, Hkv, D] -> head-major
                new = new.transpose(0, 1, 3, 2, 4)
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], new.astype(cache[name].dtype), (0,) * new.ndim)
    with jax.named_scope("granite.head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = head(params, last, cfg)
    out = (logits, cache)
    return (*out, counts) if with_counts else out


def granite_h_decode_step(
    params, tokens, pos, cache, cfg: GraniteHConfig, *,
    with_counts: bool = False
) -> Tuple:
    """tokens: [B]; pos: [B] position of each token (0 = idle slot)."""
    pos = jnp.asarray(pos)
    blocks = params["blocks"]
    x = embed(params, tokens, cfg)  # [B, d]
    cache = dict(cache)
    new_k, new_v = [], []
    seen = dict.fromkeys(SCOPE, 0)
    for layer, kind in enumerate(cfg.kinds):
        i = seen[kind]
        seen[kind] += 1

        def mamba(y):
            out, cache["conv"], cache["ssm"] = mamba_step(
                y, cache["conv"], cache["ssm"], blocks["mamba"], i, cfg)
            return out

        def attend(y):
            q, k, v = attention_project(y, blocks["attn"], i, cfg)
            new_k.append(k.astype(cache["k"].dtype))
            new_v.append(v.astype(cache["v"].dtype))
            o = decode_attention(q, cache["k"], cache["v"], pos, i,
                                 k_self=new_k[-1], v_self=new_v[-1])
            return matmul("bhd,hde->be", o.astype(y.dtype),
                          blocks["attn"]["wo"][i])

        x = block(params, x, kind, i, layer,
                  mamba if kind == "M" else attend, cfg)
    if new_k:
        with jax.named_scope(SCOPE["*"]):  # the cache write is attention's
            # at the step's END, after the last layer's reads: the docstring
            x, new_k, new_v = jax.lax.optimization_barrier((x, new_k, new_v))
            cache["k"] = write_token_to_cache(
                cache["k"], jnp.stack(new_k), pos, axis=3)
            cache["v"] = write_token_to_cache(
                cache["v"], jnp.stack(new_v), pos, axis=3)
    with jax.named_scope("granite.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
        logits = head(params, x, cfg)
    out = (logits, cache)
    with jax.named_scope(SCOPE["M"]):
        counts = {"ssm_positions": (pos > 0).sum().astype(jnp.int32),
                  "ssm_chunk_positions": jnp.asarray(
                      pos.shape[0], jnp.int32)}
    return (*out, counts) if with_counts else out
