"""KV-cache inference path for the Llama family: prefill + ragged decode.

Same design as ``gpt2_decode.py`` (head-major stacked cache
``[L, B, Hkv, T, D]``, one deferred in-place write of the step's token a
cache array) with
the Llama specifics: RMSNorm, rotary positions, SwiGLU, and **grouped-query
attention** — the cache holds only the Hkv kv-heads and decode attention
attends each group of H/Hkv query heads against its shared kv-head in one
score tile (the GQA memory win is the whole point of serving Llama-style
models: cache bytes shrink by H/Hkv).

Prefill scores in tiles (``layers.blocked_attention``, as five other
families' does): 512 queries against 512 keys at a time under an online
softmax, a group's query heads carried as an axis against its one key-value
head (keys and values are never repeated), the key blocks bounded by the
diagonal.  No array grows with the square of the rung: the dense form's
``[B, H, S, S]`` float32 scores were 537 MB a layer at 2048 positions and
half of that rung's time (PERF.md, PR 66).  Every query block of the rung
runs, padding rows attended as they always were: a prompt takes the smallest
rung that holds it, so more than half of a rung is prompt, and the longest
prompt as a traced bound on the query blocks cost every call of the 256 rung
0.5 ms where it saved 2.5 ms on the few prompts that leave the top rung's
last block empty (PERF.md, PR 66).

Reference role: the model runner inside the engines the reference wraps
(ray ``python/ray/llm/_internal/serve/engines/vllm/``).

Device operations carry ``jax.named_scope``s ``llama.embed``, ``llama.attn``
(norm, projections, rotary, attention, residual, the cache write),
``llama.mlp`` and ``llama.head`` (final norm + vocabulary product).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import KEY_BLOCK, QUERY_BLOCK, blocked_attention, rmsnorm, rope
from .llama import LlamaConfig


def llama_init_cache(cfg: LlamaConfig, batch: int, max_len: int):
    shape = (cfg.n_layer, batch, cfg.n_kv_head, max_len, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def llama_prefill(
    params, tokens, lengths, cache, cfg: LlamaConfig,
    query_block: int = QUERY_BLOCK, key_block: int = KEY_BLOCK,
) -> Tuple[jnp.ndarray, dict]:
    """tokens: [B, S] right-padded prompts; lengths: [B] true lengths.
    Returns (last_logits [B, V], cache with positions [0, S) written).
    ``query_block`` / ``key_block``: the attention's tile (a test's; the
    programs take the defaults)."""
    b, s = tokens.shape
    with jax.named_scope("llama.embed"):
        x = params["wte"][tokens].astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("llama.attn"):
        positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, layer):
        with jax.named_scope("llama.attn"):
            y = rmsnorm(x, layer["rms1"], cfg.rms_eps)
            q = jnp.einsum("bse,ehd->bshd", y, layer["wq"])
            k = jnp.einsum("bse,ekd->bskd", y, layer["wk"])
            v = jnp.einsum("bse,ekd->bskd", y, layer["wv"])
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
            o = blocked_attention(
                q, k, v, query_block=query_block, key_block=key_block)
            x = x + jnp.einsum(
                "bshd,hde->bse", o.astype(q.dtype), layer["wo"]
            ).astype(x.dtype)
        with jax.named_scope("llama.mlp"):
            y = rmsnorm(x, layer["rms2"], cfg.rms_eps)
            gate = jax.nn.silu(jnp.einsum("bse,ef->bsf", y, layer["w_gate"]))
            up = jnp.einsum("bse,ef->bsf", y, layer["w_up"])
            x = x + jnp.einsum(
                "bsf,fe->bse", gate * up, layer["w_down"]
            ).astype(x.dtype)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    with jax.named_scope("llama.attn"):  # the cache write is attention's
        # [L, B, S, Hkv, D] → head-major [L, B, Hkv, S, D].
        ks = ks.transpose(0, 1, 3, 2, 4).astype(cache["k"].dtype)
        vs = vs.transpose(0, 1, 3, 2, 4).astype(cache["v"].dtype)
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], ks, (0, 0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vs, (0, 0, 0, 0, 0)),
        }
    with jax.named_scope("llama.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        logits = jnp.einsum("be,ve->bv", last, params["lm_head"])
        return logits.astype(jnp.float32), cache


def llama_decode_step(
    params, tokens, pos, cache, cfg: LlamaConfig
) -> Tuple[jnp.ndarray, dict]:
    """tokens: [B]; pos: [B] position of each token.  Ragged decode with
    per-slot rotary positions."""
    from ..ops.decode_attention import (decode_attention,
                                        write_token_to_cache)

    b = tokens.shape[0]
    with jax.named_scope("llama.embed"):
        x = params["wte"][tokens].astype(jnp.dtype(cfg.dtype))  # [B, E]
    ck, cv = cache["k"], cache["v"]
    new_ks, new_vs = [], []

    for l in range(cfg.n_layer):
        # A layer's slices are read by both parts; attention's come first.
        with jax.named_scope("llama.attn"):
            layer = jax.tree.map(lambda a: a[l], params["blocks"])
            y = rmsnorm(x, layer["rms1"], cfg.rms_eps)
            q = jnp.einsum("be,ehd->bhd", y, layer["wq"])
            k = jnp.einsum("be,ekd->bkd", y, layer["wk"])
            v = jnp.einsum("be,ekd->bkd", y, layer["wv"])
            # rope expects [B, S, H, D]; per-slot positions ride the batch
            # dim.
            q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            new_ks.append(k.astype(ck.dtype))
            new_vs.append(v.astype(cv.dtype))
            # Deferred-scatter protocol (see gpt2_decode.py): cache holds
            # [0, pos-1]; current k/v a column of the softmax, one write
            # below.
            o = decode_attention(
                q, ck, cv, pos, l, k_self=new_ks[-1], v_self=new_vs[-1]
            )  # [B, H, D]
            x = x + jnp.einsum(
                "bhd,hde->be", o.astype(y.dtype), layer["wo"]
            ).astype(x.dtype)
        with jax.named_scope("llama.mlp"):
            y = rmsnorm(x, layer["rms2"], cfg.rms_eps)
            gate = jax.nn.silu(jnp.einsum("be,ef->bf", y, layer["w_gate"]))
            up = jnp.einsum("be,ef->bf", y, layer["w_up"])
            x = x + jnp.einsum(
                "bf,fe->be", gate * up, layer["w_down"]
            ).astype(x.dtype)

    with jax.named_scope("llama.attn"):  # the cache write is attention's
        ck = write_token_to_cache(ck, jnp.stack(new_ks), pos, axis=3)
        cv = write_token_to_cache(cv, jnp.stack(new_vs), pos, axis=3)
    with jax.named_scope("llama.head"):
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps)
        logits = jnp.einsum("be,ve->bv", x, params["lm_head"])
        return logits.astype(jnp.float32), {"k": ck, "v": cv}
