"""Ulysses-style sequence parallelism: all-to-all head↔sequence resharding.

The second half of closing the reference's SP gap (SURVEY.md §5).  Instead of
rotating K/V blocks (ring attention), Ulysses re-shards: inputs arrive
sequence-sharded [B, S/n, H, D]; one ``jax.lax.all_to_all`` over the ``seq``
axis turns them head-sharded [B, S, H/n, D]; each device runs *full-sequence*
attention for its head subset (any local kernel — including the Pallas flash
kernel); a second all-to-all restores sequence sharding.  Two all-to-alls of
activation size vs. ring's n single-hop permutes — better when n is small or
heads ≫ n; requires H divisible by the seq-axis size.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from ..ops.attention import reference_attention


def ulysses_attention_local(
    q, k, v, *, axis_name: str = "seq", causal: bool = True,
    attn_fn: Optional[Callable] = None,
):
    """shard_map-inner Ulysses attention.  q/k/v: [B, S_local, H, D] with H
    divisible by the axis size."""

    n = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    assert h % n == 0, f"heads ({h}) must divide by seq-axis size ({n})"
    attn = attn_fn or functools.partial(reference_attention, causal=causal)

    def seq_to_heads(x):
        # [B, S/n, H, D] → [B, S, H/n, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = attn(qh, kh, vh)
    return heads_to_seq(oh)


def ulysses_attention(q, k, v, mesh, *, causal: bool = True,
                      seq_axis: str = "seq", batch_axes=("data", "fsdp"),
                      attn_fn: Optional[Callable] = None):
    """Jit-compatible wrapper.  q/k/v: [B, S, H, D] global arrays (S sharded
    over ``seq_axis``; heads unsharded on that axis)."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, seq_axis, None, None)
    inner = functools.partial(
        ulysses_attention_local, axis_name=seq_axis, causal=causal,
        attn_fn=attn_fn,
    )
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)
