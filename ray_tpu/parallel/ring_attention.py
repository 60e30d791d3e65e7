"""Ring attention: blockwise context parallelism over the ``seq`` mesh axis.

Closes the reference's sequence-parallelism gap (SURVEY.md §5: no SP/CP/ring
attention anywhere in the reference — long context was delegated to external
engines).  TPU-native design: Q/K/V are sequence-sharded over the ``seq``
axis; each device computes attention of its local Q block against the K/V
block it currently holds, accumulating with the flash online-softmax rule,
while K/V blocks rotate around the ring via ``jax.lax.ppermute`` — the
collective rides neighbor ICI links, and XLA overlaps the permute with the
block matmuls.  Memory per device is O(S/n · S/n) per step instead of O(S²).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import NEG_INF


def _block_attn(q, k, v, scale, causal, q_block_idx, kv_block_idx, s_local):
    """One blockwise step: unnormalized (m, l, pv) contributions.
    q/k/v: [B, S_local, H, D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        q_pos = q_block_idx * s_local + jax.lax.broadcasted_iota(
            jnp.int32, (s_local, s_local), 0
        )
        k_pos = kv_block_idx * s_local + jax.lax.broadcasted_iota(
            jnp.int32, (s_local, s_local), 1
        )
        s = jnp.where((k_pos <= q_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [B,H,Q]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)  # unnormalized
    return m, l, pv


def ring_attention_local(q, k, v, *, axis_name: str = "seq",
                         causal: bool = True,
                         softmax_scale: Optional[float] = None):
    """The shard_map-inner ring attention.  Call inside a shard_map whose
    in_specs shard the sequence dim of q/k/v over ``axis_name``.

    q/k/v: [B, S_local, H, D] (this device's sequence shard).
    """

    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    m_acc = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l_acc = jnp.zeros((b, h, s_local), jnp.float32)
    o_acc = jnp.zeros((b, s_local, h, d), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        m_acc, l_acc, o_acc, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % n  # block the ring has delivered to us
        m_b, l_b, pv_b = _block_attn(
            q.astype(jnp.float32), k_cur.astype(jnp.float32),
            v_cur.astype(jnp.float32), scale, causal, my_idx, kv_idx, s_local,
        )
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)  # rescale old accumulators
        beta = jnp.exp(m_b - m_new)  # rescale this block
        l_new = l_acc * alpha + l_b * beta
        o_new = (
            o_acc * alpha.transpose(0, 2, 1)[..., None]
            + pv_b * beta.transpose(0, 2, 1)[..., None]
        )
        # Rotate K/V to the next neighbor (single-hop ICI transfer).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, o_new, k_nxt, v_nxt

    m_acc, l_acc, o_acc, _, _ = jax.lax.fori_loop(
        0, n, step, (m_acc, l_acc, o_acc, k, v)
    )
    # Fully-masked rows can have l == 0 only if causal masking removed every
    # key, which cannot happen (the diagonal block always contains k<=q).
    out = o_acc / jnp.maximum(l_acc, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, *, causal: bool = True,
                   seq_axis: str = "seq", batch_axes=("data", "fsdp"),
                   head_axis: str = "model"):
    """Jit-compatible wrapper: shard_maps the ring over the mesh.
    q/k/v: [B, S, H, D] global arrays (S sharded over ``seq_axis``)."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, seq_axis, head_axis, None)
    inner = functools.partial(
        ring_attention_local, axis_name=seq_axis, causal=causal
    )
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)
