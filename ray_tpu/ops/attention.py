"""Attention ops: XLA reference implementation + Pallas TPU flash kernel.

The compute-path replacement for what the reference framework delegates to
external engines (vLLM/FlashAttention CUDA kernels; see SURVEY.md §2.3 — the
reference has no attention kernels of its own).  TPU-first design:

  - ``reference_attention``: plain jnp einsum softmax — XLA already fuses
    this well for moderate sequence lengths; used as the CPU/test path and
    as the ground truth for kernel tests.
  - ``flash_attention``: blocked online-softmax Pallas kernel (VMEM-tiled,
    MXU matmuls with f32 accumulation) for long sequences on TPU; falls
    back to the reference off-TPU.  Forward kernel + custom VJP backed by
    the Pallas backward kernels below (``_flash_bwd_*``), which recompute
    per-block attention probabilities from the saved softmax statistics.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _causal_mask(sq: int, sk: int, q_offset: int = 0, k_offset: int = 0):
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return k_pos <= q_pos


def reference_attention(
    q, k, v, *, causal: bool = True, q_offset: int = 0, k_offset: int = 0,
    softmax_scale: Optional[float] = None,
):
    """q: [B, Sq, H, D]; k/v: [B, Sk, H, D] → [B, Sq, H, D]."""
    d = q.shape[-1]
    sq, sk = q.shape[1], k.shape[1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = _causal_mask(sq, sk, q_offset, k_offset)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# --------------------------------------------------------------------------
# Pallas TPU flash attention (forward kernel)
# --------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                      block_k: int, sk: int, causal: bool, scale: float):
    """Grid: (batch*heads, Sq/block_q).  Ref tiles (leading dim squeezed):
    q_ref [block_q, D], k_ref/v_ref [Sk, D], o_ref [block_q, D],
    lse_ref [block_q] (per-row logsumexp, saved for the backward kernels)."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    q_block = pl.program_id(1)
    # Matmul inputs stay in the storage dtype (bf16): the MXU's native rate
    # is bf16xbf16->f32; upcasting tiles first would run every dot at the
    # much slower f32 rate.  Scale and softmax arithmetic happen on the f32
    # accumulator.
    q = q_ref[:]

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    num_k_blocks = sk // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_tile = k_ref[pl.ds(kb * block_k, block_k), :]
        v_tile = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k_tile.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_block * block_q + iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p.astype(v_tile.dtype), v_tile,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    if causal:
        # Only K blocks up to (and including) the diagonal contribute.
        num_iter = jnp.minimum(
            jax.lax.div((q_block + 1) * block_q + block_k - 1, block_k),
            num_k_blocks,
        )
    else:
        num_iter = num_k_blocks
    m, l, acc = jax.lax.fori_loop(0, num_iter, body, (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
               interpret: bool):
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    # Fold batch and heads into the grid's first axis.
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, sk=sk,
        causal=causal, scale=scale,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qb: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, qb: (bh, qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     *, block_q: int, block_k: int, sk: int, causal: bool,
                     scale: float):
    """dQ: grid (batch*heads, Sq/block_q); inner loop over K blocks.

    ds = p * (dO·Vᵀ − delta);  dq = scale · ds · K  with p recomputed from
    the saved per-row logsumexp (the flash-attention backward recipe)."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    q_block = pl.program_id(1)
    # bf16 matmul operands, f32 accumulation/arithmetic (see fwd kernel).
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]
    delta = delta_ref[:]
    num_k_blocks = sk // block_k

    def body(kb, dq):
        k_tile = k_ref[pl.ds(kb * block_k, block_k), :]
        v_tile = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k_tile.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_block * block_q + iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v_tile.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(
            ds.astype(k_tile.dtype), k_tile,
            preferred_element_type=jnp.float32,
        )

    if causal:
        num_iter = jnp.minimum(
            jax.lax.div((q_block + 1) * block_q + block_k - 1, block_k),
            num_k_blocks,
        )
    else:
        num_iter = num_k_blocks
    dq = jax.lax.fori_loop(
        0, num_iter, body, jnp.zeros(dq_ref.shape, jnp.float32)
    )
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, block_q: int, block_k: int, sq: int,
                      causal: bool, scale: float):
    """dK/dV: grid (batch*heads, Sk/block_k); inner loop over Q blocks at or
    after the diagonal.  dv = pᵀ·dO;  dk = scale · dsᵀ·q."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    k_block = pl.program_id(1)
    # bf16 matmul operands, f32 accumulation/arithmetic (see fwd kernel).
    k_tile = k_ref[:]
    v_tile = v_ref[:]
    num_q_blocks = sq // block_q

    def body(qb, carry):
        dk, dv = carry
        q_tile = q_ref[pl.ds(qb * block_q, block_q), :]
        do = do_ref[pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[pl.ds(qb * block_q, block_q), :]
        delta = delta_ref[pl.ds(qb * block_q, block_q), :]
        s = jnp.dot(q_tile, k_tile.T,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_block * block_k + iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        pb = p.astype(do.dtype)
        dv = dv + jnp.dot(pb.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_tile.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q_tile.dtype)
        dk = dk + jnp.dot(ds.T, q_tile, preferred_element_type=jnp.float32)
        return dk, dv

    # Causal: Q blocks strictly before the diagonal see no keys of this
    # K block — start the loop at the diagonal.
    start = (
        jax.lax.div(k_block * block_k, block_q) if causal else 0
    )
    dk, dv = jax.lax.fori_loop(
        start, num_q_blocks, body,
        (jnp.zeros(dk_ref.shape, jnp.float32),
         jnp.zeros(dv_ref.shape, jnp.float32)),
    )
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: bool):
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dof = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = Σ_d dO_id · O_id  (rowwise), in plain XLA.
    delta = (
        (g.astype(jnp.float32) * o.astype(jnp.float32))
        .sum(-1)
        .transpose(0, 2, 1)
        .reshape(b * h, sq, 1)
    )

    dq_kernel = functools.partial(
        _flash_dq_kernel, block_q=block_q, block_k=block_k, sk=sk,
        causal=causal, scale=scale,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, qb: (bh, qb, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, block_q=block_q, block_k=block_k, sq=sq,
        causal=causal, scale=scale,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, kb: (bh, kb, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, kb: (bh, kb, 0)),
            pl.BlockSpec((None, sq, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda bh, kb: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bh, kb: (bh, kb, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, kb: (bh, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    unfold = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unfold(dq, sq), unfold(dk, sk), unfold(dv, sk)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    scale = q.shape[-1] ** -0.5
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    scale = q.shape[-1] ** -0.5
    return _flash_bwd(
        q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 512, block_k: int = 512,
    force_pallas: bool = False, force_reference: bool = False,
):
    """Flash attention, q/k/v: [B, S, H, D].  Calling this asks for the
    kernel: on a TPU it is the Pallas kernel or an error (a sequence that
    the blocks do not divide, or a compile the chip refuses) — never a
    silent XLA reference.  Off a TPU the unforced path is the reference;
    ``force_pallas`` runs the kernel there in interpret mode (tests).
    ``force_reference`` is the caller's explicit way to the XLA path.

    Forward and backward are both Pallas TPU kernels (backward is the
    dq + dkv two-kernel recipe recomputing p from the saved per-row
    logsumexp)."""
    on_tpu = _on_tpu()
    if force_reference or not (on_tpu or force_pallas):
        return reference_attention(q, k, v, causal=causal)
    sq, sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash attention: sequence lengths ({sq}, {sk}) are not "
            f"multiples of the blocks ({bq}, {bk}); pad the sequence, pass "
            "other blocks, or ask for the reference (force_reference=True)"
        )
    return _flash(q, k, v, causal, bq, bk, not on_tpu)
