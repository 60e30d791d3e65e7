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
    the one Pallas backward kernel below (``_flash_bwd_kernel``), which
    rebuilds each tile pair's probabilities once from the saved softmax
    statistics.
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
# Pallas TPU flash attention: one forward kernel, one backward kernel
# --------------------------------------------------------------------------
#
# Both kernels hold a tile pair's scores TRANSPOSED, ``k q^T`` =
# [block_k, block_q], keys down the sublanes and queries along the lanes.
# The softmax's reductions over keys are then elementwise maxima and sums of
# whole registers (no cross-lane reduction), the per-query statistics (m, l,
# lse, delta) are lane-dense rows, and all but one product a kernel feed the
# MXU their operands as they lie: the forward's acc^T = v^T p^T and the backward's
# dq^T = k^T ds^T contract over the first axis of both operands, whose
# transposed side is the small [block_k, D] tile.  No [block_q, block_k]
# tile is ever transposed.  out and dq leave the kernels as [D, S] and are
# turned by the reshape to [B, S, H, D] that follows them anyway.
#
# Under a causal mask a kernel visits only the tile pairs that hold a live
# score: ``_live_key_tiles`` / ``_first_query_tile`` are the loops' bounds,
# from the forward's side (a query tile's key tiles) and the backward's (a
# key tile's query tiles); ``flash_tile_work`` adds them up.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T: the MXU takes b transposed as it is
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _div(a, b):
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _min(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _live_key_tiles(qb, block_q: int, block_k: int, nk: int, causal: bool):
    """Query tile ``qb`` runs key tiles ``[0, this)``: those with a key at or
    before its last query.  ``qb`` is a Python int (``flash_tile_work``) or
    a traced program id (the kernel)."""
    if not causal:
        return nk
    return _min(_div((qb + 1) * block_q + block_k - 1, block_k), nk)


def _first_query_tile(kb, block_q: int, block_k: int, nq: int, causal: bool):
    """Key tile ``kb`` runs query tiles ``[this, nq)``: those with a query
    at or after its first key."""
    if not causal:
        return 0
    return _min(_div(kb * block_k, block_q), nq)


def flash_tile_work(sq: int, sk: int, block_q: int, block_k: int,
                    causal: bool) -> dict:
    """What the kernels execute for one head of ``[sq, sk]`` scores, counted
    from the loops' own bounds: ``pairs`` tile pairs run by each kernel (the
    forward runs 2 products a pair, the backward 5: s, dv, dp, dk, dq, each
    once), ``diagonal`` of them crossed by the diagonal (the others hold no
    dead score; all run through the one masked loop, because a second,
    unmasked loop costs more than the mask: PERF.md, PR 49), and
    ``executed_over_needed``: the score elements those pairs cover over the
    elements the mask leaves live (what ``benchmarks/lib/flops.py``
    ``flash_step_need`` counts)."""
    nq, nk = sq // block_q, sk // block_k
    live = [_live_key_tiles(qb, block_q, block_k, nk, causal)
            for qb in range(nq)]
    pairs = sum(live)
    assert pairs == sum(
        nq - _first_query_tile(kb, block_q, block_k, nq, causal)
        for kb in range(nk))
    # wholly under the diagonal: the pair's last key <= its first query
    under = sum(min(end, (qb * block_q + 1) // block_k)
                for qb, end in enumerate(live)) if causal else pairs
    needed = sum(min(r + 1, sk) for r in range(sq)) if causal else sq * sk
    return {"pairs": pairs, "diagonal": pairs - under,
            "executed_over_needed": pairs * block_q * block_k / needed}


# The side of the tiles, and there is no knob for it: swept on a v5e at the
# training cells' [32, 1024, 16, 64] over {128, 256, 512, 1024}^2, forward
# and backward apart, 512 x 512 is the fastest of both (PERF.md, PR 49: the
# table).  Tiles that hug the diagonal closer (256: 1.25 x the causal need
# against 1.5 x) run fewer score elements and are SLOWER, forward 2.77
# against 1.53 ms, backward 4.70 against 3.00: a loop turn's fixed cost
# outweighs the dead half of two diagonal tiles.
_TILE = 512


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                      block_k: int, sk: int, causal: bool, scale: float):
    """Grid: (batch*heads, Sq/block_q).  Ref tiles (leading dim squeezed):
    q_ref [block_q, D], k_ref/v_ref [Sk, D], o_ref [D, block_q] (out^T),
    lse_ref [1, block_q] (per-query logsumexp, saved for the backward
    kernel)."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    q_block = pl.program_id(1)
    # Matmul inputs stay in the storage dtype (bf16): the MXU's native rate
    # is bf16xbf16->f32; upcasting tiles first would run every dot at the
    # much slower f32 rate.  The softmax scale goes into the [block_q, D]
    # query tile once, not into every score tile; softmax arithmetic happens
    # on the f32 accumulator.
    q = (q_ref[:] * scale).astype(q_ref.dtype)

    def body(kb, carry):
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        s = jax.lax.dot_general(k_ref[keys, :], q, _NT,
                                preferred_element_type=jnp.float32)
        if causal:
            k_pos = kb * block_k + iota(jnp.int32, (block_k, block_q), 0)
            q_pos = q_block * block_q + iota(jnp.int32, (block_k, block_q), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        v_tile = v_ref[keys, :]
        acc = acc * alpha + jax.lax.dot_general(
            v_tile, p.astype(v_tile.dtype), _TN,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, _live_key_tiles(q_block, block_q, block_k, sk // block_k, causal),
        body,
        (jnp.full((1, block_q), NEG_INF, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32),
         jnp.zeros(o_ref.shape, jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


def _fold_heads(x):
    """[B, S, H, D] -> [B*H, S, D]: batch and heads are the grid's first
    axis."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold_transposed(x, b: int):
    """A kernel's [B*H, D, S] output -> [B, S, H, D]."""
    bh, d, s = x.shape
    return x.reshape(b, bh // b, d, s).transpose(0, 3, 1, 2)


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
               interpret: bool):
    """-> (out [B, Sq, H, D], lse [B*H, 1, Sq] float32)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
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
            pl.BlockSpec((None, d, block_q), lambda bh, qb: (bh, 0, qb)),
            pl.BlockSpec((None, 1, block_q), lambda bh, qb: (bh, 0, qb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, d, sq), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(_fold_heads(q), _fold_heads(k), _fold_heads(v))
    return _unfold_transposed(out, b), lse


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, sq: int, causal: bool,
                      scale: float):
    """dQ, dK, dV in one pass that builds s, p, dp, ds once a tile pair: 5
    products and 1 exponential.  Grid (batch*heads, Sk/block_k), the K/V tile
    resident, an inner loop over the query tiles from the diagonal on.  Refs:
    q_ref/do_ref [Sq, D], k_ref/v_ref [block_k, D], lse_ref/delta_ref
    [1, Sq], dk_ref/dv_ref [block_k, D]; dq_ref [D, Sq] (dq^T) keeps its
    block along the key-tile axis, so ``dq_acc`` (float32, VMEM) gathers
    every key tile's share: zeroed at the first key tile, cast into dq_ref
    after the last.  ``dk_acc`` / ``dv_acc`` (float32 [block_k, D], VMEM)
    gather a key tile's query tiles.

    With the scores transposed, p^T and ds^T are what the loop holds:
    dv += p^T dO and dk += ds^T q are plain products, dq^T += k^T ds^T
    contracts over the first axis of both.  ds = p * (dO v^T - delta), p
    from the saved per-query logsumexp."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    k_block = pl.program_id(1)
    v_tile = v_ref[:]
    # bf16 matmul operands, f32 accumulation/arithmetic (see fwd kernel).
    # The scale goes into the resident key tile once: s = q (scale k)^T and
    # dq = ds (scale k) need no other; dk takes it after the loop.
    k_tile = (k_ref[:] * scale).astype(k_ref.dtype)

    @pl.when(k_block == 0)
    def _():
        dq_acc[:] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    def body(qb, _):
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        q_tile = q_ref[rows, :]
        do = do_ref[rows, :]
        s = jax.lax.dot_general(k_tile, q_tile, _NT,
                                preferred_element_type=jnp.float32)
        if causal:
            k_pos = k_block * block_k + iota(jnp.int32, (block_k, block_q), 0)
            q_pos = qb * block_q + iota(jnp.int32, (block_k, block_q), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, rows])
        dv_acc[:] += jnp.dot(p.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_tile, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, rows])).astype(q_tile.dtype)
        dk_acc[:] += jnp.dot(ds, q_tile, preferred_element_type=jnp.float32)
        dq_acc[:, rows] += jax.lax.dot_general(
            k_tile, ds, _TN, preferred_element_type=jnp.float32)
        return 0

    nq = sq // block_q
    jax.lax.fori_loop(
        _first_query_tile(k_block, block_q, block_k, nq, causal), nq, body, 0)
    dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(k_block == pl.num_programs(1) - 1)
    def _():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: bool):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    # delta_i = Σ_d dO_id · O_id  (per query), in plain XLA; along the lanes
    # like lse.
    delta = (
        (g.astype(jnp.float32) * o.astype(jnp.float32))
        .sum(-1)
        .transpose(0, 2, 1)
        .reshape(b * h, 1, sq)
    )

    kernel = functools.partial(
        _flash_bwd_kernel, block_q=block_q, block_k=block_k, sq=sq,
        causal=causal, scale=scale,
    )
    whole_q = pl.BlockSpec((None, sq, d), lambda bh, kb: (bh, 0, 0))
    key_tile = pl.BlockSpec((None, block_k, d), lambda bh, kb: (bh, kb, 0))
    row = pl.BlockSpec((None, 1, sq), lambda bh, kb: (bh, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, sk // block_k),
        in_specs=[whole_q, key_tile, key_tile, whole_q, row, row],
        out_specs=[
            pl.BlockSpec((None, d, sq), lambda bh, kb: (bh, 0, 0)),
            key_tile, key_tile,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, d, sq), q.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, sq), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        # dq's block is revisited along the key tiles: that axis runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_fold_heads(q), _fold_heads(k), _fold_heads(v), _fold_heads(g), lse,
      delta)

    unfold = lambda x: x.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return _unfold_transposed(dq, b), unfold(dk), unfold(dv)


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
    q, k, v, *, causal: bool = True, block_q: Optional[int] = None,
    block_k: Optional[int] = None, force_pallas: bool = False,
    force_reference: bool = False,
):
    """Flash attention, q/k/v: [B, S, H, D].  Calling this asks for the
    kernel: on a TPU it is the Pallas kernel or an error (a sequence that
    the blocks do not divide, or a compile the chip refuses) — never a
    silent XLA reference.  Off a TPU the unforced path is the reference;
    ``force_pallas`` runs the kernel there in interpret mode (tests).
    ``force_reference`` is the caller's explicit way to the XLA path.

    ``block_q`` / ``block_k`` are the tiles' sides; ``None`` (what every
    model passes) is ``_TILE``, 512, the fastest of the sweep on the chip
    both ways, or the whole sequence where that is shorter.  There is no
    other knob: a tile's side is a property of the kernels and the chip,
    not of a deployment.

    Forward and backward are one Pallas TPU kernel each; the backward
    builds each tile pair's probabilities once, from the saved per-query
    logsumexp, for dq, dk and dv together (``flash_tile_work`` counts the
    pairs both run)."""
    on_tpu = _on_tpu()
    if force_reference or not (on_tpu or force_pallas):
        return reference_attention(q, k, v, causal=causal)
    sq, sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q or _TILE, sq), min(block_k or _TILE, sk)
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash attention: sequence lengths ({sq}, {sk}) are not "
            f"multiples of the blocks ({bq}, {bk}); pad the sequence, pass "
            "other blocks, or ask for the reference (force_reference=True)"
        )
    return _flash(q, k, v, causal, bq, bk, not on_tpu)
