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
    statistics.  The entry for q, k and v that come as three arrays
    (separate projections: ``models/llama.py``'s grouped heads,
    ``models/vit.py``).
  - ``flash_attention_packed``: the same two kernels for a caller that has
    ONE projected array ``[B, S, 3, H, D]`` (``models/gpt2.py``): they
    address q, k and v inside it and write one d(qkv) of its shape.  Which
    entry is taken follows from what the caller has, not from an option.
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
# Every operand and result crosses the ``pallas_call`` boundary with the
# sequence along the lanes and the head's 64 or 128 down the sublanes:
# ``[B*H, D, S]`` (``_fold``) for q, k, v, dO, out, dq, dk, dv of the
# unpacked entry.  That is where the compiled training step keeps them (the
# q/k/v projection leaves ``[B, 3, H, D, S]``), so the folds around the
# kernels are bitcasts and no ``copy`` program stands between a projection
# and a kernel; and a ``[D, S]`` tile is dense where a ``[S, 64]`` one fills
# half of every 128-lane tile row (PERF.md, PR 50).  The packed entry goes
# one step further: the projection's result crosses as it lies, ONE
# ``[B, 3, H, D, S]`` operand given three times, a head's q, k or v tile
# addressed inside it by its ``BlockSpec`` (``_head_spec``), and the
# backward's three results are views of ONE d(qkv) of that shape; only
# ``out`` and dO stay ``[B*H, D, S]``.  So the step neither splits the
# projection's result into three arrays (forward, and again under the
# layers' remat) nor lays three gradients back into one (PERF.md, PR 59).
#
# Both kernels hold a tile pair's scores TRANSPOSED, ``k q^T`` =
# [block_k, block_q], keys down the sublanes and queries along the lanes.
# The softmax's reductions over keys are then elementwise maxima and sums of
# whole registers (no cross-lane reduction), the per-query statistics (m, l,
# lse, delta) are lane-dense rows, and no [block_k, block_q] tile is ever
# turned: acc^T = v^T p^T and dq^T = k^T ds^T are plain products, dv^T =
# dO^T p and dk^T = q^T ds contract over the lanes of both sides (the
# ``q k^T`` form).  What does not come as it lies is k (and in the backward
# v) as [keys, D] for s^T = k q^T and dp^T = v dO^T: the small [D, block_k]
# side.  The forward turns the resident K once a head into VMEM; the
# backward contracts over the sublanes of both sides and leaves the turn to
# the compiler (each is what the chip preferred: PERF.md, PR 50).
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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, k_rows, *,
                      block_q: int, block_k: int, sk: int, causal: bool,
                      scale: float, tile_axis: int = 1):
    """Grid: (batch*heads, Sq/block_q), or (batch, heads, Sq/block_q) over
    the packed projection; the last axis (``tile_axis``) in order.  Ref tiles
    (leading dim squeezed): q_ref [D, block_q] (q^T), k_ref/v_ref [D, Sk]
    (k^T, v^T: the same block for every query tile of a head), o_ref
    [D, block_q] (out^T), lse_ref [1, block_q] (per-query logsumexp, saved
    for the backward kernel).  ``k_rows`` (VMEM, [Sk, D]) is k turned, once a
    head: at the head's first query tile."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    q_block = pl.program_id(tile_axis)

    @pl.when(q_block == 0)
    def _():
        k_rows[:] = k_ref[:].T

    # Matmul inputs stay in the storage dtype (bf16): the MXU's native rate
    # is bf16xbf16->f32; upcasting tiles first would run every dot at the
    # much slower f32 rate.  The softmax scale goes into the [D, block_q]
    # query tile once, not into every score tile; softmax arithmetic happens
    # on the f32 accumulator.
    q = (q_ref[:] * scale).astype(q_ref.dtype)

    def body(kb, carry):
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        s = jnp.dot(k_rows[keys, :], q, preferred_element_type=jnp.float32)
        if causal:
            k_pos = kb * block_k + iota(jnp.int32, (block_k, block_q), 0)
            q_pos = q_block * block_q + iota(jnp.int32, (block_k, block_q), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        v_cols = v_ref[:, keys]
        acc = acc * alpha + jnp.dot(v_cols, p.astype(v_cols.dtype),
                                    preferred_element_type=jnp.float32)
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


def _fold(x):
    """[B, S, H, D] -> [B*H, D, S], the kernels' layout: batch and heads are
    the grid's first axis, the sequence lies along the lanes."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 3, 1).reshape(b * h, d, s)


def _unfold(x, b: int):
    """A kernel's [B*H, D, S] -> [B, S, H, D]."""
    bh, d, s = x.shape
    return x.reshape(b, bh // b, d, s).transpose(0, 3, 1, 2)


def _head_spec(rows: int, cols: int, heads: Optional[int] = None,
               plane: Optional[int] = None, *, resident: bool = False):
    """The ``BlockSpec`` of one head's ``[rows, cols]`` tile, at the grid
    cell's column tile or, ``resident``, at the head's only one.  ``heads``
    None: a ``[B*H, rows, S]`` operand under the grid ``(B*H, tiles)``.
    Else the grid is ``(B, H, tiles)`` (``_grid``) and the operand that same
    array (``plane`` None: head ``b * H + h``) or the packed projection
    ``[B, 3, H, D, S]``, the tile addressed in its plane ``plane`` (q 0, k 1,
    v 2) where it lies."""
    import jax.experimental.pallas as pl

    col = (lambda j: 0) if resident else (lambda j: j)
    if heads is None:
        return pl.BlockSpec((None, rows, cols), lambda i, j: (i, 0, col(j)))
    if plane is None:
        return pl.BlockSpec((None, rows, cols),
                            lambda b, h, j: (b * heads + h, 0, col(j)))
    return pl.BlockSpec((None, None, None, rows, cols),
                        lambda b, h, j: (b, plane, h, 0, col(j)))


def _heads_of(q, k, v):
    """(B*H, D, Sq, Sk, H if packed) of a kernel's q, k, v: three
    ``[B*H, D, S]`` arrays, or the ONE packed ``[B, 3, H, D, S]`` given for
    all three."""
    if q.ndim == 5:
        assert q is k and q is v, "one packed projection, three times"
        b, _three, h, d, s = q.shape
        return b * h, d, s, s, h
    bh, d, sq = q.shape
    return bh, d, sq, k.shape[2], None


def _grid(bh: int, tiles: int, heads: Optional[int]):
    """The grid, its static arguments to a kernel body and its semantics:
    heads by tiles, the tiles' axis last and in order (a scratch or a
    result block lives across it).  Over the packed projection batch and
    heads are an axis each, so that no index map divides."""
    import jax.experimental.pallas.tpu as pltpu

    grid = (bh, tiles) if heads is None else (bh // heads, heads, tiles)
    semantics = ("parallel",) * (len(grid) - 1) + ("arbitrary",)
    return grid, len(grid) - 1, pltpu.CompilerParams(
        dimension_semantics=semantics)


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """q [B*H, D, Sq], k/v [B*H, D, Sk] (``_fold``ed), or the packed
    projection [B, 3, H, D, S] as all three, its planes addressed by the
    ``BlockSpec``s -> (out [B*H, D, Sq], lse [B*H, 1, Sq] float32)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bh, d, sq, sk, heads = _heads_of(q, k, v)
    # k_rows is filled at a head's first query tile: that axis runs in order
    grid, tile_axis, params = _grid(bh, sq // block_q, heads)
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, sk=sk,
        causal=causal, scale=d ** -0.5, tile_axis=tile_axis,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_head_spec(d, block_q, heads, 0),
                  _head_spec(d, sk, heads, 1, resident=True),
                  _head_spec(d, sk, heads, 2, resident=True)],
        out_specs=[_head_spec(d, block_q, heads),
                   _head_spec(1, block_q, heads)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d, sq), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((sk, d), k.dtype)],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, sq: int, causal: bool,
                      scale: float, tile_axis: int = 1):
    """dQ, dK, dV in one pass that builds s, p, dp, ds once a tile pair: 5
    products and 1 exponential.  Grid (batch*heads, Sk/block_k), or (batch,
    heads, Sk/block_k) over the packed projection (``tile_axis`` 2), the K/V tile
    resident, an inner loop over the query tiles from the diagonal on.  Refs,
    all transposed: q_ref/do_ref [D, Sq], k_ref/v_ref [D, block_k],
    lse_ref/delta_ref [1, Sq], dk_ref/dv_ref [D, block_k]; dq_ref [D, Sq]
    keeps its block along the key-tile axis, so ``dq_acc`` (float32, VMEM)
    gathers every key tile's share: zeroed at the first key tile, cast into
    dq_ref after the last.  ``dk_acc`` / ``dv_acc`` (float32 [D, block_k],
    VMEM) gather a key tile's query tiles.

    With the scores transposed, p^T and ds^T are what the loop holds, and
    q^T, dO^T stream as they come: dv^T += dO^T p and dk^T += q^T ds
    contract over the lanes of both sides, dq^T += k^T ds^T is plain, and
    s^T = k q^T and dp^T = v dO^T contract over the sublanes of both (the
    compiler turns the [D, block_k] side a product; on the chip that beats
    tiles turned once a grid cell into VMEM, 2.41 against 2.52 ms a layer:
    PERF.md, PR 50).  ds = p * (dO v^T - delta), p from the saved per-query
    logsumexp."""
    import jax.experimental.pallas as pl

    iota = jax.lax.broadcasted_iota
    k_block = pl.program_id(tile_axis)
    # bf16 matmul operands, f32 accumulation/arithmetic (see fwd kernel).
    # The scale goes into the resident key tile once: s = q (scale k)^T and
    # dq = ds (scale k) need no other; dk takes it after the loop.
    k_cols = (k_ref[:] * scale).astype(k_ref.dtype)
    v_cols = v_ref[:]

    @pl.when(k_block == 0)
    def _():
        dq_acc[:] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    def body(qb, _):
        cols = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        q_cols = q_ref[:, cols]
        do = do_ref[:, cols]
        s = jax.lax.dot_general(k_cols, q_cols, _TN,
                                preferred_element_type=jnp.float32)
        if causal:
            k_pos = k_block * block_k + iota(jnp.int32, (block_k, block_q), 0)
            q_pos = qb * block_q + iota(jnp.int32, (block_k, block_q), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, cols])
        dv_acc[:] += jax.lax.dot_general(
            do, p.astype(do.dtype), _NT, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_cols, do, _TN,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, cols])).astype(q_cols.dtype)
        dk_acc[:] += jax.lax.dot_general(
            q_cols, ds, _NT, preferred_element_type=jnp.float32)
        dq_acc[:, cols] += jnp.dot(k_cols, ds,
                                   preferred_element_type=jnp.float32)
        return 0

    nq = sq // block_q
    jax.lax.fori_loop(
        _first_query_tile(k_block, block_q, block_k, nq, causal), nq, body, 0)
    dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(k_block == pl.num_programs(tile_axis) - 1)
    def _():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dqkv_ref, dq_acc, dk_acc, dv_acc, *, block_k: int,
                             tile_axis: int, **static):
    """``_flash_bwd_kernel`` with its three results as views of ONE:
    dqkv_ref [3, D, S] is a head's planes of d(qkv) [B, 3, H, D, S], its
    block kept along the key-tile axis as ``dq``'s is.  A key tile's dk and
    dv are its columns of planes 1 and 2, dq is plane 0, whole."""
    import jax.experimental.pallas as pl

    keys = pl.ds(
        pl.multiple_of(pl.program_id(tile_axis) * block_k, block_k), block_k)
    _flash_bwd_kernel(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dqkv_ref.at[0],
        dqkv_ref.at[1, :, keys], dqkv_ref.at[2, :, keys], dq_acc, dk_acc,
        dv_acc, block_k=block_k, tile_axis=tile_axis, **static)


def _flash_bwd(q, k, v, o, lse, g, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """q, k, v, o, g ``_fold``ed, [B*H, D, S]: -> (dq, dk, dv) so too.  With
    the packed projection [B, 3, H, D, S] as q, k and v: -> d(qkv) of that
    shape, ONE result whose planes the kernel writes where they lie."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    bh, d, sq, sk, heads = _heads_of(q, k, v)
    # delta_i = Σ_d dO_id · O_id  (per query), in plain XLA: a sum down the
    # sublanes of [D, S], its result along the lanes like lse.
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        1, keepdims=True)

    # dq's block (d(qkv)'s) is revisited along the key tiles: that axis runs
    # in order
    grid, tile_axis, params = _grid(bh, sk // block_k, heads)
    static = dict(block_q=block_q, block_k=block_k, sq=sq, causal=causal,
                  scale=d ** -0.5, tile_axis=tile_axis)
    whole_q = _head_spec(d, sq, heads, resident=True)
    row = _head_spec(1, sq, heads, resident=True)
    if heads is None:
        kernel = functools.partial(_flash_bwd_kernel, **static)
        key_tile = _head_spec(d, block_k)
        out_specs = [whole_q, key_tile, key_tile]
        out_shape = [
            jax.ShapeDtypeStruct((bh, d, sq), q.dtype),
            jax.ShapeDtypeStruct((bh, d, sk), k.dtype),
            jax.ShapeDtypeStruct((bh, d, sk), v.dtype),
        ]
    else:
        kernel = functools.partial(_flash_bwd_packed_kernel, **static)
        out_specs = pl.BlockSpec((None, 3, None, d, sq),
                                 lambda b, h, kb: (b, 0, h, 0, 0))
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_head_spec(d, sq, heads, 0, resident=True),
                  _head_spec(d, block_k, heads, 1),
                  _head_spec(d, block_k, heads, 2),
                  whole_q, row, row],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((d, sq), jnp.float32),
            pltpu.VMEM((d, block_k), jnp.float32),
            pltpu.VMEM((d, block_k), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, g, lse, delta)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    folded = _fold(q), _fold(k), _fold(v)
    out, lse = _flash_fwd(*folded, causal, block_q, block_k, interpret)
    return _unfold(out, q.shape[0]), (*folded, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    grads = _flash_bwd(q, k, v, out, lse, _fold(g), causal, block_q, block_k,
                       interpret)
    return tuple(_unfold(x, g.shape[0]) for x in grads)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_packed(qkv, causal, block_q, block_k, interpret):
    return _flash_packed_fwd_rule(qkv, causal, block_q, block_k, interpret)[0]


def _flash_packed_fwd_rule(qkv, causal, block_q, block_k, interpret):
    # [B, S, 3, H, D] seen as [B, 3, H, D, S]: where the compiled step keeps
    # the projection's result, so a bitcast there
    packed = qkv.transpose(0, 2, 3, 4, 1)
    out, lse = _flash_fwd(packed, packed, packed, causal, block_q, block_k,
                          interpret)
    return _unfold(out, qkv.shape[0]), (packed, out, lse)


def _flash_packed_bwd_rule(causal, block_q, block_k, interpret, res, g):
    packed, out, lse = res
    dqkv = _flash_bwd(packed, packed, packed, out, lse, _fold(g), causal,
                      block_q, block_k, interpret)
    return (dqkv.transpose(0, 4, 1, 2, 3),)


_flash_packed.defvjp(_flash_packed_fwd_rule, _flash_packed_bwd_rule)


def _tiles(sq: int, sk: int, block_q: Optional[int], block_k: Optional[int]):
    """The tiles' sides for sequences of ``sq`` queries and ``sk`` keys:
    what was asked for, else ``_TILE``, the whole sequence where that is
    shorter; an error where they do not divide the sequences."""
    bq, bk = min(block_q or _TILE, sq), min(block_k or _TILE, sk)
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash attention: sequence lengths ({sq}, {sk}) are not "
            f"multiples of the blocks ({bq}, {bk}); pad the sequence, pass "
            "other blocks, or ask for the reference (force_reference=True)"
        )
    return bq, bk


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
    bq, bk = _tiles(q.shape[1], k.shape[1], block_q, block_k)
    return _flash(q, k, v, causal, bq, bk, not on_tpu)


def flash_attention_packed(
    qkv, *, causal: bool = True, block_q: Optional[int] = None,
    block_k: Optional[int] = None, force_pallas: bool = False,
):
    """``flash_attention`` for a caller that HAS one projected array: qkv
    [B, S, 3, H, D] (q, k, v its planes, as ``"bse,ethd->bsthd"`` gives
    them) -> [B, S, H, D].  The kernels address q, k and v inside it and the
    backward writes ONE d(qkv) of its shape, so nothing splits the
    projection's result on the way in and nothing lays three gradients back
    into one on the way out.  The same kernels, tiles and errors as
    ``flash_attention``, which remains for separate projections; off a TPU
    the unforced path is the reference on the three planes."""
    if qkv.ndim != 5 or qkv.shape[2] != 3:
        raise ValueError(
            f"flash attention: a packed projection is [B, S, 3, H, D], not "
            f"{qkv.shape}")
    on_tpu = _on_tpu()
    if not (on_tpu or force_pallas):
        return reference_attention(
            qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal=causal)
    bq, bk = _tiles(qkv.shape[1], qkv.shape[1], block_q, block_k)
    return _flash_packed(qkv, causal, bq, bk, not on_tpu)
