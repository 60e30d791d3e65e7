"""One query token a slot against its latents in the stacked cache: ONE
pipelined pass over the live blocks.

The latent cache is one donated leaf ``[A, B, T, C]`` (attentions, slots,
positions, ``[ckv | kr]``: ``models/mla.py``).  A decode step's absorbed
query ``qc [B, H, C]`` scores positions ``[0, pos)`` of its slot over all
``C`` channels and weighs the first ``rkv`` of them, with the current token's
latent as one more column.  The arithmetic is ``decode_attention.
attend_blocks``' online softmax: bfloat16 products summed in float32, a
float32 running ``(max, sum, weighted latents)`` that every block of
``extent_step(T)`` = 512 positions updates, and as many blocks as the BATCH's
longest context needs (``live_extent``).

In XLA (``latent_attention_xla``, the form off a TPU and the kernel's oracle
in the tests) that is a loop whose body copies a block ``[B, 512, C]`` out of
the leaf into the fast memory and then runs the two products on it: serial,
copy j, products j, copy j + 1, and on the v5e the copy alone was 39 % of
the Mistral-4 cell's decode step (PERF.md, PR 58 / PR 68).  ``_kernel`` is the
same pass as a grid ``(B / slots, T / 512)`` over the leaf WHERE IT LIES: the
grid's double buffering fetches block ``j + 1`` of a cell's slots while block
``j`` is scored, so a step pays the larger of fetch and products and not
their sum.  Nothing of the leaf is sliced or copied first: the leaf goes in
whole and the block's ``index_map`` picks layer and block.

Where the leaf lies.  ``C`` (320, 576) is no multiple of 128, so the TPU keeps
the leaf's POSITIONS on the lanes and its channels on the sublanes
(``decode_attention.tile_positions``): the bytes of ``[A, B, T, C]`` as the
programs hold it are those of a row-major ``[A, B, C, T]``.  The kernel is
handed that view (``swapaxes``: no data moves, ``tests/test_tpu_compile.py``
reads it from the compiled steps), and both products take it as it comes:
scores ``[H, C] x [C, 512]``, weights ``[H, 512] x [rkv, 512]^T``.

The live bound is a prefetched scalar: blocks at and beyond it are fetched
by nobody (from there on the index map stands on the next cell's first
block, and a block index that does not change moves nothing) and their grid
steps do nothing.  A block wholly beyond a ROW's own context changes nothing
of that row (weights exactly zero, the maximum no higher), so a row's result
is the same bits whatever its neighbours' contexts made the bound.

``latent_attention`` is the one way in: on a TPU the kernel or the
compiler's error, anywhere else the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention
from .decode_attention import (NEG_INF, attend_live_blocks, extent_step,
                               live_extent)
from .delta_update import LANES, SUBLANES

# What a cell's block of latents may take of the fast memory: it is held
# twice (the grid's double buffer), beside the queries, the running sums and
# what the products spill, in the v5e's 128 MiB of which a kernel is given
# ``vmem_limit_bytes``.
_BLOCK_BYTES = 6 << 20


def slots_per_cell(b: int, c: int, step: int, itemsize: int) -> int:
    """How many slots one grid cell carries: the most (a power of two that
    divides ``b``) whose block of ``step`` positions of ``c`` channels stays
    within ``_BLOCK_BYTES``.  The shapes decide, and nothing else: a Kimi
    block of all 64 slots is 37.7 MB."""
    slots = 1
    while (b % (2 * slots) == 0
           and 2 * slots * step * c * itemsize <= _BLOCK_BYTES):
        slots *= 2
    return slots


def _matmul(spec, x, w):
    """The cache's dtype in, float32 out (``models/layers.matmul``)."""
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


def latent_attention_xla(latent_cache, layer: int, qc, latent_self, pos, *,
                         rkv: int, scale: float):
    """The pass in plain XLA: ``attend_live_blocks`` with each block taken
    out of the stack once for both products, operation for operation what
    ``mla_absorbed`` held until PR 68 (``tests/test_models_lowering.py`` pins
    the families' lowered text).  Same arguments and result as
    ``latent_attention``."""
    _, b, t, c = latent_cache.shape
    step = extent_step(t)
    s_self = _matmul("bhc,bc->bh", qc, latent_self) * scale

    def block(start):
        latents = jax.lax.dynamic_slice(
            latent_cache, (layer, 0, start, 0), (1, b, step, c))[0]
        scores = _matmul("bhc,btc->bht", qc, latents) * scale
        before = jnp.arange(step)[None, None] < (pos - start)[:, None, None]
        return jnp.where(before, scores, NEG_INF), lambda p: _matmul(
            "bht,btc->bhc", p.astype(qc.dtype), latents[..., :rkv])

    return attend_live_blocks(
        block, jnp.max(pos), t, qc.shape[:2] + (rkv,),
        [(s_self, latent_self[:, None, :rkv].astype(jnp.float32))])


def _kernel(meta_ref, q_ref, lat_ref, s_self_ref, v_self_ref, o_ref,
            m_ref, l_ref, acc_ref, *, rkv: int, scale: float):
    """One grid step: block ``j`` of ``slots`` slots.  meta_ref (SMEM):
    layer, live blocks, then ``pos`` of every slot.  q_ref ``[slots, H, C]``,
    lat_ref ``[slots, C, step]`` (positions on the lanes), s_self_ref
    ``[slots, H, 1]`` and v_self_ref ``[slots, 1, rkv]`` float32 (the current
    token's column), o_ref ``[slots, H, rkv]`` float32; the running maximum,
    sum and weighted latents in scratch, from block 0 to the last grid step,
    which merges the column and divides."""
    import jax.experimental.pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    slots, _, step = lat_ref.shape

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < meta_ref[1])
    def _():
        at = jax.lax.broadcasted_iota(jnp.int32, (1, step), 1) + j * step
        # unrolled: one slot's products run under the softmax of the one
        # before it (a ``fori_loop`` is a sixth slower on the v5e, PR 68)
        for b in range(slots):
            latents = lat_ref[b]
            scores = jnp.dot(q_ref[b], latents,
                             preferred_element_type=jnp.float32) * scale
            scores = jnp.where(at < meta_ref[2 + i * slots + b], scores,
                               NEG_INF)
            m = m_ref[b]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l_ref[b] = l_ref[b] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[b] = acc_ref[b] * alpha + jax.lax.dot_general(
                p.astype(latents.dtype), latents[:rkv],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[b] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        m = m_ref[...]
        m_new = jnp.maximum(m, s_self_ref[...])
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_self_ref[...] - m_new)
        o_ref[...] = ((acc_ref[...] * alpha + p * v_self_ref[...])
                      / (l_ref[...] * alpha + p))


@functools.partial(jax.jit, static_argnames=(
    "rkv", "scale", "slots", "interpret"))
def _call(meta, qc, leaf_t, s_self, v_self, *, rkv: int, scale: float,
          slots: int, interpret: bool):
    """The kernel over ``leaf_t [A, B, C, T]``.  Layer, bound and positions
    are ONE prefetched operand and this a jitted function of its own, so
    that a step's attentions are calls of one lowered kernel
    (``delta_update._call``'s lesson: PERF.md, PR 57)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    _, b, c, t = leaf_t.shape
    h = qc.shape[1]
    step = extent_step(t)
    block = slots * c * step * leaf_t.dtype.itemsize
    cells = b // slots

    def latents(i, j, meta):
        """A dead block is nobody's.  From the first dead step on, the map
        stands on the NEXT cell's block 0, so that its fetch runs under this
        cell's last live block and not under the last grid step, which has
        no products to hide it (a block index that does not change moves
        nothing); the last cell's stays on its last live block."""
        live = meta[1]
        dead, last = j >= live, i == cells - 1
        return (meta[0], jnp.where(dead & ~last, i + 1, i), 0,
                jnp.where(dead, jnp.where(last, live - 1, 0), j))

    return pl.pallas_call(
        functools.partial(_kernel, rkv=rkv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cells, t // step),
            in_specs=[
                pl.BlockSpec((slots, h, c), lambda i, j, meta: (i, 0, 0)),
                pl.BlockSpec((None, slots, c, step), latents),
                pl.BlockSpec((slots, h, 1), lambda i, j, meta: (i, 0, 0)),
                pl.BlockSpec((slots, 1, rkv), lambda i, j, meta: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((slots, h, rkv),
                                   lambda i, j, meta: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((slots, h, 1), jnp.float32),
                            pltpu.VMEM((slots, h, 1), jnp.float32),
                            pltpu.VMEM((slots, h, rkv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rkv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the latents' block twice, and as much again for the queries,
            # the sums and what a slot's products spill
            vmem_limit_bytes=max(32 << 20, 4 * block)),
        name="latent_attention",
        interpret=interpret,
    )(meta, qc, leaf_t, s_self, v_self)


def latent_attention(latent_cache, layer: int, qc, latent_self, pos, *,
                     rkv: int, scale: float, force_pallas: bool = False,
                     slots: int | None = None):
    """Softmax-weighted latents of one query token a slot: latent_cache ``[A,
    B, T, C]`` (the stacked leaf, of which attention ``layer``'s slice holds
    positions ``[0, pos)``; ``T`` of several extents), qc ``[B, H, C]`` (the
    absorbed query, in the cache's dtype), latent_self ``[B, C]`` (the
    current token's, one more column), pos ``[B]`` -> ``[B, H, rkv]`` float32:
    ``softmax(scale qc . latents)`` over the row's positions and itself,
    times the latents' first ``rkv`` channels.

    On a TPU, where a block is whole tiles (``C`` whole sublane tiles of the
    cache's dtype), this is the Pallas kernel or the compiler's error, never
    the XLA form in silence; off a TPU, or where it is not,
    ``latent_attention_xla``.  ``force_pallas`` runs the kernel off a TPU in
    interpret mode and ``slots`` overrides ``slots_per_cell`` (both the
    tests' and the sweep's)."""
    _, b, t, c = latent_cache.shape
    step = extent_step(t)
    itemsize = latent_cache.dtype.itemsize
    on_tpu = attention._on_tpu()
    tiles = (step < t and c % (SUBLANES * 4 // itemsize) == 0
             and rkv % LANES == 0 and qc.dtype == latent_cache.dtype)
    if force_pallas and not tiles:
        raise ValueError(
            f"latent_attention: blocks of [{c}, {step}] {latent_cache.dtype} "
            f"of a cache of {t} positions, {rkv} of the channels weighed, "
            "are not whole tiles of several extents")
    if not (tiles and (on_tpu or force_pallas)):
        return latent_attention_xla(latent_cache, layer, qc, latent_self,
                                    pos, rkv=rkv, scale=scale)
    slots = slots or slots_per_cell(b, c, step, itemsize)
    if b % slots:
        raise ValueError(f"latent_attention: {b} slots are not a multiple "
                         f"of the {slots} a grid cell carries")
    s_self = _matmul("bhc,bc->bh", qc, latent_self) * scale
    live = live_extent(jnp.max(pos), t) // step
    meta = jnp.concatenate([
        jnp.asarray([layer], jnp.int32), live.astype(jnp.int32)[None],
        pos.astype(jnp.int32)])
    return _call(meta, qc, jnp.swapaxes(latent_cache, 2, 3),
                 s_self[..., None],
                 latent_self[:, None, :rkv].astype(jnp.float32), rkv=rkv,
                 scale=scale, slots=slots, interpret=not on_tpu)
