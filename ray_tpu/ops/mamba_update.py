"""Mamba-2's one-token update: ONE pass over the state.

A head's state is a matrix ``S [P, N]`` float32 (``P`` the head's width down
the sublanes, ``N`` the state's size on the lanes).  A decode step needs, a
head, with ``x [P]``, the scalars ``dt`` and ``keep = exp(dt a)`` and its
GROUP's ``b, c [N]``::

    S_new = keep S + (dt x) (x) b
    y     = S_new c

``y`` reads ``S_new``: XLA runs an elementwise fusion that reads ``S`` and
writes ``S_new`` into the leaf and a reduce fusion that reads it again, three
crossings of the state where two (read once, write once) would do.
``_kernel`` below keeps a head's eight registers from the update through the
read-out and writes them over the block it read: the state operand is the
WHOLE stacked leaf ``[layers, slots, H, P, N]``, aliased to the result, and
the block's ``index_map`` picks the layer (a slice handed in, or an
``.at[i].set`` of what came out, would each be one crossing more).

Everything small comes in beside the state without ever taking its shape:
``keep`` a scalar a head (``[slots, 1, H]`` in the scalar memory), ``dt x``
as it lies (``[slots, H, P]``, a head a sublane), ``b`` and ``c`` a GROUP
(``[slots, 2, G, N]``: repeated a head they would be ``H / G`` times the
bytes), spread down the sublanes, which costs nothing.  ``y`` leaves the way
``dt x`` came.  Every product and every sum of the recurrence is float32 on
the vector unit, none rounded to bfloat16.

What sets the kernel's shape is the cross-lane unit, not the memory (one
layer timed alone on a v5e, 64 slots; PERF.md, PR 61).  ``dt x`` lies down a
head's rows and must be spread over the 128 lanes before it meets ``b``;
``S_new c`` is summed across them.  Either costs the cross-lane unit ~9
cycles a register, and a head's eight registers have ~107 cycles of memory
time: ONE of the two hides under the block's copy in and out, both do not
(0.604 ms a layer at Granite's widths, where XLA's three crossings take
0.636 and the blocks merely copied 0.456).  So the sum stays the compiler's
``sum(-1)``, and the spread goes through the matrix unit, which has nothing
else to do: eight heads' ``dt x`` are cut into three bfloat16 pieces that
add up to the float32 EXACTLY (8 + 8 + 8 bits), turned once so that ``P``
runs down the sublanes as a head's rows do, and a head's three pieces are
summed against ones in one bfloat16 pass with a float32 accumulator: every
lane gets back every bit (``tests/test_mamba_update.py``; on the chip the
new state is bit-identical to XLA's).  0.457 ms a layer, the copy's pace.
What did not do: both through the cross-lane unit (above); the sum through
the matrix unit at full float32 precision, six passes (0.508 with the
spread on the cross-lane unit, 0.998 with both on the matrix unit, which
then sets the pace); the lanes folded by rotations (2.0 ms: a rotation costs
what a sum does); ``keep`` as a column like ``dt x`` (eight more spreads a
head).

``mamba_update`` is the one way in.  On a TPU whose tiles the leaf fills (``P
% 8 == 0``, ``N % 128 == 0``, heads a multiple of eight) it is the kernel or
the compiler's error; anywhere else the XLA formulation
(``mamba_update_xla``), which is also the kernel's oracle in the tests (they
run the kernel in interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention
from .delta_update import LANES, SUBLANES


def mamba_update_xla(leaf, at: int, x, dt, keep, b, c):
    """The update in plain XLA: an elementwise pass that writes ``keep S +
    (dt x) (x) b`` into layer ``at`` of the leaf and a reduce pass over it
    for ``y``.  Same arguments and results as ``mamba_update``."""
    r = x.shape[1] // b.shape[1]
    b, c = (jnp.repeat(v, r, axis=1)[:, :, None] for v in (b, c))  # [B,H,1,N]
    new = (keep[..., None, None] * leaf[at].astype(jnp.float32)
           + (dt[..., None] * x)[..., None] * b)
    return (new * c).sum(-1), leaf.at[at].set(new.astype(leaf.dtype))


def _kernel(at_ref, keep_ref, s_ref, dtx_ref, bc_ref, s_out, y_ref, *,
            per_group: int):
    """One grid step: ``hb`` heads of ``sb`` slots of one layer (``at_ref``:
    which, read by the blocks' index maps alone).  s_ref / s_out ``[sb, hb,
    P, N]`` (the same bytes), keep_ref ``[sb, 1, H]`` scalars (every head of
    the slot), dtx_ref ``[sb, hb, P]``, bc_ref ``[sb, 2, G, N]`` (b, c: every
    group), y_ref ``[sb, hb, P]``.  A head ``[P, N]`` (8 registers at the
    published widths) is read once and held from the update through the
    read-out."""
    import jax.experimental.pallas as pl

    del at_ref
    sb, hb, p, n = s_ref.shape
    turns = hb // SUBLANES
    first = pl.program_id(1) * hb
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, LANES), 1)
    piece_of = lane % SUBLANES  # the head whose piece a lane of ``cols`` holds
    ones = jnp.ones((LANES, n), jnp.bfloat16)
    rest = jnp.zeros((LANES - 3 * SUBLANES, p), jnp.float32)

    def eight_heads(t, carry):
        slot, h0 = t // turns, pl.multiple_of(t % turns * SUBLANES, SUBLANES)
        # eight heads' dt x [8, P] as three bfloat16 pieces that add up to
        # the float32, turned so that P runs down the sublanes as a head's
        # rows do: head j's pieces on lanes j, 8 + j and 16 + j
        dtx = dtx_ref[slot, pl.ds(h0, SUBLANES), :]
        high = dtx.astype(jnp.bfloat16).astype(jnp.float32)
        mid = (dtx - high).astype(jnp.bfloat16).astype(jnp.float32)
        cols = jnp.concatenate([high, mid, dtx - high - mid, rest]).T
        y = jnp.zeros((p, LANES), jnp.float32)
        for j in range(SUBLANES):  # static: a head's column is a static lane
            head = first + h0 + j  # of the slot's
            b, c = (bc_ref[slot, k, pl.ds(head // per_group, 1), :]
                    for k in range(2))
            # head j's column over all the lanes: its three pieces summed
            # against ones by the matrix unit, which adds them exactly
            spread = jnp.dot(
                jnp.where(piece_of == j, cols, 0.0).astype(
                    jnp.bfloat16), ones, preferred_element_type=jnp.float32)
            new = keep_ref[slot, 0, head] * s_ref[slot, h0 + j] + spread * b
            s_out[slot, h0 + j] = new
            y = jnp.where(lane == j, (new * c).sum(-1, keepdims=True), y)
        y_ref[slot, pl.ds(h0, SUBLANES), :] = y.T[:SUBLANES]
        return carry

    jax.lax.fori_loop(0, sb * turns, eight_heads, None)


# The bytes of state a grid step carries, at most, and there is no knob for
# it: whole slots while they fit, else the largest run of a slot's heads that
# does (eight at least).  In and out, each double-buffered, a block is held
# four times in the fast memory the call asks for.  Swept on the chip at 64
# slots (PERF.md, PR 61), ms a layer at 0.5 / 1 / 2 / 4 / 8 MB a block:
# Granite's 64 heads 0.4766 / 0.4579 / 0.4606 / 0.4596 / 0.4645, Nemotron's
# 128 heads 0.9074 / 0.8791 / 0.8740 / 0.8785 / 0.8794 (0.8900 at 16): the
# memory's time (the same blocks copied and nothing else: 0.4557, 0.8716)
# from 1 MB up, and several slots a block buy nothing.
_BLOCK_BYTES = 2 << 20


def _block(slots: int, heads: int, head_bytes: int):
    """(slots, heads) of a grid step's block: the rule above."""
    fit = max(SUBLANES, _BLOCK_BYTES // head_bytes)
    if fit < heads:
        return 1, max(hb for hb in range(SUBLANES, fit + 1, SUBLANES)
                      if heads % hb == 0)
    return max(sb for sb in range(1, fit // heads + 1)
               if slots % sb == 0), heads


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _call(at, leaf, keep, dtx, bc, *, block, interpret: bool):
    """The kernel over layer ``at [1]`` (int32) of ``leaf``.  The layer is an
    OPERAND, prefetched for the index maps, and this a jitted function of its
    own, so that a decode step's Mamba-2 layers (thirty-six of Granite's
    forty) are calls of ONE lowered kernel (``delta_update._call``'s lesson:
    PERF.md, PR 57)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    _, slots, heads, p, n = leaf.shape
    sb, hb = block
    groups = bc.shape[2]
    state = pl.BlockSpec((None, sb, hb, p, n),
                         lambda s, h, at: (at[0], s, h, 0, 0))
    small = pl.BlockSpec((sb, hb, p), lambda s, h, at: (s, h, 0))
    return pl.pallas_call(
        functools.partial(_kernel, per_group=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // sb, heads // hb),
            in_specs=[
                pl.BlockSpec((sb, 1, heads), lambda s, h, at: (s, 0, 0),
                             memory_space=pltpu.SMEM),
                state, small,
                pl.BlockSpec((sb, 2, groups, n),
                             lambda s, h, at: (s, 0, 0, 0)),
            ],
            out_specs=[state, small]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((slots, heads, p), jnp.float32)],
        input_output_aliases={2: 0},  # the leaf, after the layer and keep
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state's block in and out, each double-buffered, and as
            # much again for the small operands and what a head spills
            vmem_limit_bytes=max(16 << 20, 6 * sb * hb * p * n * 4)),
        name="mamba_update",
        interpret=interpret,
    )(at, keep, leaf, dtx, bc)


def mamba_update(leaf, at: int, x, dt, keep, b, c, *,
                 force_pallas: bool = False, block=None):
    """One token a slot through the recurrence of layer ``at`` (static) of
    the stacked state ``leaf [layers, B, H, P, N]`` float32.  x ``[B, H,
    P]``, dt, keep ``[B, H]``, b, c ``[B, G, N]``, float32 -> (``y = S_new c
    [B, H, P]``, the leaf with layer ``at`` updated: the same buffer where
    the caller donated it; the other layers are not touched).

    On a TPU, where a head fills whole tiles and the heads a sublane tile,
    this is the Pallas kernel or the compiler's error, never the XLA
    formulation in silence; off a TPU, or where they do not,
    ``mamba_update_xla``.  ``force_pallas`` runs the kernel off a TPU in
    interpret mode and ``block`` (slots, heads) overrides the rule of
    ``_block`` (both the tests' and the sweep's)."""
    _, slots, heads, p, n = leaf.shape
    on_tpu = attention._on_tpu()
    tiles = (p % SUBLANES == 0 and n % LANES == 0 and heads % SUBLANES == 0
             and leaf.dtype == jnp.float32)
    if force_pallas and not tiles:
        raise ValueError(
            f"mamba_update: {heads} heads of [{p}, {n}] {leaf.dtype} are not "
            f"whole ({SUBLANES}, {LANES}) float32 tiles, eight heads a turn")
    if not (tiles and (on_tpu or force_pallas)):
        return mamba_update_xla(leaf, at, x, dt, keep, b, c)
    sb, hb = block or _block(slots, heads, p * n * 4)
    if slots % sb or heads % hb or hb % SUBLANES:
        raise ValueError(
            f"mamba_update: {slots} slots of {heads} heads are not a whole "
            f"number of blocks of {sb} slots of {hb} heads (eight a turn)")
    new, y = _call(jnp.asarray([at], jnp.int32), leaf, keep[:, None],
                   dt[..., None] * x, jnp.stack([b, c], axis=1),
                   block=(sb, hb), interpret=not on_tpu)
    return y, new
