"""The gated delta rule's one-token update: ONE pass over the state.

A head's state is a matrix ``S [dk, dv]`` float32, ``p`` heads side by side
on the lanes of a packed row ``[dk, p dv]`` (``models/delta_rule.pack_state``).
A decode step needs, a head, with ``q, k [dk]``, ``v [dv]`` and the scalars
``alpha`` (the decay) and ``beta``::

    delta = beta (v - alpha S^T k)
    o     = alpha S^T q + (k . q) delta        (= S_new^T q, without S_new)
    S_new = alpha S + k (x) delta

Where the decay is a VECTOR over the key channels (``alpha [dk]``: Kimi Delta
Attention; a scalar a head is Olmo-Hybrid's), it multiplies the state's ROWS,
``S' = Diag(alpha) S``::

    delta = beta (v - S'^T k)
    o     = S'^T q + (k . q) delta
    S_new = S' + k (x) delta

and is a COLUMN beside ``q`` and ``k`` where the scalar rode the lanes beside
``v``.  The gate's shape is static (``alpha``'s last axis: 1 or ``dk``) and
chooses between two branches of ONE kernel function, each of which lowers
as if the other were not there: a scalar gate's kernel is the kernel it was
(PERF.md, PR 67).

``S^T k`` must be finished before ``delta`` is known and ``S_new`` needs
``delta``: XLA runs a reduce fusion over ``S`` and then an elementwise one
that reads ``S`` again and writes it, three crossings of the state where two
(read once, write once) would do.  ``_kernel`` below keeps a block of rows in
the chip's fast memory between the reduction and the update, and writes the
new state over the block it read: the state operand is the WHOLE stacked leaf
``[layers, slots, H / p, dk, p dv]``, aliased to the result, and the block's
``index_map`` picks the layer (a slice handed in, or an ``.at[i].set`` of
what came out, would each be one crossing more).

Everything small comes in beside the state without ever taking its shape:
``q`` and ``k`` as COLUMNS (``[slots, dk, 2 H]``: ``dk`` down the sublanes as
the state's rows are, a head a lane; ``[slots, dk, 3 H]`` with a vector
gate's ``alpha`` after them), spread over their head's lanes inside
the kernel; ``v``, ``alpha``, ``beta`` and ``k . q`` on the lanes of their
head (``[slots, 4, H / p, p dv]``; three without the scalar ``alpha``),
spread down the sublanes.  All float32,
on the vector unit: no product is rounded to bfloat16.

``delta_update`` is the one way in.  On a TPU whose tiles the leaf fills
(``dk % 8 == 0``, ``p dv % 128 == 0``) it is the kernel or the compiler's
error; anywhere else the XLA formulation (``delta_update_xla``), which is also
the kernel's oracle in the tests (they run the kernel in interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention

LANES, SUBLANES = 128, 8

# Slots a grid step carries, and there is no knob for it.  A slot's rows
# (fifteen of ``[96, 384]`` float32 at the published widths) are 2.2 MB: in
# and out, each double-buffered, 8.8 MB of the v5e's 16 MiB of scoped fast
# memory.  Swept on the chip at 1 / 2 / 4 (PERF.md, PR 57): 0.4449 / 0.4443 /
# 0.4422 ms a layer of 64 slots, the memory's time, not a grid step's.
_SLOTS = 1


def over_lanes(a, p: int, dv: int):
    """A head's numbers beside its packed state: a ``[B, H, n]`` -> ``[B, H /
    p, n, p dv]``, head ``j`` of a row's ``p`` on its own ``dv`` lanes.  A
    select between broadcasts, so it fuses into whatever reads the state."""
    b, h, n = a.shape
    a = a.reshape(b, h // p, p, n)
    head = jnp.arange(p * dv) // dv
    out = a[:, :, 0, :, None]
    for j in range(1, p):
        out = jnp.where(head == j, a[:, :, j, :, None], out)
    return jnp.broadcast_to(out, (b, h // p, n, p * dv))


def vector_gate(alpha, dk: int) -> bool:
    """The decay is a vector over a head's ``dk`` key channels (``[B, H,
    dk]``) and not a scalar a head (``[B, H, 1]``)."""
    return alpha.shape[-1] == dk and dk > 1


def delta_update_xla(leaf, at: int, q, k, v, alpha, beta):
    """The update in plain XLA: a reduce pass over layer ``at``'s state for
    ``S^T k`` and ``S^T q`` and an elementwise one that writes ``alpha S + k
    (x) delta`` into the leaf.  Same arguments and results as
    ``delta_update``."""
    s = leaf[at].astype(jnp.float32)
    b, rows, dk, lanes = s.shape
    h, dv = v.shape[1:]
    p = h // rows
    k_lanes = over_lanes(k, p, dv)
    if vector_gate(alpha, dk):  # the decay multiplies the state's rows
        s = over_lanes(alpha, p, dv) * s  # S' = Diag(alpha) S
        sk = (s * k_lanes).sum(2).reshape(b, h, dv)
        sq = (s * over_lanes(q, p, dv)).sum(2).reshape(b, h, dv)
        delta = beta * (v - sk)
        o = sq + (k * q).sum(-1, keepdims=True) * delta
        new = s + k_lanes * delta.reshape(b, rows, 1, lanes)
        return o, leaf.at[at].set(new.astype(leaf.dtype))
    # S^T k and S^T q of the state as it came, a head: [B, H, dv]
    sk = (s * k_lanes).sum(2).reshape(b, h, dv)
    sq = (s * over_lanes(q, p, dv)).sum(2).reshape(b, h, dv)
    delta = beta * (v - alpha * sk)
    o = alpha * sq + (k * q).sum(-1, keepdims=True) * delta
    new = (over_lanes(alpha, p, dv) * s
           + k_lanes * delta.reshape(b, rows, 1, lanes))
    return o, leaf.at[at].set(new.astype(leaf.dtype))


def _kernel(at_ref, s_ref, qk_ref, row_ref, s_out, o_ref, *, p: int,
            gate_columns: bool = False):
    """One grid step: ``slots`` slots of one layer (``at_ref``: which, read by
    the blocks' index maps alone).  s_ref / s_out ``[slots, R, dk, W]`` (the
    same bytes), qk_ref ``[slots, dk, 2 H]`` (q's heads, then k's), row_ref
    ``[slots, 4, R, W]`` (v, alpha, beta, k . q), o_ref ``[slots, R, W]``.  A
    row ``[dk, W]`` (36 registers at the published widths) is read once and
    held from the two reductions through the update.  ``gate_columns``: the
    decay is a vector a head, the third group of ``qk_ref``'s columns
    (``[slots, dk, 3 H]``), and ``row_ref`` holds v, beta and k . q alone."""
    del at_ref
    slots, rows, dk, width = s_ref.shape
    dv = width // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)

    def one_slot(b, carry):
        qk = qk_ref[b]

        def over_lanes(first: int):
            """Columns ``[first, first + p)`` of ``qk``, a row's heads, each
            spread over its own ``dv`` lanes: ``[dk, W]``."""
            out = jnp.broadcast_to(qk[:, first:first + 1], (dk, width))
            for j in range(1, p):
                out = jnp.where(lane >= j * dv, jnp.broadcast_to(
                    qk[:, first + j:first + j + 1], (dk, width)), out)
            return out

        for r in range(rows):  # static: a head's column is a static lane
            s = s_ref[b, r]
            k = over_lanes((rows + r) * p)
            if gate_columns:  # S' = Diag(alpha) S, then the rule on S'
                s = over_lanes((2 * rows + r) * p) * s
                sk = (s * k).sum(0, keepdims=True)
                sq = (s * over_lanes(r * p)).sum(0, keepdims=True)
                v, beta, kq = (row_ref[b, n, r:r + 1] for n in range(3))
                delta = beta * (v - sk)
                o_ref[b, r:r + 1] = sq + kq * delta
                s_out[b, r] = s + k * delta
                continue
            sk = (s * k).sum(0, keepdims=True)
            sq = (s * over_lanes(r * p)).sum(0, keepdims=True)
            v, alpha, beta, kq = (row_ref[b, n, r:r + 1] for n in range(4))
            delta = beta * (v - alpha * sk)
            o_ref[b, r:r + 1] = alpha * sq + kq * delta
            s_out[b, r] = alpha * s + k * delta
        return carry

    jax.lax.fori_loop(0, slots, one_slot, None)


@functools.partial(jax.jit, static_argnames=(
    "p", "slots", "interpret", "gate_columns"))
def _call(at, leaf, qk, row, *, p: int, slots: int, interpret: bool,
          gate_columns: bool = False):
    """The kernel over layer ``at [1]`` (int32) of ``leaf``.  The layer is an
    OPERAND, prefetched for the index maps, and this a jitted function of its
    own, so that a decode step's nine linear layers are nine calls of ONE
    lowered kernel: with the layer a constant of each, tracing and lowering
    the unrolled rows nine times added ~2 s to every start of a replica
    (PERF.md, PR 57)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    _, b, rows, dk, width = leaf.shape
    state = pl.BlockSpec((None, slots, rows, dk, width),
                         lambda s, at: (at[0], s, 0, 0, 0))
    block = slots * rows * dk * width * 4
    return pl.pallas_call(
        functools.partial(_kernel, p=p, gate_columns=gate_columns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // slots,),
            in_specs=[
                state,
                pl.BlockSpec((slots,) + qk.shape[1:],
                             lambda s, at: (s, 0, 0)),
                pl.BlockSpec((slots,) + row.shape[1:],
                             lambda s, at: (s, 0, 0, 0)),
            ],
            out_specs=[state, pl.BlockSpec((slots, rows, width),
                                           lambda s, at: (s, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((b, rows, width), jnp.float32)],
        input_output_aliases={1: 0},  # the leaf, after the prefetched layer
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state's block in and out, each double-buffered, and as
            # much again for the small operands and what the rows spill
            vmem_limit_bytes=max(16 << 20, 6 * block)),
        name="delta_update",
        interpret=interpret,
    )(at, leaf, qk, row)


def delta_update(leaf, at: int, q, k, v, alpha, beta, *,
                 force_pallas: bool = False, slots: int | None = None):
    """One token a slot through the delta rule of layer ``at`` (static) of
    the stacked state ``leaf [layers, B, H / p, dk, p dv]`` float32.  q, k
    ``[B, H, dk]``, v ``[B, H, dv]``, beta ``[B, H, 1]``, alpha ``[B, H, 1]`` (a
    scalar gate) or ``[B, H, dk]`` (a vector gate, ``dk > 1``), float32 ->
    (``o [B, H, dv]``, the leaf with layer ``at`` updated: the same buffer
    where the caller donated it; the other layers are not touched).

    On a TPU, where the leaf fills whole tiles, this is the Pallas kernel or
    the compiler's error, never the XLA formulation in silence; off a TPU,
    or where a row is not whole tiles, ``delta_update_xla``.
    ``force_pallas`` runs the kernel off a TPU in interpret mode and
    ``slots`` overrides ``_SLOTS`` (both the tests' and the sweep's)."""
    _, b, rows, dk, width = leaf.shape
    h, dv = v.shape[1:]
    p = h // rows
    on_tpu = attention._on_tpu()
    tiles = (dk % SUBLANES == 0 and width % LANES == 0
             and leaf.dtype == jnp.float32)
    if force_pallas and not tiles:
        raise ValueError(
            f"delta_update: a row [{dk}, {width}] {leaf.dtype} is not whole "
            f"({SUBLANES}, {LANES}) float32 tiles")
    if not (tiles and (on_tpu or force_pallas)):
        return delta_update_xla(leaf, at, q, k, v, alpha, beta)
    slots = slots or _SLOTS
    if b % slots:
        raise ValueError(f"delta_update: {b} slots are not a multiple of "
                         f"the {slots} a grid step carries")
    # columns: dk down the sublanes like the state's rows, a head a lane
    columns = vector_gate(alpha, dk)
    qk = jnp.concatenate([q, k] + [alpha] * columns, axis=1).swapaxes(1, 2)
    kq = (k * q).sum(-1, keepdims=True)  # qk: [B, dk, 2 H] or [B, dk, 3 H]
    row = jnp.stack([  # [B, 4 or 3, R, W]: each on the lanes of its head
        jnp.broadcast_to(a, (b, h, dv)).reshape(b, rows, width)
        for a in ((v, beta, kq) if columns else (v, alpha, beta, kq))],
        axis=1)
    new, o = _call(jnp.asarray([at], jnp.int32), leaf, qk, row, p=p,
                   slots=slots, interpret=not on_tpu, gate_columns=columns)
    return o.reshape(b, h, dv), new
