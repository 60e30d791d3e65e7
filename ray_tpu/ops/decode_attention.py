"""Single-token decode attention over a KV cache — the serving hot op.

During generation each sequence attends one query token against its own
``[0, pos]`` cache prefix.  This is HBM-bandwidth-bound (the live cache
prefix streams through once per token), so the job is to stream the live
prefix and as little else as can be.  The TPU analog of the paged/decode
attention kernels the reference gets from vLLM's CUDA side (SURVEY.md §2.3:
the reference has no kernels of its own).

One path, in plain XLA: ``decode_attention``, which the GPT-2, Llama,
Nemotron-H and MiMo-V2 decode steps call, and ``attend_live_blocks`` under
it, which the latent attention of LongCat, Mistral-4 and Kimi-Linear calls
with a block of its own off a TPU (``ops/latent_attention.py``
``latent_attention_xla``; on a TPU the same pass over the same blocks is that
file's Pallas kernel, which fetches a block while the one before it is
scored).

What a step reads.  A full-extent cache is read up to the BATCH's longest
live context, in blocks of ``extent_step(T)`` = 512 positions: one loop with
a dynamic trip count a layer (``attend_live_blocks``), bounded on the device
from ``pos`` itself, the blocks taken out of the stacked cache where they
lie.  A ring (``window``) and a cache of one block are read whole, in one
softmax.  The live prefix of each SLOT alone (in the traffic of the
benchmark's cells a fifth of the cache, where the batch's longest context
makes a step read 54-88 %: ``cache_read_pct.serve``) is still nobody's: it
takes a ragged copy or a paged cache (ROADMAP D3, S5 (a)).

The *current* token's k/v ride in as separate ``[B, Hkv, D]`` operands
(``k_self`` / ``v_self``) and are one more column of the softmax — this is
what lets a decode step defer its cache writes to one
``write_token_to_cache`` a cache array at its end, instead of two scatters
per layer.  Grouped-query attention is native: each kv head carries its
``G = H // Hkv`` query rows as one ``[G, T]`` score tile.

Layouts (head-major, nothing transposes on the hot path):
  q        [B, H, D];  k/v cache [L, B, Hkv, T, D];  k/v self [B, Hkv, D]
  pos      [B]  — index of the current token (attends [0, pos-1] + self)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_held(newest, window: int):
    """The position each slot of a ring of ``window`` slots holds once
    positions ``0 .. newest`` are in: slot ``r`` the newest ``p <= newest``
    with ``p = r mod window``; negative where no position has reached the
    slot yet (it holds whatever it held).  newest ``[..., 1]`` -> ``[...,
    window]``.  THE ring's addressing rule: prefill fills by it, decode masks
    by it and writes position ``p`` at ``p mod window``."""
    return newest - jnp.mod(newest - jnp.arange(window), window)


def ring_positions(pos, newest, window: int):
    """Which slots of a ring the token at ``pos`` attends: those whose
    position ``p`` (``ring_held``; ``newest`` is ``pos - 1`` while the current
    token rides beside the cache, ``pos`` once it is written) is ``>= 0`` and
    inside the window, ``pos - p < window``.  pos, newest ``[B, 1, 1, 1]`` ->
    mask ``[B, 1, 1, window]``."""
    held = ring_held(newest, window)
    return (held >= 0) & (pos - held < window)


EXTENT_STEP = 512


def extent_step(t: int) -> int:
    """The step by which a decode's read of ``t`` cache positions is bounded:
    blocks of 512 positions (four of 2048, eight of 4096).  Whole memory
    tiles whatever the leaf's layout (every ``tile_positions`` divides 128),
    and long enough that a block's fixed cost in the loop over blocks, 4 us
    on the v5e beside 23 us a 256 positions of a Mistral layer's keys and
    values, is a tenth of its read (PERF.md, PR 46: at 256 the finer bound
    gave back more than it took).  A ``t`` of one block or of no whole
    number of them has ONE extent, itself."""
    return EXTENT_STEP if t > EXTENT_STEP and t % EXTENT_STEP == 0 else t


def live_extent(longest, t: int):
    """How many of ``t`` cache positions a decode step reads when the
    longest of its rows needs positions ``[0, longest)``: the smallest
    multiple of ``extent_step(t)`` that holds them, and at least one step.
    Integer arithmetic alone, so the host (``llm/engine.py``'s dispatch
    span) and the program (a traced ``longest``) say the same number."""
    step = extent_step(t)
    steps = (longest + step - 1) // step
    return (steps + (steps == 0)) * step


def attend_blocks(block, n_blocks, step: int, shape, columns=()):
    """Softmax-weighted values over the first ``n_blocks`` blocks of ``step``
    positions and a few columns beside them -> ``shape`` ``[..., Dv]``
    float32; ``n_blocks`` may be traced (the loop's trip count).
    ``block(start) -> (scores, weigh)``: the masked float32 scores ``[...,
    step]`` of positions ``[start, start + step)`` and ``weigh(p) -> [...,
    Dv]`` float32, their values under weights ``p``.  ``columns``: ``(score
    [...], value [..., Dv] or None)``, one more logit each (the current
    token's; a sink, whose weight counts in the sum and nothing else).

    An online softmax: a running ``(max, sum, weighted values)`` that every
    block and column updates alike.  A block that lies wholly beyond a row's
    own context changes nothing of that row (its weights are exact zeros and
    its maximum no higher), and every block runs the one compiled body, so a
    row's result is the same BITS whatever made the trip count longer.  A
    row must see a position somewhere (a block or a column)."""

    def update(carry, scores, weigh):
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + weigh(p))

    lead = tuple(shape[:-1]) + (1,)
    carry = jax.lax.fori_loop(
        0, n_blocks,
        lambda j, carry: update(carry, *block(j * step)),
        (jnp.full(lead, NEG_INF, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(shape, jnp.float32)))
    for score, value in columns:
        carry = update(carry, score[..., None],
                       lambda p: 0.0 if value is None else p * value)
    _, l, acc = carry
    return acc / l


def attend_live_blocks(block, longest, t: int, shape, columns=()):
    """``attend_blocks`` over the LIVE blocks of ``t`` cache positions: blocks
    of ``extent_step(t)`` positions, as many as this step's
    ``live_extent(longest, t)`` holds, so a block beyond every row's context
    is never read.  A decode step's read of a full-extent cache; a row's
    result is the same bits whatever its neighbours' contexts made the trip
    count."""
    step = extent_step(t)
    return attend_blocks(block, live_extent(longest, t) // step, step, shape,
                         columns)


def attend_listed_blocks(q, k_cache, v_cache, ids, pos, layer: int, *,
                         block_size: int, turn: int, k_self, v_self):
    """``attend_live_blocks``' sibling for a row that reads a LIST of blocks
    and not the contiguous ``[0, live_extent)``: q ``[B, H, D]``, caches ``[L,
    B, Hkv, T, D]``, ids ``[B, Hkv, n]`` int32: the blocks of ``block_size``
    positions (block ``b`` = positions ``[b block_size, (b + 1)
    block_size)``) each (row, key-value head) reads, the listed ones FIRST
    and -1 after them; ``n`` a multiple of ``turn``.  Of a listed block the
    positions before ``pos [B]`` count, and the current token's ``k_self`` /
    ``v_self [B, Hkv, D]`` is one more column (the deferred write) ->
    ``[B, H, Dv]`` in the values' dtype.

    ``turn`` entries of every row's list are gathered at a time out of the
    stacked cache where it lies (the layer is part of the gather's index:
    no slice of the leaf is taken first) and go through one update of the
    online softmax; as many turns run as the longest list needs, so a batch
    whose rows all list ``turn`` blocks reads ``turn * block_size``
    positions a row and head whatever their contexts are.  An entry beyond
    a row's own list is masked: exact zeros, the same bits whatever its
    neighbours made the trip count."""
    n_layers, b, hkv, t, d = k_cache.shape
    dv = v_cache.shape[-1]
    h = q.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = d ** -0.5
    if t % block_size or ids.shape[-1] % turn:
        raise ValueError(
            f"a cache of {t} positions in blocks of {block_size}, a list of "
            f"{ids.shape[-1]} in turns of {turn}: neither may leave a rest")
    blocks = t // block_size
    # [L, B, Hkv, T / block, block, D]: the same bytes where a block is
    # whole memory tiles
    kb_all = k_cache.reshape(n_layers, b, hkv, blocks, block_size, d)
    vb_all = v_cache.reshape(n_layers, b, hkv, blocks, block_size, dv)
    row = jnp.arange(b)[:, None, None]
    head = jnp.arange(hkv)[None, :, None]
    limit = pos[:, None, None, None]

    def block(start):  # ``start`` counts positions of the LIST
        listed = jax.lax.dynamic_slice_in_dim(
            ids, start // block_size, turn, axis=2)  # [B, Hkv, turn]
        at = jnp.clip(listed, 0, blocks - 1)
        kb = kb_all[layer, row, head, at]  # [B, Hkv, turn, block, D]
        vb = vb_all[layer, row, head, at]
        scores = jnp.einsum(
            "bkgd,bknsd->bkgns", qg, kb).astype(jnp.float32) * scale
        where = at[..., None] * block_size + jnp.arange(block_size)
        seen = ((listed >= 0)[..., None] & (where < limit))[:, :, None]
        scores = jnp.where(seen, scores, NEG_INF).reshape(b, hkv, g, -1)
        return scores, lambda p: jnp.einsum(
            "bkgns,bknsd->bkgd",
            p.reshape(b, hkv, g, turn, block_size).astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)

    columns = [(jnp.einsum("bkgd,bkd->bkg", qg, k_self).astype(jnp.float32)
                * scale, v_self[:, :, None, :].astype(jnp.float32))]
    turns = (jnp.max(jnp.sum(ids >= 0, axis=-1)) + turn - 1) // turn
    out = attend_blocks(block, turns, turn * block_size, (b, hkv, g, dv),
                        columns)
    return out.astype(v_cache.dtype).reshape(b, h, dv)


@functools.partial(jax.jit, static_argnames=("layer", "window"))
def decode_attention(q, k_cache, v_cache, pos, layer: int = 0, *,
                     k_self=None, v_self=None, window=None, sink=None):
    """q [B,H,D]; k cache [L,B,Hkv,T,D], v cache [L,B,Hkv,T,Dv] (the values'
    width is their own); pos [B] -> [B,H,Dv].  ``layer`` is static: that
    slice of the stacked cache is read where it lies.

    Without self k/v: attends [0, pos] of the cache (current token assumed
    already written).  With ``k_self`` / ``v_self`` [B,Hkv,D]: attends
    [0, pos-1] plus the explicit current token (the deferred-scatter form
    the decode steps use).

    ``window`` (static): the cache's position axis is a RING of that extent
    (``T == window``): position ``p`` lives at ``p mod window`` and the token
    attends the last ``window`` positions, itself included
    (``ring_positions``).  ``sink`` ``[H]``: one more logit a head in the
    softmax, whose probability is dropped (the row then sums to less than
    one).  Both default to absent, and the program without them is what it
    was.

    A cache of several extents (``extent_step``) that is no ring is read in
    blocks, up to the BATCH's longest live context and not to ``T``
    (``attend_live_blocks``).  Nothing in a ring is dead, and a short cache
    has one extent: both are scored whole, in one softmax, as ever."""
    k = k_cache[layer]  # [B, Hkv, T, D]
    v = v_cache[layer]
    b, hkv, t, d = k.shape
    dv = v.shape[-1]
    h = q.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = d ** -0.5
    limit = pos[:, None, None, None]
    if window is not None and t != window:
        raise ValueError(f"a ring of {window} positions in a cache of {t}")
    step = extent_step(t)
    if window is None and step < t:
        def block(start):
            at = (layer, 0, 0, start, 0)
            kb = jax.lax.dynamic_slice(k_cache, at, (1, b, hkv, step, d))[0]
            vb = jax.lax.dynamic_slice(v_cache, at, (1, b, hkv, step, dv))[0]
            scores = jnp.einsum(
                "bkgd,bktd->bkgt", qg, kb).astype(jnp.float32) * scale
            idx = jnp.arange(step)[None, None, None, :]
            mask = (idx <= limit - start if k_self is None
                    else idx < limit - start)
            return jnp.where(mask, scores, NEG_INF), lambda p: jnp.einsum(
                "bkgt,bktd->bkgd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)

        columns = []
        if k_self is not None:
            columns.append((
                jnp.einsum("bkgd,bkd->bkg", qg, k_self).astype(jnp.float32)
                * scale, v_self[:, :, None, :].astype(jnp.float32)))
        if sink is not None:
            columns.append((jnp.broadcast_to(sink.astype(jnp.float32).reshape(
                1, hkv, g), (b, hkv, g)), None))
        # a row reads [0, pos), and [0, pos] where its own token lies there
        out = attend_live_blocks(block, jnp.max(pos) + (k_self is None), t,
                                 (b, hkv, g, dv), columns)
        return out.astype(v.dtype).reshape(b, h, dv)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k).astype(jnp.float32) * scale
    if window is not None:
        mask = ring_positions(
            limit, limit if k_self is None else limit - 1, window)
    else:
        idx = jnp.arange(t)[None, None, None, :]
        # with self k/v: strictly before the current token
        mask = idx <= limit if k_self is None else idx < limit
    scores = jnp.where(mask, scores, NEG_INF)
    if k_self is None and sink is None:  # no column beside the cache's
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v.dtype), v)
        return out.reshape(b, h, dv)
    columns = [scores]
    if k_self is not None:
        columns.append((
            jnp.einsum("bkgd,bkd->bkg", qg, k_self).astype(jnp.float32) * scale
        )[..., None])
    if sink is not None:
        columns.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, g, 1), (b, hkv, g, 1)))
    probs = jax.nn.softmax(jnp.concatenate(columns, axis=-1), axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs[..., :t].astype(v.dtype), v)
    if k_self is not None:
        out = out + (probs[..., t:t + 1].astype(v.dtype)
                     * v_self[:, :, None, :])
    return out.reshape(b, h, dv)


def tile_positions(shape, dtype, axis: int) -> int:
    """How many positions along ``axis`` one memory tile of such an array
    holds on the TPU.  A tile is 128 lanes of the minor axis by the
    sublanes of the next one: 8 rows of 32 bits, so 16 of bf16.  The TPU
    keeps an array's last axis minor unless that would pad it: where the
    last axis is no multiple of 128 it swaps the last two
    (``tests/test_tpu_compile.py`` reads that from the compiler for
    twenty-one shapes).  So positions lie on the sublanes of Mistral's ``[..,
    T, 128]`` and on the lanes of LongCat's ``[.., T, 576]``, GPT-2's ``[..,
    T, 64]`` and MiMo's keys ``[.., T, 192]``, whose ring of 128 positions is
    then ONE tile."""
    last_is_minor = shape[-1] % 128 == 0
    on_lanes = axis == len(shape) - (1 if last_is_minor else 2)
    return 128 if on_lanes else 32 // jnp.dtype(dtype).itemsize


def write_token_to_cache(cache_arr, new, pos, axis: int):
    """Write one token a slot into a stacked cache, in place.

    ``cache_arr`` has its slots on axis 1 and its positions on ``axis``
    (3 in ``[L,B,Hkv,T,D]``, 2 in the latent ``[A,B,T,C]``); ``new`` is
    ``cache_arr`` without the position axis; ``pos`` [B].  Slot ``b``'s row
    at ``pos[b]`` becomes ``new[:, b]`` and nothing else changes (a
    ``pos[b]`` outside ``[0, T)`` writes nothing).

    A loop over the slots, each turn a read-modify-write of the aligned
    memory tile that holds ``pos[b]`` (``tile_positions``).  The start is
    ``pos & -rows`` under ``allow_negative_indices=False`` so that the
    compiler can see its low bits are zero: it then fuses read, select and
    write into one update of the donated cache where it lies (0.1-0.2 ms a
    step at the serving cells' sizes, v5e).  Every shorter way to say this
    costs more there (PERF.md, PR 34): a ``dynamic_update_slice`` vmapped
    over ``pos`` is one ``scatter``, which the compiler brackets with a
    relayout of the operand in and out (four 1 GB copies a Mistral step);
    a ``where`` over the position axis reads and writes everything; a loop
    of one-row updates, a tile of the wrong size or one whose alignment the
    compiler cannot see is updated through masked partial stores (0.8-1.2
    ms a LongCat step, where a row is one lane of 288 tiles).  The loop's
    operations carry the scope ``cache_write`` inside the caller's
    (``<family>.attn/cache_write/while/...``): a step's write is told from
    its attention's loops in a trace's ``op_name``s."""
    with jax.named_scope("cache_write"):
        return _write_token_to_cache(cache_arr, new, pos, axis)


def _write_token_to_cache(cache_arr, new, pos, axis: int):
    t = cache_arr.shape[axis]
    rows = min(t, tile_positions(cache_arr.shape, cache_arr.dtype, axis))
    new = jnp.expand_dims(new, axis)
    sizes = list(cache_arr.shape)
    sizes[1], sizes[axis] = 1, rows
    offsets = jnp.arange(rows).reshape(
        [rows if i == axis else 1 for i in range(cache_arr.ndim)])

    def write_slot(b, arr):
        start = jnp.bitwise_and(pos[b], -rows) if t > rows else 0
        if t % rows:  # the last tile would end beyond T: it starts early
            start = jnp.minimum(start, t - rows)
        at = [0] * arr.ndim
        at[1], at[axis] = b, start
        old = jax.lax.dynamic_slice(
            arr, at, sizes, allow_negative_indices=False)
        row = jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1)
        # XLA clamps a start outside the array; the mask follows it.
        first = jnp.clip(start, 0, t - rows)
        return jax.lax.dynamic_update_slice(
            arr, jnp.where(offsets == pos[b] - first, row, old), at,
            allow_negative_indices=False)

    return jax.lax.fori_loop(0, cache_arr.shape[1], write_slot, cache_arr)
