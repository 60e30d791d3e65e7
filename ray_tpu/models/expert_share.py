"""An expert layer's share on one chip: the part every family's expert layer
has in common once it has routed.

A layer that is told which experts it holds (``experts_held`` from
``expert_offset``) routes over ALL the model's experts, its own way
(LongCat: softmax, not renormalised, identity experts; Nemotron-H and
MiMo-V2: sigmoid scores, renormalised, ``sigmoid_route`` here; Mistral-4:
softmax, renormalised over the chosen, ``softmax_route``), and then
computes
``sum_{e held, chosen} w_e f_e(u)`` for the tokens that chose a held expert.
That sum is here: ``held_choices`` turns the router's choices into the held
experts' hit mask and combine weights, ``held_experts`` gathers each
expert's tokens and walks the chunks (a decode step's rows are one chunk: a
turn a touched expert on the whole batch).  The expert itself, ``f_e``, is the
caller's: gated SwiGLU on the hidden state (``layers.ffn``: LongCat, MiMo-V2,
Mistral-4, Laguna), an ungated ``relu^2`` MLP on a latent in
``nemotron_h.py``.  What absent experts would add is left out; what
every chip computes alike (a shared expert) is the family's, beside this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import matmul

# Rows of one expert's matrix product: a held expert sees few tokens (0.5-3 a
# decode step, 8-128 a prefill of 256-4096 rows), so its tokens are gathered
# and run in chunks of at most this many rows (``chunk_rows``: twice as many
# in a prefill of 8,192 rows or more, where it sees 250-650; a decode step's
# rows, never more than this, are one chunk and stay where they are); an
# expert no live token chose runs nothing and reads no weight.
EXPERT_CHUNK = 128
LOOP_COUNT_NAMES = ("held_chunks", "held_chunk_rows")  # ``loop_counts``


def runs_every_held_expert(rows: int, top_k: int, n_routed: int) -> bool:
    """Which way a layer's held experts run, read off the SHAPES, never off
    the load (the two ways round differently, so a request's greedy ids
    would depend on who else is served: ``nemotron_h.moe``).  When the rows
    fit one chunk (a decode step) and would, each choosing on its own, touch
    three in four of the experts or more, every held expert runs on every
    row in batched products, which stream the layer's experts once at the
    memory's speed whatever was chosen; otherwise ``held_experts``: a
    prefill's gather and chunk loop, a decode step's turn a touched expert.

    Where the line lies is a timing of one layer's held experts on the v5e
    at the four callers' step shapes (PERF.md, PR 54): a turn of the loop
    costs its expert's read (76 us of 50 MB, 36 of 19, 19 of 11) and the
    products read all the held at 79-90 % of the memory's speed, so the
    loop wins while fewer than 14.5 of Mistral-4's 16, 13.9 of MiMo-V2's,
    12.3 of Laguna's, 100 of Nemotron-H's 128 are touched: 77-90 %, and
    every shape under three in four is under all of them.  Independent rows
    touch ``1 - (1 - k / E) ** rows``:
    64 % in Mistral-4's step (32 x 4 / 128, a choice an expert; 43-49 % as
    served) and 72 % in Laguna's (32 x 10 / 256), which take the loop; 87 %
    in MiMo-V2's (64 x 8 / 256, a tie) and 94 % in Nemotron-H's (64 x 22 /
    512), which run them all."""
    return (rows <= EXPERT_CHUNK
            and 1 - (1 - top_k / n_routed) ** rows >= 0.75)


def chosen_scores(p, sel):
    """``p [N, E]`` at ``sel [N, k]`` -> ``[N, k]``, the bits ``take_along_axis``
    gives: a select and a max over ``E``, which fuse into one pass.  The
    gather of ``N k`` scalars it replaces cost a 16,384-row prefill 1.7 ms an
    expert layer on the v5e, as much as the router's product (PERF.md,
    PR 53); a max, not a sum, so that no pass can merge it into the sum
    over the chosen that follows and reorder that."""
    picked = sel[..., None] == jnp.arange(p.shape[-1])
    return jnp.where(picked, p[:, None, :], -jnp.inf).max(-1)


def sigmoid_route(u, router, bias, top_k: int, scale: float = 1.0):
    """The renormalised sigmoid router (``noaux_tc`` without group limits).
    u ``[N, d]`` float32, ``router [d, E]`` and ``bias [E]`` float32 -> the
    ``top_k`` experts each token chose ``[N, k]`` (the largest of ``p + bias``,
    ``p = sigmoid(u router)``) and their combine weights ``scale p / sum(p)``
    over the chosen, float32."""
    logits = jnp.dot(u, router, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(p + bias, top_k)
    chosen = chosen_scores(p, sel)
    w = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return sel, w


def softmax_route(u, router, top_k: int, scale: float = 1.0):
    """The renormalised softmax router (no bias, no group limits).  u ``[N,
    d]`` float32, ``router [d, E]`` float32 -> the ``top_k`` experts each token
    chose ``[N, k]`` (the largest of ``p = softmax(u router)``) and their
    combine weights ``scale p / sum(p)`` over the chosen, float32."""
    logits = jnp.dot(u, router, precision=jax.lax.Precision.HIGHEST)
    chosen, sel = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return sel, scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def held_choices(sel, w, live, expert_offset: int, experts_held: int):
    """The router's choices as the held experts see them.  ``sel [N, k]``
    chosen expert ids, ``w [N, k]`` float32 combine weights, ``live [N]``
    bool (a padded or idle row chooses nothing here) -> ``held [N, k]`` bool
    (the choice fell on an expert held here), ``hit [N, Eh]`` bool, ``w_held
    [N, Eh]`` float32."""
    local = sel - expert_offset
    held = (local >= 0) & (local < experts_held) & live[:, None]
    onehot = held[..., None] & (local[..., None] == jnp.arange(experts_held))
    return held, onehot.any(1), (w[..., None] * onehot).sum(1)


def chunk_rows(n: int) -> int:
    """Rows of one turn of ``held_experts`` over ``n`` rows, read off the
    shape: ``EXPERT_CHUNK`` where a held expert sees tens of rows (larger
    chunks would run padding), twice that from 8,192 rows on, where it sees
    hundreds and a turn's products cost what the expert's weights cost to
    read, whatever the rows: 33 us for 128 rows and 39 us for 256 of
    Laguna's experts (19 MB), 78 and 91 us of Mistral-4's (50 MB), in their
    16,384-row prefills on the v5e; 512 rows lose more to the padding of
    each expert's last chunk than they save (PERF.md, PR 53)."""
    return min(n, EXPERT_CHUNK if n < 8192 else 2 * EXPERT_CHUNK)


def held_experts(u, hit, w_held, expert):
    """``sum_e w_held[:, e] * expert(u, e)`` over the experts held here, for
    the tokens that chose them: ``u [N, d]``, ``hit [N, Eh]`` bool,
    ``w_held [N, Eh]`` float32, ``expert(x [chunk, d], e) -> [chunk, d]``
    float32 -> ``[N, d]`` float32.  Dropless: each expert's tokens are
    gathered (hit rows first, in row order) and run in chunks of
    ``chunk_rows(N)`` rows, as many chunks as its tokens need; a token's
    held choices are added in float32 in ascending expert order.  One loop
    walks the chunks of all experts, so an expert nobody chose costs no
    iteration and its weights are not read.  ``expert`` takes its weights as
    ``stack[layer, e]`` of the whole layer-stacked subtree, inside the loop:
    a layer's slice taken outside it is copied (1.2 GB a layer at LongCat's
    published sizes) before the loop may read it.

    What a turn of 256 rows costs in a 16,384-row prefill on the v5e (device
    trace, PERF.md, PR 53; Laguna's ``d`` 3072 / Mistral-4's 4096): the rows'
    gather 9 / 11 us, the products 39 / 91 us, the scatter-add 87 / 113 us.
    The scatter-add is XLA's price for a float32 row by index, 0.34-0.44 us,
    and it is the same inside this loop and in ONE scatter-add after it: with
    the rows permuted into expert order once, the chunks contiguous slices
    and the result combined once (bit-identical, tried in PR 53) the layer
    took as long, so the gather and the add stay where the chunk is.

    Where all the rows are ONE chunk (``n <= chunk_rows(n)``: a decode step;
    no prefill rung is that short) there is nothing to gather: a turn runs
    the i-th TOUCHED expert, in ascending order, on the whole batch and
    adds it where it was chosen, so the same float32 sum in the same order
    without the sort, the gather and the scatter-add (bit-identical to them
    on the v5e at the five cells' step shapes).  The trip count is the
    load's, a row's value is not: it is computed from that row alone
    whatever the others hold or choose.  A turn there costs what its
    expert's matrices cost to read at 64-83 % of the memory's speed: 19 us of
    Nemotron-H's 11 MB, 36 of Laguna's 19, 76 of Mistral-4's 50 (77 on
    MiMo-V2's 64 rows), 111 of LongCat's 75.5; the sort, gather and
    scatter-add it dropped were 4-9 us of a turn (PERF.md, PR 54)."""
    n, d = u.shape
    held = hit.shape[1]
    out = jnp.zeros((n, d), jnp.float32)
    if held == 0:  # a share with no expert
        return out
    chunk = chunk_rows(n)
    if n <= chunk:  # a decode step: every turn's chunk is all the rows
        ends = jnp.cumsum(hit.any(0))  # a turn for each touched expert

        def one_expert(i, out):
            e = (ends <= i).sum()  # the i-th touched expert, ascending
            chose = jax.lax.dynamic_index_in_dim(hit, e, 1)
            w = jax.lax.dynamic_index_in_dim(w_held, e, 1)
            return out + jnp.where(chose, expert(u, e) * w, 0.0)

        return jax.lax.fori_loop(0, ends[-1], one_expert, out)
    padded = -(-n // chunk) * chunk
    counts = hit.sum(0)  # [Eh] tokens of each expert
    # Per expert, its rows first; the tail (and the padding to whole chunks)
    # indexes past the last row, so the gather fills zeros and the scatter
    # drops; every index is distinct.
    order = jnp.argsort(~hit.T, axis=1, stable=True)
    past = n + jnp.arange(padded)[None]
    order = jnp.where(jnp.arange(n)[None] < counts[:, None], order,
                      past[:, :n])
    order = jnp.concatenate(
        [order, jnp.broadcast_to(past[:, n:], (held, padded - n))], 1)
    chunks = -(-counts // chunk)  # [Eh] chunks of each expert
    ends = jnp.cumsum(chunks)

    def one_chunk(i, out):
        e = (ends <= i).sum()  # the expert whose chunk this is
        first = (i - (ends[e] - chunks[e])) * chunk
        rows = jax.lax.dynamic_slice(order, (e, first), (1, chunk))[0]
        x = u.at[rows].get(mode="fill", fill_value=0)
        w = w_held.at[rows, e].get(mode="fill", fill_value=0)
        return out.at[rows].add(expert(x, e) * w[:, None], mode="drop",
                                unique_indices=True)

    return jax.lax.fori_loop(0, ends[-1], one_chunk, out)


def loop_counts(hit, looped: bool = True):
    """What ``held_experts`` runs for ``hit [N, Eh]``, for the family's
    counts (int32 scalars): ``held_chunks``, its loop's turns, and
    ``held_chunk_rows``, the rows they ran, of which ``routed_held`` chose
    their expert and the rest pad an expert's last chunk.  Zeros for a layer
    that ran the dense products instead (``looped`` false)."""
    if not looped:
        return dict.fromkeys(LOOP_COUNT_NAMES, jnp.zeros((), jnp.int32))
    chunk = chunk_rows(hit.shape[0])
    chunks = (-(-hit.sum(0) // chunk)).sum()
    return {"held_chunks": chunks, "held_chunk_rows": chunks * chunk}


def held_experts_dense(u, w_held, experts, i: int):
    """``sum_e w_held[:, e] SwiGLU_e(u)`` with EVERY held expert run on every
    row (a row that did not choose it weighs 0): three batched products over
    layer ``i``'s stacks, no gather, no loop.  u ``[N, d]`` in the matrices'
    dtype, w_held ``[N, Eh]`` float32 -> ``[N, d]`` float32.  Dropless and row by
    row like the loop; the weight is applied before the last product (which
    is linear), in float32."""
    gate = jax.nn.silu(matmul("nd,edf->enf", u, experts["w_gate"][i]))
    up = matmul("nd,edf->enf", u, experts["w_up"][i])
    h = (gate * up * w_held.T[..., None]).astype(u.dtype)
    return matmul("enf,efd->nd", h, experts["w_down"][i])
