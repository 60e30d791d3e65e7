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
expert's tokens and walks the chunks.  The expert itself, ``f_e``, is the
caller's: gated SwiGLU on the hidden state in ``longcat.py`` (``ffn``, which
``mimo_v2.py`` and ``mistral4.py`` run too), an ungated ``relu^2`` MLP on a
latent in ``nemotron_h.py``.  What absent experts would add is left out; what
every chip computes alike (a shared expert) is the family's, beside this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Rows of one expert's matrix product: a held expert sees few tokens (0.5-3 a
# decode step, 11-32 a prefill), so its tokens are gathered and run in
# chunks of at most this many rows; an expert no live token chose runs
# nothing and reads no weight.
EXPERT_CHUNK = 128


def _matmul(spec, x, w):  # ``longcat.matmul``, which imports this module
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


def runs_every_held_expert(rows: int, top_k: int, n_routed: int) -> bool:
    """Which way a layer's held experts run, read off the SHAPES, never off
    the load (the two ways round differently, so a request's greedy ids
    would depend on who else is served: ``nemotron_h.moe`` has the
    timings).  When the rows fit one chunk and make a choice or more an
    expert (a decode step of 64 slots) nearly every held expert is touched,
    and running ALL of them on every row in batched products streams the
    layer's experts once at the memory's speed; otherwise (a prefill) the
    gather and the chunk loop of ``held_experts``, whose cost hardly grows
    with the rows."""
    return rows <= EXPERT_CHUNK and rows * top_k >= n_routed


def sigmoid_route(u, router, bias, top_k: int, scale: float = 1.0):
    """The renormalised sigmoid router (``noaux_tc`` without group limits).
    u ``[N, d]`` float32, ``router [d, E]`` and ``bias [E]`` float32 -> the
    ``top_k`` experts each token chose ``[N, k]`` (the largest of ``p + bias``,
    ``p = sigmoid(u router)``) and their combine weights ``scale p / sum(p)``
    over the chosen, float32."""
    logits = jnp.dot(u, router, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(p + bias, top_k)
    chosen = jnp.take_along_axis(p, sel, axis=-1)
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


def held_experts(u, hit, w_held, expert):
    """``sum_e w_held[:, e] * expert(u, e)`` over the experts held here, for
    the tokens that chose them: ``u [N, d]``, ``hit [N, Eh]`` bool,
    ``w_held [N, Eh]`` float32, ``expert(x [chunk, d], e) -> [chunk, d]``
    float32 -> ``[N, d]`` float32.  Dropless: each expert's tokens are
    gathered (hit rows first, in row order) and run in chunks of
    ``EXPERT_CHUNK`` rows, as many chunks as its tokens need.  One loop
    walks the chunks of all experts, so an expert nobody chose costs no
    iteration and its weights are not read.  ``expert`` takes its weights as
    ``stack[layer, e]`` of the whole layer-stacked subtree, inside the loop:
    a layer's slice taken outside it is copied (1.2 GB a layer at LongCat's
    published sizes) before the loop may read it."""
    n, d = u.shape
    held = hit.shape[1]
    out = jnp.zeros((n, d), jnp.float32)
    if held == 0:  # a share with no expert
        return out
    chunk = min(n, EXPERT_CHUNK)
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


def held_experts_dense(u, w_held, experts, i: int):
    """``sum_e w_held[:, e] SwiGLU_e(u)`` with EVERY held expert run on every
    row (a row that did not choose it weighs 0): three batched products over
    layer ``i``'s stacks, no gather, no loop.  u ``[N, d]`` in the matrices'
    dtype, w_held ``[N, Eh]`` float32 -> ``[N, d]`` float32.  Dropless and row by
    row like the loop; the weight is applied before the last product (which
    is linear), in float32."""
    gate = jax.nn.silu(_matmul("nd,edf->enf", u, experts["w_gate"][i]))
    up = _matmul("nd,edf->enf", u, experts["w_up"][i])
    h = (gate * up * w_held.T[..., None]).astype(u.dtype)
    return _matmul("enf,efd->nd", h, experts["w_down"][i])
