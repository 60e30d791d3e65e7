"""The gated delta rule over a sequence, which Olmo-Hybrid and Kimi-Linear
share: a head's state is a matrix ``S [dk, dv]`` float32, and a token decays
it, then writes into it by a delta rule::

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + k_t (x) beta_t (v_t - S'^T k_t),        o_t = S_t^T q_t

The GATE's shape is static and the one thing that tells the two families
apart here: ``g = log alpha`` comes as ``[B, S, H]`` (a SCALAR a head a token:
Olmo-Hybrid's Gated DeltaNet, where ``Diag(alpha)`` is ``alpha I``) or as
``[B, S, H, dk]`` (a VECTOR over the key channels: Kimi Delta Attention).
``delta_chunked`` runs either in chunks; ``pack_state`` / ``unpack_state``
lay heads side by side on the lanes of a cache row.  The one-token update is
``ops/delta_update.py``.  This module imports no family (``layers.py``).

**A scalar gate.**  With ``gamma_i`` the running product of ``alpha`` inside
a chunk of ``C``: ``A = strictly_lower(diag(beta) (K K^T * gamma_i /
gamma_j))``, ``[W | U] = (I + A)^-1 diag(beta) [K * gamma | V]`` (one
triangular solve a head a chunk: the WY / UT transform); then chunk by chunk
against the carried state: ``V' = U - W S``, ``O = (Q * gamma) S + lower(Q K^T
* gamma_i / gamma_j) V'``, ``S <- gamma_C S + (K * gamma_C / gamma)^T V'``.

**A vector gate.**  ``gamma_i`` is a vector ``Gamma_i [dk]`` and the ``[C, C]``
matrix ``gamma_i / gamma_j`` that multiplied ``K K^T`` is gone: the decay goes
INTO the sum over channels, ``(K K^T)_ij -> sum_c K_ic K_jc Gamma_ic /
Gamma_jc``.  Written as a product of two scaled operands, ``(K * Gamma)(K /
Gamma)^T``, it overflows float32 as soon as one channel's decay over a chunk
passes ``e^88`` (thirteen tokens at ``alpha = 1e-3``) although every entry
that is kept (``i >= j``) is at most ``|K_i| |K_j|``.  So it is NOT written
so: ``exp(log Gamma_ic - log Gamma_jc)`` is taken pair by pair and channel
by channel, masked to ``i >= j`` BEFORE the exponential (no exponent is ever
positive), and summed over ``c`` on the vector unit: ``C^2 dk`` exponentials a
head a chunk, one chunk at a time (``lax.map``: the ``[C, C, dk]`` tensor of
all chunks at once would be 1 GB a 2,048-row sequence at 32 heads).  Exact
at any decay: a channel at ``alpha = 1e-3`` beside one at ``1 - 1e-6`` in the
same head equals the recurrence (``tests/test_kimi_linear.py``).  Everything
that crosses a chunk's edge has an exponent ``<= 0`` by itself: ``Q * Gamma``,
``K * Gamma``, ``K * Gamma_C / Gamma``, ``Gamma_C``.  What it costs beside the
scalar rule is in PERF.md (PR 67).

A position with ``beta = 0`` and ``g = 0`` neither writes nor decays the
state: that is how padding (``t >= length``) is left out of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def delta_chunked(q, k, v, g, beta, chunk: int):
    """The recurrence ``S_t = alpha_t S_{t-1} + k_t (x) beta_t (v_t - alpha_t
    S_{t-1}^T k_t)``, ``o_t = S_t^T q_t`` from ``S = 0``, in chunks.  q, k ``[B,
    S, H, dk]`` (normalised, q scaled), v ``[B, S, H, dv]``, g = ``log alpha``
    ``[B, S, H]`` (a scalar gate) or ``[B, S, H, dk]`` (a vector gate:
    ``alpha_t`` multiplies the state's ROWS) and beta ``[B, S, H]`` (both 0 =
    the position is left out of the state), all float32 -> o ``[B, S, H,
    dv]``, the last state ``[B, H, dk, dv]``, float32.  Float32 THROUGHOUT,
    its products at ``HIGHEST`` precision (``rule``): they are under a
    hundredth of a prefill's operations and cost it 0.2 ms a layer at 512
    rows on the v5e (1.68 against 1.50 ms: PERF.md, PR 56), and with
    operands rounded to bfloat16 the rule alone is off the recurrence by
    0.45 % of its output, four times a projection's rounding (``V' = U - W
    S`` and ``O = Q S + ...`` are differences of larger terms): 2.56 %
    against 2.09 at the logits of twelve layers."""
    if g.ndim == q.ndim:
        return _chunked_vector_gate(q, k, v, g, beta, chunk)
    bsz, s, h, dk = q.shape
    pad = -s % chunk
    if pad:  # beta = g = 0 there: the state passes through
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (s + pad) // chunk
    rule = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunks(a):  # [B, S, H, x] -> [B, n, H, C, x]
        return a.reshape(bsz, n, chunk, h, -1).transpose(0, 1, 3, 2, 4)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g)[..., 0], chunks(beta)  # [B, n, H, C], [.., C, 1]
    # log gamma_i: the decay from the chunk's start through position i
    acum = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # gamma_i / gamma_j where i >= j, 0 above the diagonal
    decay = jnp.exp(jnp.where(
        lower, acum[..., :, None] - acum[..., None, :], -jnp.inf))
    a = jnp.where(jnp.tril(lower, -1), beta * decay * rule(
        "bnhik,bnhjk->bnhij", k, k), 0.0)
    # [W | U] = (I + A)^-1 diag(beta) [K * gamma | V]: the diagonal of ones
    # is the solve's ``unit_diagonal``
    wu = jax.lax.linalg.triangular_solve(
        a, beta * jnp.concatenate([k * jnp.exp(acum)[..., None], v], -1),
        left_side=True, lower=True, unit_diagonal=True)
    inside = decay * rule("bnhik,bnhjk->bnhij", q, k)
    q_in = q * jnp.exp(acum)[..., None]
    k_out = k * jnp.exp(acum[..., -1:] - acum)[..., None]
    through = jnp.exp(acum[..., -1])  # [B, n, H]: a whole chunk's decay

    def next_chunk(state, inp):
        w, u, q_c, inside_c, k_c, keep = inp
        v_new = u - rule("bhck,bhkv->bhcv", w, state)
        o = (rule("bhck,bhkv->bhcv", q_c, state)
             + rule("bhij,bhjv->bhiv", inside_c, v_new))
        state = keep[..., None, None] * state + rule(
            "bhck,bhcv->bhkv", k_c, v_new)
        return state, o

    last, o = jax.lax.scan(
        next_chunk, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
        tuple(x.swapaxes(0, 1) for x in (
            wu[..., :dk], wu[..., dk:], q_in, inside, k_out, through)))
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, n * chunk, h, -1)
    return o[:, :s], last


def _chunked_vector_gate(q, k, v, g, beta, chunk: int):
    """``delta_chunked`` where ``g [B, S, H, dk]`` is a vector a head: the
    module's docstring.  The same transform and the same walk over the
    carried state as the scalar gate's; what differs is where the decay
    sits (inside the sums over ``dk``) and that a chunk's two ``[C, C]``
    matrices are made pair by pair."""
    bsz, s, h, dk = q.shape
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (s + pad) // chunk
    rule = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunks(a):  # [B, S, H, x] -> [n, B, H, C, x]
        return a.reshape(bsz, n, chunk, h, -1).transpose(1, 0, 3, 2, 4)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    acum = jnp.cumsum(g, axis=-2)  # [n, B, H, C, dk]: log Gamma_i
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def pairs(inp):
        """One chunk's ``sum_c K_ic K_jc Gamma_ic / Gamma_jc`` and the same
        with ``Q_i``, ``i >= j``: ``[B, H, C, C]`` each."""
        q_c, k_c, a_c = inp
        decay = jnp.exp(jnp.where(
            lower[..., None], a_c[..., :, None, :] - a_c[..., None, :, :],
            -jnp.inf))  # [B, H, C, C, dk], 0 above the diagonal
        k_j = k_c[..., None, :, :] * decay
        return ((k_c[..., :, None, :] * k_j).sum(-1),
                (q_c[..., :, None, :] * k_j).sum(-1))

    kk, inside = jax.lax.map(pairs, (q, k, acum))
    a = jnp.where(jnp.tril(lower, -1), beta * kk, 0.0)
    wu = jax.lax.linalg.triangular_solve(
        a, beta * jnp.concatenate([k * jnp.exp(acum), v], -1),
        left_side=True, lower=True, unit_diagonal=True)
    q_in = q * jnp.exp(acum)
    k_out = k * jnp.exp(acum[..., -1:, :] - acum)
    through = jnp.exp(acum[..., -1, :])  # [n, B, H, dk]: a chunk's decay

    def next_chunk(state, inp):
        w, u, q_c, inside_c, k_c, keep = inp
        v_new = u - rule("bhck,bhkv->bhcv", w, state)
        o = (rule("bhck,bhkv->bhcv", q_c, state)
             + rule("bhij,bhjv->bhiv", inside_c, v_new))
        state = keep[..., None] * state + rule("bhck,bhcv->bhkv", k_c, v_new)
        return state, o

    last, o = jax.lax.scan(
        next_chunk, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
        (wu[..., :dk], wu[..., dk:], q_in, inside, k_out, through))
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, n * chunk, h, -1)
    return o[:, :s], last


def pack_state(state, p: int):
    """``[B, H, dk, dv]`` -> the cache's ``[B, H / p, dk, p dv]``: ``p`` heads
    side by side on the lanes, so that a row is a whole number of the TPU's
    128 (a ``[96, 192]`` float32 matrix alone is padded to ``[96, 256]``: a
    third more to hold, read and write every step).  ``p = 1`` (heads whose
    values are whole tiles already: Kimi-Linear's 128) is the state itself."""
    b, h, dk, dv = state.shape
    return state.reshape(b, h // p, p, dk, dv).swapaxes(2, 3).reshape(
        b, h // p, dk, p * dv)


def unpack_state(packed, p: int):
    """``pack_state``'s inverse."""
    b, rows, dk, lanes = packed.shape
    return packed.reshape(b, rows, dk, p, lanes // p).swapaxes(2, 3).reshape(
        b, rows * p, dk, lanes // p)
