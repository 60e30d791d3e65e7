"""Single-token decode attention over a KV cache — the serving hot op.

During generation each sequence attends one query token against its own
``[0, pos]`` cache prefix.  This is HBM-bandwidth-bound (the live cache
prefix streams through once per token), so the Pallas kernel's job is to
stream exactly the live prefix and nothing else.  The TPU analog of the
paged/decode attention kernels the reference gets from vLLM's CUDA side
(SURVEY.md §2.3: the reference has no kernels of its own).

Kernel design (v5e-measured; see ``models/gpt2_decode.py`` docstring):
  - grid ``(B,)`` — one program per batch row, all kv heads processed
    in-program so program count stays low (per-(b,h) and per-(b,t-block)
    grids both measured launch-overhead-bound on v5e);
  - each program copies its full [Hkv, T, D] cache slice HBM→VMEM; the
    in-kernel online-softmax loop is bounded by the row's live prefix
    (``pos``), so only compute — not the copy — is ragged.  On the
    bandwidth-limited v5e-lite part this is why the XLA path currently
    wins for decode (20.5 vs 29 ms at B=32/T=1024; the model decode steps
    default to ``kernel=False``); ragged copy elision via scalar-prefetched
    clamped index maps is the known follow-up;
  - the *current* token's k/v ride in as separate [B, Hkv, D] operands and
    are merged into the online softmax as a final length-1 block — this is
    what lets the engine defer all cache scatters to one batched write per
    step instead of two per layer (TPU scatters are ~1 ms each);
  - grouped-query attention is native: each kv head carries its
    ``G = H // Hkv`` query rows as one [G, block_t] score tile.

What the v5e compiler accepts (``tests/test_tpu_compile.py``, PR 22): the
kernel compiles at the GPT-2 shape (L,B,H,Hkv,T,D) = (12,32,12,12,1024,64).
It is REFUSED for VMEM (``RESOURCE_EXHAUSTED``) at TinyLlama's
(22,32,32,8,2048,64) and at the 7B shape (32 kv heads, T=4096, D=128: 129 MB
of 128 MB) — one whole ``[Hkv, T, D]`` window per cache operand is the
design above, so wider caches need the T-blocked copy.  ``kernel=True`` on a
TPU is the kernel or an error, never the reference in silence; the model
decode steps default to ``kernel=False``.

Layouts (head-major, nothing transposes on the hot path):
  q        [B, H, D];  k/v cache [L, B, Hkv, T, D];  k/v self [B, Hkv, D]
  pos      [B]  — index of the current token (attends [0, pos-1] + self)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reference_decode_attention(q, k_cache, v_cache, pos, layer: int,
                               k_self=None, v_self=None):
    """Ground truth in plain XLA.  q [B,H,D]; caches [L,B,Hkv,T,D].

    Without self k/v: attends [0, pos] of the cache (current token assumed
    already written).  With self k/v: attends [0, pos-1] plus the explicit
    current token (the deferred-scatter form the kernel implements)."""
    k = k_cache[layer]  # [B, Hkv, T, D]
    v = v_cache[layer]
    b, hkv, t, d = k.shape
    h = q.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = d ** -0.5
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k).astype(jnp.float32) * scale
    limit = pos[:, None, None, None]
    idx = jnp.arange(t)[None, None, None, :]
    if k_self is None:
        mask = idx <= limit
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v.dtype), v)
        return out.reshape(b, h, d)
    mask = idx < limit  # strictly before the current token
    scores = jnp.where(mask, scores, NEG_INF)
    s_self = (
        jnp.einsum("bkgd,bkd->bkg", qg, k_self).astype(jnp.float32) * scale
    )[..., None]
    full = jnp.concatenate([scores, s_self], axis=-1)
    probs = jax.nn.softmax(full, axis=-1)
    out = jnp.einsum(
        "bkgt,bktd->bkgd", probs[..., :-1].astype(v.dtype), v
    ) + probs[..., -1:].astype(v.dtype) * v_self[:, :, None, :]
    return out.reshape(b, h, d)


def write_token_to_cache(cache_arr, new, pos):
    """Write one token's k or v into the stacked cache.

    cache_arr [L,B,Hkv,T,D]; new [L,B,Hkv,D]; pos [B] → updated cache.
    Lowered as vmapped ``dynamic_update_slice`` — measured ~1 ms for a full
    12-layer write on v5e, vs ~12 ms for the equivalent gather/scatter
    (TPU scatters with multiple index dims lower pathologically)."""

    def per_lb(c, u, p):  # c [Hkv,T,D], u [Hkv,D]
        return jax.lax.dynamic_update_slice(c, u[:, None, :], (0, p, 0))

    over_b = jax.vmap(per_lb, in_axes=(0, 0, 0))
    over_lb = jax.vmap(over_b, in_axes=(0, 0, None))
    return over_lb(cache_arr, new, pos)


def _decode_kernel(pos_ref, q_ref, ks_ref, vs_ref, k_ref, v_ref, o_ref, *,
                   block_t: int, n_blocks: int, scale: float):
    """Grid (B,) — one program per batch row, all kv heads at once (keeps
    program count low; per-(b,h) and per-(b,t-block) grids measured
    launch-overhead-bound on v5e).  Tiles (squeezed): q [Hkv, G, D],
    ks/vs [Hkv, D] (current token), k/v [Hkv, T, D].  In-kernel online
    softmax with a dynamic block bound: only the [0, pos] prefix is swept."""
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    pos = pos_ref[b]
    q = q_ref[...].astype(jnp.float32) * scale  # [Hkv, G, D]
    hkv, g, d = q.shape

    def body(tb, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[:, pl.dslice(tb * block_t, block_t), :].astype(jnp.float32)
        v = v_ref[:, pl.dslice(tb * block_t, block_t), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, Tb]
        idx = tb * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(idx < pos, s, NEG_INF)  # strictly-before mask
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_cur, l_cur, acc

    live_blocks = jnp.minimum(
        jax.lax.div(pos + block_t - 1, block_t), n_blocks
    )
    m0 = jnp.full((hkv, g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, g, 1), jnp.float32)
    acc0 = jnp.zeros((hkv, g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, live_blocks, body, (m0, l0, acc0))

    # Merge the current token as a length-1 block, then normalize.
    ks = ks_ref[...].astype(jnp.float32)  # [Hkv, D]
    vs = vs_ref[...].astype(jnp.float32)
    s_self = jnp.sum(q * ks[:, None, :], axis=-1, keepdims=True)
    m_cur = jnp.maximum(m, s_self)
    alpha = jnp.exp(m - m_cur)
    p_self = jnp.exp(s_self - m_cur)
    l_cur = l * alpha + p_self
    acc = acc * alpha + p_self * vs[:, None, :]
    o_ref[...] = (acc / jnp.maximum(l_cur, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("layer", "block_t", "kernel", "interpret")
)
def decode_attention(q, k_cache, v_cache, pos, layer: int = 0, *,
                     k_self=None, v_self=None, block_t: int = 256,
                     kernel: bool = True, interpret: bool = False):
    """q [B,H,D], k/v cache [L,B,Hkv,T,D], pos [B] → [B,H,D].

    ``layer`` is static: the BlockSpecs read that slice of the stacked
    cache in place.  With ``k_self``/``v_self`` [B,Hkv,D] the current
    token's k/v are merged in-kernel and the cache is treated as holding
    only [0, pos-1] (deferred-scatter protocol); without them the cache row
    at ``pos`` must already be written."""
    from .attention import _on_tpu

    b, h, d = q.shape
    _l, _b, hkv, t, _d = k_cache.shape
    g = h // hkv
    on_tpu = _on_tpu()
    if not kernel or not (on_tpu or interpret):
        return reference_decode_attention(
            q, k_cache, v_cache, pos, layer, k_self, v_self
        )
    if t % block_t or k_self is None:
        raise ValueError(
            f"decode attention kernel: cache length {t} must be a multiple "
            f"of block_t={block_t} and k_self/v_self must be given; ask for "
            "the reference with kernel=False"
        )
    interpret = interpret and not on_tpu  # never the interpreter on a chip
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = d ** -0.5
    n_blocks = t // block_t
    qf = q.reshape(b, hkv, g, d)
    posf = pos.astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_t=block_t, n_blocks=n_blocks, scale=scale
        ),
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # pos, whole array
            pl.BlockSpec((None, hkv, g, d), lambda rb: (rb, 0, 0, 0)),
            pl.BlockSpec((None, hkv, d), lambda rb: (rb, 0, 0)),
            pl.BlockSpec((None, hkv, d), lambda rb: (rb, 0, 0)),
            pl.BlockSpec(
                (None, None, hkv, t, d), lambda rb: (layer, rb, 0, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, hkv, t, d), lambda rb: (layer, rb, 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((None, hkv, g, d), lambda rb: (rb, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(posf, qf, k_self, v_self, k_cache, v_cache)
    return out.reshape(b, h, d)
