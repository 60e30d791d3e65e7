"""The Mamba-2 mixer, which Nemotron-H and Granite-4.0-H run whole and
MiniCPM-SALA's lightning layers run in part (``ssd_chunked``: the one
chunked decay scan of the tree).

Over whole sequences (prefill, training) ``mamba_sequence``: projections
(``mamba_project``), the causal convolution, ``split_xbc``, the chunked scan
(``ssd_chunked``) and the gated grouped norm with the output product
(``mamba_output``).  One token a row (decode) ``mamba_step``: the same
projections around ``ops/conv_update.py`` and ``ops/mamba_update.py``, which
step layer ``i`` of the two stacked state leaves where they lie.  The
mathematics and the leaves' layout are in ``nemotron_h.py``'s and
``nemotron_h_decode.py``'s docstrings.

``cfg`` is any config with the fields read here BY NAME (``NemotronHConfig``,
``GraniteHConfig``): ``d_inner``, ``mamba_num_heads``, ``mamba_head_dim``,
``n_groups``, ``ssm_state_size``, ``conv_kernel``, ``chunk_size``,
``rms_eps``, ``dtype``.  ``m`` is a stack of Mamba-2 layers' weights
(``w_z``, ``w_xbc``, ``w_dt``, ``dt_bias``, ``conv_w``, ``conv_b``,
``a_log``, ``d_skip``, ``norm``, ``w_out``), ``i`` the layer in it.  This
module imports no family (``layers.py``) and enters no ``jax.named_scope``:
the scope is the caller's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.conv_update import conv_update
from ..ops.mamba_update import mamba_update
from .layers import conv_sequence, matmul


def mamba_project(y, m, i: int, cfg):
    """y ``[..., d]`` in ``cfg.dtype`` -> ``z [..., HP]``, ``xBC [..., HP +
    2GN]`` (before the convolution) and ``dt [..., H]`` (after the
    softplus), float32."""
    z = matmul("...e,ef->...f", y, m["w_z"][i])
    xbc = matmul("...e,ef->...f", y, m["w_xbc"][i])
    dt = jax.nn.softplus(matmul("...e,eh->...h", y, m["w_dt"][i])
                         + m["dt_bias"][i])
    return z, xbc, dt


def split_xbc(xbc, cfg):
    """``[..., HP + 2GN]`` -> ``x [..., H, P]``, ``B``, ``C [..., G, N]``."""
    gn = cfg.n_groups * cfg.ssm_state_size
    x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim),
            b.reshape(*lead, cfg.n_groups, cfg.ssm_state_size),
            c.reshape(*lead, cfg.n_groups, cfg.ssm_state_size))


def mamba_output(y, z, m, i: int, cfg):
    """``RMSNorm_grouped(y * silu(z)) Wout``: y ``[..., H, P]`` float32, z
    ``[..., HP]`` -> ``[..., d]`` float32."""
    lead, g = z.shape[:-1], cfg.n_groups
    y = y.reshape(*lead, g, cfg.d_inner // g) * jax.nn.silu(z).reshape(
        *lead, g, cfg.d_inner // g)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.rms_eps)
    y = y.reshape(*lead, cfg.d_inner) * m["norm"][i].astype(jnp.float32)
    return matmul("...f,fe->...e", y.astype(jnp.dtype(cfg.dtype)),
                  m["w_out"][i])


def ssd_chunked(x, dt, a, b, c, d_skip, chunk: int, dtype):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
    S_t C_t + D x_t`` from ``S = 0``, in chunks.  x ``[B, S, H, P]``, dt
    ``[B, S, H]`` (0 = the position is left out of the state), a, d_skip
    ``[H]``, b, c ``[B, S, G, N]``, all float32 -> y ``[B, S, H, P]``, the
    last state ``[B, H, P, N]``, float32.  The products read ``dtype``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    pad = -s % chunk
    if pad:  # dt = 0 there: the state passes through
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n).astype(dtype)
    c = c.reshape(bsz, nc, chunk, g, n).astype(dtype)
    # log of the decay from a chunk's start through position q, inclusive
    acum = jnp.cumsum(dt * a.reshape(g, r), axis=2)  # [B, c, Q, G, R]
    # inside a chunk: y_q += sum_{k <= q} (C_q . B_k) exp(acum_q - acum_k)
    # dt_k x_k
    seg = (acum.transpose(0, 1, 3, 4, 2)[..., :, None]
           - acum.transpose(0, 1, 3, 4, 2)[..., None, :])  # [B,c,G,R,Q,K]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = matmul("bcqgn,bckgn->bcgqk", c, b)
    weights = (cb[:, :, :, None] * decay
               * dt.transpose(0, 1, 3, 4, 2)[..., None, :])
    y = matmul("bcgrqk,bckgrp->bcqgrp", weights.astype(dtype),
               x.astype(dtype))
    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(acum[:, :, -1:] - acum) * dt  # [B, c, Q, G, R]
    ends = matmul("bckgrp,bckgn->bcgrpn",
                  (x * to_end[..., None]).astype(dtype), b)
    through = jnp.exp(acum[:, :, -1])  # [B, c, G, R] a whole chunk's decay

    def next_chunk(state, inp):
        end, keep = inp
        return keep[..., None, None] * state + end, state

    last, before = jax.lax.scan(
        next_chunk, jnp.zeros((bsz, g, r, p, n), jnp.float32),
        (ends.swapaxes(0, 1), through.swapaxes(0, 1)))
    # what the chunks before it left: y_q += exp(acum_q) C_q . S_before
    y = y + jnp.exp(acum)[..., None] * matmul(
        "bcqgn,bcgrpn->bcqgrp", c, before.swapaxes(0, 1).astype(dtype))
    y = y + d_skip.reshape(g, r, 1) * x
    return (y.reshape(bsz, nc * chunk, h, p)[:, :s],
            last.reshape(bsz, h, p, n))


def mamba_sequence(y, lengths, m, i: int, cfg):
    """The Mamba-2 mixer over whole sequences.  y ``[B, S, d]``, lengths
    ``[B]`` -> (``[B, S, d]`` float32, the convolution's state ``[B, (K-1)(HP
    + 2GN)]`` = its last ``K-1`` TRUE inputs side by side, oldest first, the
    state ``[B, H, P, N]`` after position ``length - 1``).  Positions ``>=
    length`` change neither."""
    s = y.shape[1]
    z, xbc, dt = mamba_project(y, m, i, cfg)
    dt = jnp.where(jnp.arange(s)[None, :, None] < lengths[:, None, None],
                   dt, 0.0)
    conv, conv_state = conv_sequence(xbc, lengths, m["conv_w"], i)
    x, b, c = split_xbc(jax.nn.silu(conv + m["conv_b"][i]), cfg)
    out, state = ssd_chunked(
        x, dt, -jnp.exp(m["a_log"][i]), b, c, m["d_skip"][i], cfg.chunk_size,
        jnp.dtype(cfg.dtype))
    return (mamba_output(out, z, m, i, cfg),
            conv_state.reshape(y.shape[0], -1), state)


def mamba_step(y, conv_leaf, leaf, m, i: int, cfg):
    """One token a row through Mamba-2 layer ``i``, whose state is layer
    ``i`` of the two stacked leaves ``conv_leaf [M, B, (K-1)(HP + 2GN)]`` and
    ``leaf [M, B, H, P, N]``.  y ``[B, d]`` -> (``[B, d]`` float32, the two
    leaves with layer ``i`` stepped: the same buffers where the caller
    donated them, ``ops/conv_update.py`` and ``ops/mamba_update.py``)."""
    z, xbc, dt = mamba_project(y, m, i, cfg)
    conv, conv_leaf = conv_update(conv_leaf, i, xbc, m["conv_w"][i])
    x, b, c = split_xbc(jax.nn.silu(conv + m["conv_b"][i]), cfg)
    keep = jnp.exp(dt * -jnp.exp(m["a_log"][i]))  # [B, H]
    out, leaf = mamba_update(leaf, i, x, dt, keep, b, c)
    out = out + m["d_skip"][i][:, None] * x  # [B, H, P]
    return mamba_output(out, z, m, i, cfg), conv_leaf, leaf
