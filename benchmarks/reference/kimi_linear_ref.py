"""Kimi-Linear forward, plain: float32 ``jax.numpy``, one full causal forward,
no cache, no chunks, no kernels: Kimi Delta Attention by its RECURRENCE (a
``lax.scan`` over positions), latent attention with per-head keys and values
EXPANDED from the latent and dense masked scores, the experts a Python loop
over the ones it is given.

Follows ``config.json`` of ``moonshotai/Kimi-Linear-48B-A3B-Instruct``
(``model_type`` ``kimi_linear``), the Kimi Linear report (arXiv:2510.26692)
and the equations in ``ray_tpu/models/kimi_linear.py``'s docstring; it
imports nothing of ``ray_tpu``.  A block is ``x = x + Mixer(N(x)); x = x +
FFN(N(x))``.

KDA: ``[q~ | k~ | v~] = u Wqkv``; ``(q, k, v) = silu(conv(.))``; ``q``, ``k``
L2-normalised a head, ``q`` times ``dk^-1/2``; the decay a VECTOR a head,
``alpha = exp(-exp(A_log[h]) softplus((u Wfa) Wfb + dt_bias))`` in ``(0,
1)^dk``; ``beta = sigmoid(u Wb)``; ``S' = Diag(alpha_t) S_{t-1}``, ``S_t = S' +
k_t (x) beta_t (v_t - S'^T k_t)``, ``o_t = S_t^T q_t``; ``RMSNorm_dv(o) * w *
sigmoid((u Wga) Wgb)``; ``Wo``.  MLA: ``q = u Wq`` (no bottleneck), ``[ckv |
kr] = u Wkva``, ``ckv`` normed, ``kr`` as projected (NO rotation: the KDA
layers carry position), a head's key ``[ckv Wkb | kr]``, value ``ckv Wvb``,
causal softmax at ``(dn+dr)^-1/2``.  FFN: a dense SwiGLU in the first layers;
elsewhere sigmoid scores over all experts, the ``k`` largest of ``score +
bias``, the chosen scores renormalised and times ``routed_scaling_factor``,
plus one shared SwiGLU.  The share: given ``expert_offset`` and the held
experts in ``params["experts"]``, routed experts outside ``[offset, offset +
held)`` add nothing, as in the program; with every expert held it is the
uncut model.  Weights are the program's pytree (one stack a kind of
sub-block), upcast matrix by matrix.

``sizes`` may switch a mechanism, for the controls that a comparison must
fail: ``scalar_gate`` True (the decay averaged over a head's channels: a
scalar gate, Olmo-Hybrid's rule on these weights), ``conv`` False (no
convolution: ``silu`` of the projections), ``output_gate`` ``"silu"``,
``rotate_kr`` True (interleaved rotary at ``rope_theta`` 1e4 on the ``dr``
columns of query and key), ``renormalise`` False, ``routed_scaling_factor``
1, ``latent_short`` True (a query does not read its own position's latent:
one position too few), ``shared`` False.  ``query_block``: the dense scores
are computed for that many query rows at a time against ALL keys (the same
arithmetic, for sequences whose ``[H, S, S]`` does not fit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
# a letter of the program's ``kinds`` -> the stacks of its mixer and its FFN
STACKS = {"K": ("kda", "moe"), "M": ("mla", "moe"),
          "k": ("kda", "dense"), "m": ("mla", "dense")}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)


def kda_recurrence(q, k, v, g, beta, state=None):
    """The recurrence, token by token.  q, k ``[B, S, H, dk]`` (already
    normalised and scaled), v ``[B, S, H, dv]``, g = ``log alpha`` ``[B, S, H,
    dk]`` (a vector a head) and beta ``[B, S, H]`` -> (o ``[B, S, H, dv]``, the
    last state ``[B, H, dk, dv]``)."""
    bsz, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)

    def step(s, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        s = jnp.exp(g_t)[..., None] * s  # Diag(alpha) S: the ROWS
        kv = (s * k_t[..., None]).sum(-2)  # S'^T k  [B, H, dv]
        s = s + k_t[..., None] * (beta_t[..., None] * (v_t - kv))[:, :, None]
        return s, (s * q_t[..., None]).sum(-2)

    state, o = jax.lax.scan(
        step, state, tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), state


def kda(u, w, sizes):
    """u [B, S, d] normed -> [B, S, d]; ``w``: one KDA layer's weights."""
    h, dk = sizes["linear_num_heads"], sizes["linear_head_dim"]
    taps = w["conv_w"].shape[0]
    bsz, s, _ = u.shape
    qkv = u @ _f32(w["w_qkv"])
    f = (u @ _f32(w["w_fa"])) @ _f32(w["w_fb"]) + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        f.reshape(bsz, s, h, dk))
    if sizes.get("scalar_gate"):
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ _f32(w["w_b"]))
    z = (u @ _f32(w["w_ga"])) @ _f32(w["w_gb"])
    if sizes.get("conv", True):
        padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        qkv = sum(padded[:, j:j + s] * w["conv_w"][j] for j in range(taps))
    qkv = jax.nn.silu(qkv)
    q, k, v = (qkv[..., n * h * dk:(n + 1) * h * dk].reshape(bsz, s, h, dk)
               for n in range(3))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / jnp.sqrt(
        float(dk))
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    o, _ = kda_recurrence(q, k, v, g, beta)
    o = _rms(o, w["norm"], sizes["rms_eps"])  # over dv, a head
    gate = (jax.nn.silu if sizes.get("output_gate") == "silu"
            else jax.nn.sigmoid)(z)
    return (o.reshape(bsz, s, h * dk) * gate) @ _f32(w["w_o"])


def _rope(x, theta: float = 1e4):
    """x [B, S, H, D], positions 0..S-1, interleaved pairs: a control's."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def mla(u, w, sizes):
    """u [B, S, d] normed -> attention output [B, S, d]."""
    rkv, dn, eps = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                    sizes["rms_eps"])
    s = u.shape[1]
    q = jnp.einsum("bse,ehd->bshd", u, _f32(w["wq"]))
    kv = u @ _f32(w["wkv_a"])
    ckv = _rms(kv[..., :rkv], w["rms_kv"], eps)
    qr, kr = q[..., dn:], kv[..., None, rkv:]
    if sizes.get("rotate_kr"):
        qr, kr = _rope(qr), _rope(kr)
    kn = jnp.einsum("bsc,chd->bshd", ckv, _f32(w["wk_b"]))
    v = jnp.einsum("bsc,chd->bshd", ckv, _f32(w["wv_b"]))
    scale = q.shape[-1] ** -0.5
    step = sizes.get("query_block") or s
    out = []
    for first in range(0, s, step):
        rows = slice(first, min(first + step, s))
        sc = (jnp.einsum("bshd,bthd->bhst", q[:, rows, :, :dn], kn)
              + jnp.einsum("bshd,btd->bhst", qr[:, rows], kr[:, :, 0]))
        at, keys = jnp.arange(s)[rows][:, None], jnp.arange(s)[None]
        seen = at >= keys
        if sizes.get("latent_short"):  # never its own position (but row 0)
            seen = (at > keys) | ((at == 0) & (keys == 0))
        sc = jnp.where(seen, sc * scale, -jnp.inf)
        out.append(jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v))
    return jnp.einsum("bshd,hde->bse", jnp.concatenate(out, 1), _f32(w["wo"]))


def moe(u, w, experts, sizes, expert_offset: int):
    """u [B, S, d] -> (the held experts' part + the shared expert [B, S, d],
    chosen experts [B, S, k])."""
    p = jax.nn.sigmoid(u @ _f32(w["router"]))
    _, sel = jax.lax.top_k(p + w["router_bias"], sizes["top_k"])
    chosen = jnp.take_along_axis(p, sel, axis=-1)
    if sizes.get("renormalise", True):
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weight = sizes["routed_scaling_factor"] * chosen
    y = jnp.zeros_like(u)
    for e in range(experts["w_gate"].shape[0]):
        w_e = (weight * (sel == expert_offset + e)).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(u, experts["w_gate"][e], experts["w_up"][e],
                              experts["w_down"][e])
    if sizes.get("shared", True):
        y = y + _swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return y, sel


def ref_layer(x, kind: str, mixer, ff, experts, sizes: dict,
              expert_offset: int = 0):
    """One block on the float32 stream ``x [B, S, d]``: ``kind`` the letter
    of ``kinds``, ``mixer`` / ``ff`` / ``experts`` that layer's weights
    (``experts`` ``None`` under a dense MLP)."""
    eps = sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        mix = kda if STACKS[kind][0] == "kda" else mla
        x = x + mix(_rms(x, mixer["rms"], eps), mixer, sizes)
        u = _rms(x, ff["rms"], eps)
        if experts is None:
            return x + _swiglu(u, ff["w_gate"], ff["w_up"], ff["w_down"])
        return x + moe(u, ff, experts, sizes, expert_offset)[0]


def ref_head(x, params, sizes: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["rms_f"], sizes["rms_eps"])
        return jnp.einsum("bse,ve->bsv", x, _f32(params["lm_head"]))


def layer_weights(params, kinds: str):
    """For each layer of ``kinds``: (kind, its mixer's weights, its FFN's,
    its held experts or ``None``), each taken from the front of its stack."""
    seen = dict.fromkeys(("kda", "mla", "dense", "moe"), 0)
    for kind in kinds:
        mixer, ff = STACKS[kind]
        i, j = seen[mixer], seen[ff]
        seen[mixer] += 1
        seen[ff] += 1
        yield (kind,
               {k: v[i] for k, v in params["blocks"][mixer].items()},
               {k: v[j] for k, v in params["blocks"][ff].items()},
               None if ff == "dense" else
               {k: v[j] for k, v in params["experts"].items()})


def kimi_linear_ref_logits(params, tokens, sizes: dict, kinds: str,
                           expert_offset: int = 0):
    """tokens [B, S] -> logits [B, S, V], float32, highest precision.
    ``sizes``: the fields of ``KimiLinearConfig`` (and the switches above);
    ``kinds``: the letters of the layers to run."""
    x = _f32(params["wte"][tokens])
    for kind, mixer, ff, experts in layer_weights(params, kinds):
        x = ref_layer(x, kind, mixer, ff, experts, sizes, expert_offset)
    return ref_head(x, params, sizes)
