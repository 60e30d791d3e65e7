"""Latent attention (MLA), which LongCat-Flash, Mistral-Small-4 and
Kimi-Linear share.

The cache holds ``[ckv | kr]`` a token an attention: the key-value latent
after its norm and ONE key of ``dr`` for all heads beside it (rotary in
LongCat and Mistral-4; Kimi-Linear's carries NO position, ``rotate=False``:
its delta-rule layers do).  ``mla_project`` makes the queries and that
latent; ``mla_expanded`` is the dense form over whole sequences (per-head
keys and values expanded from the latent: LongCat's prefill and training);
``mla_blocked`` tiles the same expansion through ``layers.blocked_attention``
(Mistral-4's and Kimi-Linear's prefill); ``mla_absorbed`` is the decode
step's form, where ``Wkvb``'s key half is folded into the query and no
per-head key or value ever exists.  The mathematics is in ``longcat.py``'s
docstring.

``cfg`` is any config with the fields read here BY NAME: ``kv_lora_rank``,
``qk_nope_head_dim``, ``rms_eps``, and where they are used ``q_lora_rank``,
``d_model`` (the latent scales), ``rope_theta`` (a rotation).  What a family
does otherwise it says by arguments that are static or absent, or by the
weights it hands in: a query projected by ONE matrix (``wq [d, H, dn+dr]``:
no ``wq_a``, ``rms_q``, ``wq_b``; Kimi-Linear's ``q_lora_rank`` null).  This
module imports no family (``layers.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.decode_attention import extent_step
from ..ops.latent_attention import latent_attention
from .layers import blocked_attention, matmul, rmsnorm, rope


def mla_project(y, att, positions, cfg, *, latent_scales: bool = True,
                inv_freq=None, q_factor=None, rotate: bool = True):
    """y ``[B, S, d]`` -> roped queries ``[B, S, H, dn+dr]`` and the latent
    ``[ckv | kr]`` ``[B, S, rkv+dr]`` that the cache holds.  ``cfg``: any
    config with the latent attention's sizes (``LongcatConfig``,
    ``Mistral4Config``).  The defaults are LongCat's conventions: ``aq`` and
    ``akv`` on query and latent, rotary at ``theta ** (-2i / dr)``.  A family
    says otherwise by arguments that are static or absent:
    ``latent_scales=False`` (neither scale), ``inv_freq`` ``[dr/2]`` (its own
    rotary frequencies), ``q_factor`` (float32, broadcast against ``[B, S, H,
    dn+dr]``: what multiplies the whole query before it is rounded, a
    softmax scale that depends on the query's position); ``rotate=False``
    (no position anywhere: the ``dr`` columns of query and key are left as
    projected and ``positions`` is not read).  A query with no bottleneck is
    said by the weights: ``att["wq"] [d, H, dn+dr]`` in place of ``wq_a``,
    ``rms_q`` and ``wq_b``."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if "wq" in att:  # one matrix, no query latent
        q = matmul("bse,ehd->bshd", y, att["wq"])
    else:
        cq = rmsnorm(matmul("bse,er->bsr", y, att["wq_a"]), att["rms_q"],
                      cfg.rms_eps).astype(y.dtype)
        q = matmul("bsr,rhd->bshd", cq, att["wq_b"])
    if latent_scales:
        q = q * (cfg.d_model / cfg.q_lora_rank) ** 0.5
    if q_factor is not None:
        q = q * q_factor
    if rotate:
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta,
                               inv_freq)], -1)
    kv = matmul("bse,er->bsr", y, att["wkv_a"])
    ckv = rmsnorm(kv[..., :rkv], att["rms_kv"], cfg.rms_eps)
    if latent_scales:
        ckv = ckv * (cfg.d_model / rkv) ** 0.5
    kr = kv[..., rkv:]
    if rotate:
        kr = rope(kv[..., None, rkv:], positions, cfg.rope_theta,
                  inv_freq)[..., 0, :]
    return q.astype(y.dtype), jnp.concatenate([ckv, kr], -1).astype(y.dtype)


def mla_blocked(q, latent, att, cfg, longest=None, **blocks):
    """Latent attention of ``[B, S]`` tokens over themselves with per-head
    keys and values expanded from the latent once (prefill, training) and
    scored in blocks; ``[B, S, d]`` float32.  A head's key is ``[kn_h | kr]``:
    the one shared key is repeated a head, so that scores are ONE product
    over ``dn+dr``.  ``att`` holds ``Wkvb`` as two leaves (``wk_b``,
    ``wv_b``)."""
    rkv = cfg.kv_lora_rank
    ckv, kr = latent[..., :rkv], latent[..., rkv:]
    kn = matmul("bsc,chd->bshd", ckv, att["wk_b"]).astype(q.dtype)
    k = jnp.concatenate([kn, jnp.broadcast_to(
        kr[:, :, None], kn.shape[:3] + kr.shape[-1:])], -1)
    v = matmul("bsc,chd->bshd", ckv, att["wv_b"]).astype(q.dtype)
    o = blocked_attention(q, k, v, longest, **blocks)
    return matmul("bshd,hde->bse", o.astype(q.dtype), att["wo"])


def mla_expanded(q, latent, att, cfg):
    """Causal attention of ``[B, S]`` tokens over themselves with per-head
    keys and values expanded from the latent (prefill, training);
    ``[B, S, d]`` float32."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    s = q.shape[1]
    kv = matmul("bsc,chd->bshd", latent[..., :rkv], att["wkv_b"]).astype(
        q.dtype)
    scores = (matmul("bshd,bthd->bhst", q[..., :dn], kv[..., :dn])
              + matmul("bshd,btd->bhst", q[..., dn:], latent[..., rkv:]))
    scores = scores / (q.shape[-1] ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = matmul("bhst,bthd->bshd", probs.astype(q.dtype), kv[..., dn:])
    return matmul("bshd,hde->bse", o.astype(q.dtype), att["wo"])


def mla_absorbed(q, latent_self, latent_cache, pos, att, cfg,
                 layer: int = 0):
    """One query token a row against its slot's latents.  q [B, H, dn+dr];
    latent_self [B, C] (the current token's); latent_cache [A, B, T, C], the
    STACKED cache, of which attention ``layer``'s slice holds [0, pos-1];
    pos [B] -> [B, d] float32.  A cache of several extents is read in blocks
    up to the batch's longest context, in one pass over the stack itself
    (``ops/latent_attention``: on a TPU a Pallas kernel that fetches block
    ``j + 1`` while block ``j`` is scored, elsewhere ``attend_live_blocks``'
    loop; the shapes decide, no argument does); a cache of ONE extent is
    scored whole, below.
    ``att`` holds ``Wkvb`` whole (``wkv_b [rkv, H, dn+dv]``, LongCat's: its
    halves are sliced out here) or as two leaves (``wk_b [rkv, H, dn]``,
    ``wv_b [rkv, H, dv]``, mistral4's: each product reads its own stack
    where it lies).  The softmax scale is ``(dn+dr)^-0.5``; what else
    multiplies the scores is in ``q`` already (``mla_project``'s
    ``q_factor``)."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if "wkv_b" in att:
        w_k, w_v = att["wkv_b"][..., :dn], att["wkv_b"][..., dn:]
    else:
        w_k, w_v = att["wk_b"], att["wv_b"]
    qt = matmul("bhn,chn->bhc", q[..., :dn], w_k).astype(q.dtype)
    qc = jnp.concatenate([qt, q[..., dn:]], -1)  # [B, H, C]
    scale = q.shape[-1] ** -0.5
    t = latent_cache.shape[2]
    if extent_step(t) < t:
        oc = latent_attention(latent_cache, layer, qc, latent_self, pos,
                              rkv=rkv, scale=scale)
    else:
        s_self = matmul("bhc,bc->bh", qc, latent_self) * scale
        latents = latent_cache[layer]
        scores = matmul("bhc,btc->bht", qc, latents) * scale
        before = jnp.arange(t)[None, None] < pos[:, None, None]
        scores = jnp.where(before, scores, -1e30)
        probs = jax.nn.softmax(
            jnp.concatenate([scores, s_self[..., None]], -1), axis=-1)
        oc = (matmul("bht,btc->bhc", probs[..., :-1].astype(q.dtype),
                     latents[..., :rkv])
              + probs[..., -1:] * latent_self[:, None, :rkv])
    o = matmul("bhc,chv->bhv", oc.astype(q.dtype), w_v)
    return matmul("bhv,hve->be", o.astype(q.dtype), att["wo"])
