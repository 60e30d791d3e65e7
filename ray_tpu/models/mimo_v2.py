"""MiMo-V2-style decoder: window attention (the last ``w`` positions, with a
learned sink) beside full attention in one model, by a per-layer pattern;
query/key heads wider than the value heads; one leading dense layer, then
expert layers that are told which experts they hold.

Source of the sizes: ``huggingface.co/XiaomiMiMo/MiMo-V2.5`` ``config.json``
(``model_type`` ``mimo_v2``, 309B-A15B).  Symbols: ``d`` d_model, ``H`` query
heads of ``D`` (192), ``Dv`` the value head (128), ``Hf`` / ``Hw`` key-value
heads of a full / a window layer (4 / 8), ``R`` the rotary part of a head
(the first ``int(192 x 0.334) = 64`` dimensions), ``w`` the window (128),
``F`` the dense width, ``Fe`` the expert width, ``E`` routed experts, ``k``
a token.  No bias anywhere.

**Model**: every block is ``x = x + attn(RMSNorm(x)); x = x + mlp(RMSNorm(
x))`` (eps 1e-5); ``attn_pattern[i]`` is ``F`` (full) or ``W`` (window),
``mlp_pattern[i]`` is ``D`` (dense) or ``E`` (experts); ``n_layer`` layers are
taken from the FRONT of both.  Then a final RMSNorm and an untied head.  The
residual stream is float32; the matrix products read ``cfg.dtype`` and
accumulate in float32, and what lies between two products is float32,
rounded once where the next product reads it (``layers.matmul``).

**Attention(u, pos)**, both kinds: ``q = u Wq`` ``[H, D]``, ``k = u Wk``
``[Hkv, D]``, ``v = a u Wv`` ``[Hkv, Dv]`` (``a`` = ``value_scale`` 0.707);
``q``, ``k`` rotated on their first ``R`` dimensions in the ``rotate_half``
pairing (dimension ``j`` with ``j + R/2``, frequency ``theta^(-2j/R)``),
``theta`` 1e7 in a full layer, 1e4 in a window layer; scores ``q k^T /
sqrt(D)`` in float32, query head ``h`` reads key-value head ``h // (H /
Hkv)``; ``out = concat_h(p v) Wo``, ``Wo [H Dv, d]``.  **Full**: causal, ``p =
softmax(scores)``.  **Window**: key ``j`` is visible to query ``i`` iff ``0 <=
i - j < w``; a learned scalar ``s_h`` a head is one more logit: ``p =
softmax([scores, s_h])`` with that column dropped, so a row sums to less
than one.

A sequence's window layers compute the BAND alone (``window_attention``:
queries of block ``c`` of ``w`` positions against the keys of blocks ``c - 1``
and ``c``), its full layers score in query blocks against the keys up to the
block's end (``full_attention``): no ``[S, S]`` array of scores exists.

**Dense MLP(u)**: ``(silu(u Wg) * (u Wu)) Wd``.  **Experts(u)**: ``s =
sigmoid(float32(u) Wr)`` over all ``E``; ``sel`` = the ``k`` largest of ``s +
bias``; ``w_i = s_i / sum_{j in sel} s_j``; ``out = sum_{i in sel} w_i
SwiGLU_i(u)``; no shared expert, no capacity, no drop.  **The share**
(``expert_share.py``): the layer holds ``experts_held`` experts from
``expert_offset`` (``params["experts"]``, its own subtree), routes over all
``E`` and sums ITS experts' part; the shares of all chips add up to the
whole layer.

Not here: the three multi-token-prediction layers and the vision and audio
towers; the model's own logits depend on neither.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of layer
(``full``, ``window``, ``dense``, ``moe``), each as long as the patterns have
layers of that kind; a model of fewer layers reads the front of each stack.
Device operations carry ``jax.named_scope``s ``mimo.embed`` (the token gather,
and the positions and mask a program makes once from its inputs),
``mimo.attn_full``, ``mimo.attn_window`` (norm to residual, and the cache
write), ``mimo.moe`` (norm, router, held experts, residual, counts),
``mimo.mlp`` and ``mimo.head`` (final norm + vocabulary product).  Routing is counted in
the program: ``routed_total`` (choices made by live tokens), ``routed_held``
(those on experts held here) and ``experts_touched`` (distinct held experts
a layer ran, summed over layers).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.decode_attention import NEG_INF
from .expert_share import (LOOP_COUNT_NAMES, held_choices, held_experts,
                           held_experts_dense, loop_counts,
                           runs_every_held_expert, sigmoid_route)
from .layers import add_counts, ffn, matmul, ring_of, rmsnorm, rope_half

# hybrid_layer_pattern (0 = full) and moe_layer_freq (0 = dense), as letters
PUBLISHED_ATTN = "F" + "WWWWF" + "WWWWWF" * 7
PUBLISHED_MLP = "D" + "E" * 47
COUNT_NAMES = ("routed_total", "routed_held", "experts_touched",
               *LOOP_COUNT_NAMES)
# a kind of attention -> its stack under params["blocks"]
STACK = {"F": "full", "W": "window"}
# Query rows of one block of a full layer's scores over a sequence.
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    attn_pattern: str = PUBLISHED_ATTN
    mlp_pattern: str = PUBLISHED_MLP
    n_layer: int = 48  # layers taken from the front of both patterns
    d_model: int = 4096
    n_head: int = 64
    n_kv_head: int = 4  # a full layer's
    n_kv_head_window: int = 8
    head_dim: int = 192  # queries and keys
    v_head_dim: int = 128
    rotary_dim: int = 64  # int(head_dim x partial_rotary_factor 0.334)
    rope_theta: float = 1e7  # full layers
    rope_theta_window: float = 1e4
    window: int = 128
    value_scale: float = 0.707
    d_ff: int = 16384
    d_expert: int = 2048
    n_routed_experts: int = 256  # the router's width, whatever is held
    experts_held: int = 256
    expert_offset: int = 0
    top_k: int = 8
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.attn_pattern) - set("FW") or set(self.mlp_pattern) - set(
                "DE") or len(self.attn_pattern) != len(self.mlp_pattern):
            raise ValueError(
                f"attn_pattern {self.attn_pattern!r} (F full, W window) and "
                f"mlp_pattern {self.mlp_pattern!r} (D dense, E experts) name "
                "the same layers, a letter each")
        if not 0 < self.n_layer <= len(self.attn_pattern):
            raise ValueError(f"n_layer {self.n_layer} of patterns of "
                             f"{len(self.attn_pattern)} layers")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")

    @property
    def attn_kinds(self) -> str:
        """The attention kinds of the layers this model runs."""
        return self.attn_pattern[:self.n_layer]

    @property
    def mlp_kinds(self) -> str:
        return self.mlp_pattern[:self.n_layer]

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_head if kind == "F" else self.n_kv_head_window

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == "F" else self.rope_theta_window

    @classmethod
    def tiny(cls, **kw) -> "MimoV2Config":
        for key, value in dict(
                vocab_size=512, attn_pattern="FWWWWWF", mlp_pattern="DEEEEEE",
                n_layer=7, d_model=64, n_head=8, n_kv_head=2,
                n_kv_head_window=4, head_dim=24, v_head_dim=16, rotary_dim=8,
                window=8, d_ff=128, d_expert=32, n_routed_experts=16,
                experts_held=16, top_k=4).items():
            kw.setdefault(key, value)
        return cls(**kw)


def mimo_v2_init(key, cfg: MimoV2Config):
    """Random weights with every stack as long as the patterns have layers
    of its kind; the sinks standard normal."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    H, D, Dv = cfg.n_head, cfg.head_dim, cfg.v_head_dim
    n = {kind: (cfg.attn_pattern + cfg.mlp_pattern).count(kind)
         for kind in "FWDE"}
    s, so = 0.02, 0.02 / (2 * len(cfg.attn_pattern)) ** 0.5
    keys = iter(jax.random.split(key, 24))

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def attention(kind):
        layers, hkv = n[kind], cfg.kv_heads(kind)
        return {
            "rms": jnp.ones((layers, d), dt),
            "wq": init((layers, d, H, D), s),
            "wk": init((layers, d, hkv, D), s),
            "wv": init((layers, d, hkv, Dv), s),
            "wo": init((layers, H, Dv, d), so),
        }

    return {
        "wte": init((cfg.vocab_size, d), s),
        "blocks": {
            "full": attention("F"),
            "window": dict(attention("W"),
                           sink=init((n["W"], H), 1.0, jnp.float32)),
            "dense": {
                "rms": jnp.ones((n["D"], d), dt),
                "w_gate": init((n["D"], d, cfg.d_ff), s),
                "w_up": init((n["D"], d, cfg.d_ff), s),
                "w_down": init((n["D"], cfg.d_ff, d), so),
            },
            "moe": {
                "rms": jnp.ones((n["E"], d), dt),
                # Router and its load-balancing bias stay float32.
                "router": init((n["E"], d, cfg.n_routed_experts), s,
                               jnp.float32),
                "router_bias": jnp.zeros((n["E"], cfg.n_routed_experts),
                                         jnp.float32),
            },
        },
        "experts": {
            "w_gate": init((n["E"], cfg.experts_held, d, cfg.d_expert), s),
            "w_up": init((n["E"], cfg.experts_held, d, cfg.d_expert), s),
            "w_down": init((n["E"], cfg.experts_held, cfg.d_expert, d), so),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), s),
    }


def mimo_v2_param_axes():
    """Logical sharding axes (leading None = a kind's layer-stack axis)."""
    attention = {
        "rms": P(None, "norm"),
        "wq": P(None, "embed", "heads", "kv"),
        "wk": P(None, "embed", "heads", "kv"),
        "wv": P(None, "embed", "heads", "kv"),
        "wo": P(None, "heads", "kv", "embed"),
    }
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "full": dict(attention),
            "window": dict(attention, sink=P(None, "heads")),
            "dense": {
                "rms": P(None, "norm"),
                "w_gate": P(None, "embed", "mlp"),
                "w_up": P(None, "embed", "mlp"),
                "w_down": P(None, "mlp", "embed"),
            },
            "moe": {
                "rms": P(None, "norm"),
                "router": P(None, "embed", None),
                "router_bias": P(None, None),
            },
        },
        "experts": {
            "w_gate": P(None, "expert", "embed", "mlp"),
            "w_up": P(None, "expert", "embed", "mlp"),
            "w_down": P(None, "expert", "mlp", "embed"),
        },
        "rms_f": P("norm"),
        "lm_head": P("vocab", "embed"),
    }


# ---------------------------------------------------------------- attention
def attention_project(y, att, i: int, positions, kind: str,
                      cfg: MimoV2Config):
    """y ``[..., d]`` in ``cfg.dtype`` at ``positions`` -> roped q ``[..., H,
    D]``, roped k ``[..., Hkv, D]`` and v ``[..., Hkv, Dv]`` (scaled), in y's
    dtype."""
    q = matmul("...e,ehd->...hd", y, att["wq"][i])
    k = matmul("...e,ekd->...kd", y, att["wk"][i])
    v = cfg.value_scale * matmul("...e,ekd->...kd", y, att["wv"][i])
    q = rope_half(q, positions, cfg.theta(kind), cfg.rotary_dim)
    k = rope_half(k, positions, cfg.theta(kind), cfg.rotary_dim)
    return q.astype(y.dtype), k.astype(y.dtype), v.astype(y.dtype)


def full_attention(q, k, v, block: int = QUERY_BLOCK):
    """Causal attention of ``[B, S]`` tokens over themselves, scored in
    blocks of ``block`` queries against the keys up to the block's end.  q
    ``[B, S, H, D]``, k ``[B, S, Hkv, D]``, v ``[B, S, Hkv, Dv]`` -> ``[B, S,
    H, Dv]`` float32."""
    bsz, s, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(bsz, s, hkv, h // hkv, d)
    out = []
    for start in range(0, s, block):
        end = min(start + block, s)
        scores = matmul("bskgd,btkd->bkgst", qg[:, start:end],
                        k[:, :end]) / d ** 0.5
        causal = (jnp.arange(start, end)[:, None] >= jnp.arange(end)[None])
        probs = jax.nn.softmax(jnp.where(causal, scores, NEG_INF), axis=-1)
        out.append(matmul("bkgst,btkv->bskgv", probs.astype(q.dtype),
                          v[:, :end]))
    return jnp.concatenate(out, axis=1).reshape(bsz, s, h, v.shape[-1])


def window_attention(q, k, v, sink, window: int):
    """Attention of ``[B, S]`` tokens over the last ``window`` positions,
    themselves included, with the sink's logit a head: the band alone.  The
    sequence is cut into blocks of ``window``; the queries of block ``c`` see
    keys of blocks ``c - 1`` and ``c`` only, so the scores are ``[.., S / w,
    w, 2 w]``.  Shapes as ``full_attention``, sink ``[H]`` float32."""
    bsz, s, h, d = q.shape
    hkv, g = k.shape[2], h // k.shape[2]
    pad = -s % window
    if pad:  # keys beyond a query are never seen; their queries are cut off
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    nc = (s + pad) // window

    def with_previous(a):  # [B, S, Hkv, X] -> [B, c, 2 w, Hkv, X]
        a = a.reshape(bsz, nc, window, hkv, a.shape[-1])
        before = jnp.pad(a[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)
        return jnp.concatenate([before, a], axis=2)

    qb = q.reshape(bsz, nc, window, hkv, g, d)
    scores = matmul("bcqkgd,bctkd->bckgqt", qb, with_previous(k)) / d ** 0.5
    # query ``q`` of a block is at ``w + q`` of its two blocks of keys
    behind = (window + jnp.arange(window)[:, None]
              - jnp.arange(2 * window)[None])
    band = (behind >= 0) & (behind < window)
    # the first block has no block before it
    there = (jnp.arange(nc)[:, None, None] > 0) | (
        jnp.arange(2 * window) >= window)
    mask = (band & there)[None, :, None, None]  # [1, c, 1, 1, w, 2 w]
    scores = jnp.where(mask, scores, NEG_INF)
    column = jnp.broadcast_to(sink.reshape(hkv, g, 1, 1),
                              scores.shape[:-1] + (1,))
    probs = jax.nn.softmax(jnp.concatenate([scores, column], -1), axis=-1)
    o = matmul("bckgqt,bctkv->bcqkgv", probs[..., :-1].astype(q.dtype),
               with_previous(v))
    return o.reshape(bsz, nc * window, h, v.shape[-1])[:, :s]


# ------------------------------------------------------------------ experts
def moe(u, live, params, i: int, cfg: MimoV2Config):
    """Expert layer ``i``'s share on this chip.  ``u [N, d]`` normed tokens
    in float32 (the router reads them as they are, the experts in
    ``cfg.dtype``), ``live [N]`` bool (a padded or idle row chooses nothing:
    it touches no expert and is not counted) -> (``[N, d]`` float32,
    counts).  A decode step of 64 slots (64 x 8 / 256 = 2.0 choices an
    expert, seven in eight of the sixteen touched if the rows choose each
    on its own) runs every held expert in batched products: 1.11 ms a layer
    on the v5e whatever was chosen, which the loop over the touched ones
    (a prefill's way) reaches at 13.9 of 16, a turn of its one-chunk form
    being 78 us (PERF.md, PR 54: a tie at these shapes, so the step stays
    where PR 45 put it; the served rows touch 67 %, where the loop would be
    ~0.25 ms a layer faster: ROADMAP S5).  The way is read off the SHAPES
    (``expert_share.runs_every_held_expert``)."""
    blocks, experts = params["blocks"]["moe"], params["experts"]
    with jax.named_scope("mimo.moe"):
        sel, w = sigmoid_route(u, blocks["router"][i],
                               blocks["router_bias"][i], cfg.top_k)
        held, hit, w_held = held_choices(
            sel, w, live, cfg.expert_offset, cfg.experts_held)
        ud = u.astype(jnp.dtype(cfg.dtype))
        dense = runs_every_held_expert(u.shape[0], cfg.top_k,
                                       cfg.n_routed_experts)
        if dense:
            y = held_experts_dense(ud, w_held, experts, i)
        else:  # [i, e] inside the loop: expert_share.py
            y = held_experts(ud, hit, w_held, lambda x, e: ffn(
                x, experts["w_gate"][i, e], experts["w_up"][i, e],
                experts["w_down"][i, e]))
        return y, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit, looped=not dense),
        }


# -------------------------------------------------------------------- model
def leaf_scope(leaf: str) -> str:
    """The scope of the attention that keeps cache leaf ``leaf``."""
    return "mimo.attn_window" if leaf.endswith("_win") else "mimo.attn_full"


def run_layers(params, x, live, attend, cfg: MimoV2Config):
    """The blocks of the first ``cfg.n_layer`` layers over the float32 stream
    ``x [..., d]``.  ``attend(kind, i, y)`` is the attention of the ``i``-th
    layer of its kind (``F`` / ``W``) on the normed state in ``cfg.dtype`` (a
    sequence's or one decode step's: the caller's, which keeps what the
    cache needs) -> ``[..., H, Dv]``; ``live`` has ``x``'s leading shape.
    Every weight is taken as ``stack[i]`` where it is used (a layer's slice
    taken first is a copy of the layer)."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    total = dict.fromkeys(COUNT_NAMES, jnp.zeros((), jnp.int32))
    seen = dict.fromkeys("FWDE", 0)
    for attn_kind, mlp_kind in zip(cfg.attn_kinds, cfg.mlp_kinds):
        i, j = seen[attn_kind], seen[mlp_kind]
        seen[attn_kind] += 1
        seen[mlp_kind] += 1
        with jax.named_scope("mimo.attn_" + STACK[attn_kind]):
            att = blocks[STACK[attn_kind]]
            y = rmsnorm(x, att["rms"][i], cfg.rms_eps).astype(dt)
            o = attend(attn_kind, i, y).astype(dt)
            x = x + matmul("...hv,hve->...e", o, att["wo"][i])
        if mlp_kind == "D":
            with jax.named_scope("mimo.mlp"):
                dense = blocks["dense"]
                u = rmsnorm(x, dense["rms"][j], cfg.rms_eps).astype(dt)
                x = x + ffn(u, dense["w_gate"][j], dense["w_up"][j],
                            dense["w_down"][j])
        else:
            with jax.named_scope("mimo.moe"):
                u = rmsnorm(x, blocks["moe"]["rms"][j], cfg.rms_eps)  # f32
                u, rows = u.reshape(-1, u.shape[-1]), live.reshape(-1)
            y, counts = moe(u, rows, params, j, cfg)
            with jax.named_scope("mimo.moe"):
                x = x + y.reshape(x.shape)
                total = add_counts(total, counts)
    return x, total


def mimo_v2_forward(params, tokens, lengths, cfg: MimoV2Config):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S,
    d]``, what a cache holds of it, head-major: ``k`` ``[Lf, B, Hf, S, D]`` /
    ``v`` ``[.., Dv]`` of the full layers and the rings ``k_win`` ``[Lw, B, Hw,
    w, D]`` / ``v_win`` of the window layers at each row's TRUE length
    (``ring_of``), routing counts of the positions ``< length``)."""
    blocks = params["blocks"]
    with jax.named_scope("mimo.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        live = positions[None] < lengths[:, None]
    kept = {"k": [], "v": [], "k_win": [], "v_win": []}

    def attend(kind, i, y):
        att = blocks[STACK[kind]]
        q, k, v = attention_project(y, att, i, positions, kind, cfg)
        if kind == "F":
            kept["k"].append(k.transpose(0, 2, 1, 3))
            kept["v"].append(v.transpose(0, 2, 1, 3))
            return full_attention(q, k, v)
        kept["k_win"].append(ring_of(k, lengths, cfg.window))
        kept["v_win"].append(ring_of(v, lengths, cfg.window))
        return window_attention(q, k, v, att["sink"][i], cfg.window)

    x, counts = run_layers(params, x, live, attend, cfg)
    with jax.named_scope("mimo.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    stacked = {}
    for name, v in kept.items():
        if v:
            with jax.named_scope(leaf_scope(name)):
                stacked[name] = jnp.stack(v)
    return x, stacked, counts


def mimo_v2_apply(params, tokens, cfg: MimoV2Config, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "mimo_v2 runs one chip's share of a layer; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = mimo_v2_forward(params, tokens, lengths, cfg)
    with jax.named_scope("mimo.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def mimo_v2_loss(params, tokens, cfg: MimoV2Config, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = mimo_v2_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
