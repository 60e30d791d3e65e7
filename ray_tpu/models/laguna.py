"""Laguna-style decoder: grouped-query attention whose window layers carry
MORE query heads than its full layers, a gate a head on the attention's
output, rotary by kind (YaRN on half of a full layer's head, plain on all
of a window layer's), one leading dense layer, then expert layers that add
a shared expert to a held share of many small routed ones.

Source of the sizes: ``huggingface.co/poolside/Laguna-S-2.1`` ``config.json``
(``model_type`` ``laguna``, 117.6 B parameters, ~8.4 B a token).  Symbols:
``d`` d_model, ``Hf`` / ``Hw`` query heads of a full / a window layer (48 /
72), ``Hkv`` key-value heads (8), ``D`` the head (128), ``w`` the window
(512), ``F`` the dense width, ``Fe`` the routed and the shared experts'
width, ``E`` routed experts, ``k`` a token.  No bias anywhere.

**Model**: every block is ``x = x + attn(RMSNorm(x)); x = x + ff(RMSNorm(
x))`` (eps 1e-6); ``attn_pattern[i]`` is ``F`` (full) or ``W`` (window),
``mlp_pattern[i]`` is ``D`` (dense) or ``E`` (experts); ``n_layer`` layers are
taken from the FRONT of both.  Then a final RMSNorm and an untied head.  The
residual stream is float32; the matrix products read ``cfg.dtype`` and
accumulate in float32, and what lies between two products is float32,
rounded once where the next product reads it (``layers.matmul``).

**Attention(u, pos)** of a layer with ``H`` query heads (its kind's): ``q =
u Wq`` ``[H, D]``, ``k = u Wk``, ``v = u Wv`` ``[Hkv, D]``; ``q`` and ``k`` each
RMS-normalised over ``D`` with a learned weight a layer; both rotated by the
layer's kind (below); ``score_h(i, j) = q_h(i) . k_{h // (H / Hkv)}(j) /
sqrt(D)`` (query groups of 6 or 9), float32 softmax over the visible ``j``:
full ``j <= i``; window ``0 <= i - j < w``, no sink; ``o_h = P v``.  **The
gate**: ``g = sigmoid(u Wg)``, ``Wg [d, H]``: one scalar a head a token, from
the layer's NORMED INPUT; ``out = concat_h(g_h o_h) Wo``, ``Wo [H D, d]``.

**Rotary** (``rotate_half`` pairing, ``layers.rope_half``).  A window layer:
base ``rope_theta_window`` (1e4) on all ``D`` dimensions, no scaling.  A full
layer: the FIRST ``rotary_dim`` (64) dimensions of a head, the others pass;
YaRN (``layers.yarn_inv_freq`` over the ``rotary_dim / 2`` pairs: base
``rope_theta`` 5e5, ``rope_factor`` 128 over ``rope_original_max`` 8192
positions, ``beta_fast`` 32 / ``beta_slow`` 1: pairs 0-9 keep their frequency,
18-31 turn 128 x slower, a linear ramp between), and cos and sin carry
``rope_attention_factor`` ``m`` = 1.4852 (``0.1 ln 128 + 1``; the config gives
it), so the rotated half of a score carries ``m^2`` and the half that passes
carries 1.

**Dense FF(u)**: ``(silu(u Wg) * (u Wu)) Wd``.  **Experts(u)**: ``s =
sigmoid(float32(u) Wr)`` over all ``E``; ``sel`` = the ``k`` largest of ``s +
bias``; ``w = a s_sel / sum(s_sel)`` (``a`` = ``routed_scaling_factor`` 2.5);
``y = SwiGLU_shared(u) + sum_{e in sel} w_e SwiGLU_e(u)``, the shared expert
ungated and unscaled; no capacity, no drop.  **The share**
(``expert_share.py``): the layer holds ``experts_held`` experts from
``expert_offset`` (``params["experts"]``, its own subtree), routes over all
``E``, sums ITS experts' part and adds the shared expert, which every chip
computes for the tokens that live on it.  The held parts of all shares and
the shared expert counted once add up to the whole layer.

A sequence's attention is scored a tile of 512 queries by 512 keys at a
time (``layers.blocked_attention``, grouped: the 8 key-value heads are
never repeated): a full layer's tiles stop at the diagonal and at the longest
prompt, a window layer's are the BAND alone (two key tiles a query tile), so
no array grows with the square of the sequence.

Parameters: ``params["blocks"]`` holds one layer-stack a KIND of layer
(``full`` with ``wq [Lf, d, Hf, D]``, ``window`` with ``[Lw, d, Hw, D]``,
``dense``, ``moe``), each as long as the patterns have layers of that kind; a
model of fewer layers reads the front of each stack.  Device operations
carry ``jax.named_scope``s ``laguna.embed`` (the token gather, and the
positions and masks a program makes once from its inputs),
``laguna.attn_full``, ``laguna.attn_window`` (norm to residual, and the cache
write), ``laguna.moe`` (norm, router, held experts, counts), ``laguna.shared``
(the shared expert and the residual), ``laguna.mlp`` and ``laguna.head``
(final norm + vocabulary product).  Routing is counted in
the program: ``routed_total`` (choices made by live tokens), ``routed_held``
(those on experts held here) and ``experts_touched`` (distinct held experts
a layer ran, summed over layers).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .expert_share import (LOOP_COUNT_NAMES, held_choices, held_experts,
                           held_experts_dense, loop_counts,
                           runs_every_held_expert, sigmoid_route)
from .layers import (add_counts, blocked_attention, ffn, matmul, ring_of,
                     rmsnorm, rope_half, scan_or_call, yarn_inv_freq)

# layer_types (full at l mod 4 == 0) and mlp_layer_types, as letters
PUBLISHED_ATTN = "FWWW" * 12
PUBLISHED_MLP = "D" + "E" * 47
COUNT_NAMES = ("routed_total", "routed_held", "experts_touched",
               *LOOP_COUNT_NAMES)
# a kind of layer -> its stack under params["blocks"]
STACK = {"F": "full", "W": "window", "D": "dense", "E": "moe"}
# a kind of attention -> its cache leaves (``laguna_decode.py``)
LEAVES = {"F": ("k", "v"), "W": ("k_win", "v_win")}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    attn_pattern: str = PUBLISHED_ATTN
    mlp_pattern: str = PUBLISHED_MLP
    n_layer: int = 48  # layers taken from the front of both patterns
    d_model: int = 3072
    n_head: int = 48  # a full layer's query heads
    n_head_window: int = 72
    n_kv_head: int = 8
    head_dim: int = 128
    window: int = 512
    rotary_dim: int = 64  # full layers: head_dim x partial_rotary_factor 0.5
    rope_theta: float = 5e5  # full layers, YaRN-scaled
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4852030263919618
    rope_theta_window: float = 1e4  # window layers: all of the head, plain
    d_ff: int = 12288
    d_expert: int = 1024  # routed and shared experts alike
    n_routed_experts: int = 256  # the router's width, whatever is held
    experts_held: int = 256
    expert_offset: int = 0
    top_k: int = 10
    routed_scaling_factor: float = 2.5
    rms_eps: float = 1e-6
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
        if self.n_head % self.n_kv_head or self.n_head_window % self.n_kv_head:
            raise ValueError(
                f"{self.n_head} / {self.n_head_window} query heads over "
                f"{self.n_kv_head} key-value heads: no whole groups")

    @property
    def attn_kinds(self) -> str:
        """The attention kinds of the layers this model runs."""
        return self.attn_pattern[:self.n_layer]

    @property
    def mlp_kinds(self) -> str:
        return self.mlp_pattern[:self.n_layer]

    def heads(self, kind: str) -> int:
        return self.n_head if kind == "F" else self.n_head_window

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """The published GROUPS of 6 and 9 over two key-value heads; a window
        of 8 and sixteen trained positions scaled by 8, so that a hundred
        positions wrap the ring a dozen times and turn the slowed pairs (1-3
        of a full layer's 4) by radians."""
        for key, value in dict(
                vocab_size=512, attn_pattern="FWWWF", mlp_pattern="DEEEE",
                n_layer=5, d_model=64, n_head=12, n_head_window=18,
                n_kv_head=2, head_dim=16, window=8, rotary_dim=8,
                rope_theta=1e4, rope_factor=8.0, rope_original_max=16,
                rope_attention_factor=0.1 * math.log(8.0) + 1.0,
                d_ff=128, d_expert=32, n_routed_experts=16, experts_held=16,
                top_k=4).items():
            kw.setdefault(key, value)
        return cls(**kw)


# --------------------------------------------------------------- parameters
def kind_counts(cfg: LagunaConfig) -> dict:
    """Layers of each kind in the whole patterns: the stacks' lengths."""
    return {kind: (cfg.attn_pattern + cfg.mlp_pattern).count(kind)
            for kind in "FWDE"}


def laguna_init(key, cfg: LagunaConfig):
    """Random weights with every stack as long as the patterns have layers
    of its kind."""
    d, dt, D, Fe = cfg.d_model, jnp.dtype(cfg.dtype), cfg.head_dim, cfg.d_expert
    n = kind_counts(cfg)
    s, so = 0.02, 0.02 / (2 * len(cfg.attn_pattern)) ** 0.5
    keys = iter(jax.random.split(key, 32))

    def init(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def attention(kind):
        layers, h, hkv = n[kind], cfg.heads(kind), cfg.n_kv_head
        return {
            "rms": jnp.ones((layers, d), dt),
            "wq": init((layers, d, h, D), s),
            "wk": init((layers, d, hkv, D), s),
            "wv": init((layers, d, hkv, D), s),
            "q_norm": jnp.ones((layers, D), dt),
            "k_norm": jnp.ones((layers, D), dt),
            "wg": init((layers, d, h), s),
            "wo": init((layers, h, D, d), so),
        }

    return {
        "wte": init((cfg.vocab_size, d), s),
        "blocks": {
            "full": attention("F"),
            "window": attention("W"),
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
                # The shared expert.
                "w_gate": init((n["E"], d, Fe), s),
                "w_up": init((n["E"], d, Fe), s),
                "w_down": init((n["E"], Fe, d), so),
            },
        },
        "experts": {
            "w_gate": init((n["E"], cfg.experts_held, d, Fe), s),
            "w_up": init((n["E"], cfg.experts_held, d, Fe), s),
            "w_down": init((n["E"], cfg.experts_held, Fe, d), so),
        },
        "rms_f": jnp.ones((d,), dt),
        "lm_head": init((cfg.vocab_size, d), s),
    }


def laguna_param_axes():
    """Logical sharding axes (leading None = a kind's layer-stack axis)."""
    attention = {
        "rms": P(None, "norm"),
        "wq": P(None, "embed", "heads", "kv"),
        "wk": P(None, "embed", "heads", "kv"),
        "wv": P(None, "embed", "heads", "kv"),
        "q_norm": P(None, "norm"),
        "k_norm": P(None, "norm"),
        "wg": P(None, "embed", "heads"),
        "wo": P(None, "heads", "kv", "embed"),
    }
    ff = {
        "rms": P(None, "norm"),
        "w_gate": P(None, "embed", "mlp"),
        "w_up": P(None, "embed", "mlp"),
        "w_down": P(None, "mlp", "embed"),
    }
    return {
        "wte": P(None, "embed"),
        "blocks": {
            "full": dict(attention),
            "window": dict(attention),
            "dense": dict(ff),
            "moe": dict(ff, router=P(None, "embed", None),
                        router_bias=P(None, None)),
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
def rotary(x, positions, kind: str, cfg: LagunaConfig):
    """The kind's rotary on ``x [..., heads, D]`` float32: a window layer's
    plain table over the whole head, a full layer's YaRN table over the first
    ``rotary_dim`` dimensions with the attention factor on cos and sin."""
    if kind == "W":
        return rope_half(x, positions, cfg.rope_theta_window, cfg.head_dim)
    return rope_half(
        x, positions, cfg.rope_theta, cfg.rotary_dim,
        inv_freq=yarn_inv_freq(
            cfg.rotary_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow),
        factor=cfg.rope_attention_factor)


def attention_project(y, att, i, positions, kind: str, cfg: LagunaConfig):
    """y ``[..., d]`` in ``cfg.dtype`` at ``positions`` -> normed and roped q
    ``[..., H, D]`` and k ``[..., Hkv, D]``, v ``[..., Hkv, D]``, in y's dtype;
    ``att`` the kind's stack, ``i`` the layer's place in it."""
    q = matmul("...e,ehd->...hd", y, att["wq"][i])
    k = matmul("...e,ekd->...kd", y, att["wk"][i])
    v = matmul("...e,ekd->...kd", y, att["wv"][i])
    q = rotary(rmsnorm(q, att["q_norm"][i], cfg.rms_eps), positions, kind,
               cfg)
    k = rotary(rmsnorm(k, att["k_norm"][i], cfg.rms_eps), positions, kind,
               cfg)
    return q.astype(y.dtype), k.astype(y.dtype), v.astype(y.dtype)


# ------------------------------------------------------------------ experts
def moe(u, live, params, i, cfg: LagunaConfig):
    """Expert layer ``i``'s share on this chip: its held experts' part of the
    routed sum + the shared expert.  ``u [N, d]`` normed tokens in float32
    (the router reads them as they are, the experts in ``cfg.dtype``), ``live
    [N]`` bool (a padded or idle row chooses nothing: it touches no held
    expert and is not counted) -> (``[N, d]`` float32, counts).  A decode step
    of 32 slots (32 x 10 / 256 = 1.25 choices an expert: independent rows
    touch 72 % of the held experts, the served ones 70 %) takes the loop over
    the touched ones like a prefill, in its one-chunk form: a turn reads ONE
    expert's 19 MB for the whole batch, 36 us on the v5e, and 11-12 turns
    cost 0.42-0.46 ms where the batched products over all sixteen took 0.47
    whatever was chosen (PERF.md, PR 54).  The way is read off the SHAPES,
    never off the load (``expert_share.runs_every_held_expert``)."""
    blocks, experts = params["blocks"]["moe"], params["experts"]
    with jax.named_scope("laguna.moe"):
        ud = u.astype(jnp.dtype(cfg.dtype))
        sel, w = sigmoid_route(u, blocks["router"][i],
                               blocks["router_bias"][i], cfg.top_k,
                               cfg.routed_scaling_factor)
        held, hit, w_held = held_choices(
            sel, w, live, cfg.expert_offset, cfg.experts_held)
        dense = runs_every_held_expert(u.shape[0], cfg.top_k,
                                       cfg.n_routed_experts)
        if dense:
            y = held_experts_dense(ud, w_held, experts, i)
        else:  # [i, e] inside the loop: expert_share.py
            y = held_experts(ud, hit, w_held, lambda x, e: ffn(
                x, experts["w_gate"][i, e], experts["w_up"][i, e],
                experts["w_down"][i, e]))
    with jax.named_scope("laguna.shared"):
        y = y + ffn(ud, blocks["w_gate"][i], blocks["w_up"][i],
                    blocks["w_down"][i])
    with jax.named_scope("laguna.moe"):
        return y, {  # int32 scalars
            "routed_total": live.sum() * cfg.top_k,
            "routed_held": held.sum(),
            "experts_touched": hit.any(0).sum(),
            **loop_counts(hit, looped=not dense),
        }


# -------------------------------------------------------------------- model
def block(params, x, live, kinds: str, i, j, attend, cfg: LagunaConfig):
    """One block of kinds ``kinds`` (attention's letter, then the MLP's) over
    the float32 stream ``x [..., d]`` -> (the stream after it, routing counts
    or ``None``).  ``i`` / ``j``: the layer's place in its attention / MLP
    stack, Python ints in the decode step, whose layers are written out, a
    loop's counter in a forward over a sequence (``laguna_forward``); every
    weight is taken as ``stack[i]`` where it is used (a layer's slice taken
    first is a copy of the layer).  ``attend(att, y)`` is the attention of
    the normed state in ``cfg.dtype`` (a sequence's or one decode step's: the
    caller's, which keeps what the cache needs) -> ``[..., H, D]``; the gate
    and the output projection are here.  ``live`` has ``x``'s leading
    shape."""
    blocks, dt = params["blocks"], jnp.dtype(cfg.dtype)
    attn_kind, mlp_kind = kinds
    with jax.named_scope("laguna.attn_" + STACK[attn_kind]):
        att = blocks[STACK[attn_kind]]
        y = rmsnorm(x, att["rms"][i], cfg.rms_eps).astype(dt)
        gate = jax.nn.sigmoid(matmul("...e,eh->...h", y, att["wg"][i]))
        o = attend(att, y).astype(jnp.float32) * gate[..., None]
        x = x + matmul("...hd,hde->...e", o.astype(dt), att["wo"][i])
    if mlp_kind == "D":
        with jax.named_scope("laguna.mlp"):
            dense = blocks["dense"]
            u = rmsnorm(x, dense["rms"][j], cfg.rms_eps).astype(dt)
            return x + ffn(u, dense["w_gate"][j], dense["w_up"][j],
                           dense["w_down"][j]), None
    with jax.named_scope("laguna.moe"):
        u = rmsnorm(x, blocks["moe"]["rms"][j], cfg.rms_eps)  # float32
        u, live = u.reshape(-1, u.shape[-1]), live.reshape(-1)
    y, counts = moe(u, live, params, j, cfg)
    with jax.named_scope("laguna.shared"):  # the sum's last term
        return x + y.reshape(x.shape), counts


def layer_runs(cfg: LagunaConfig):
    """The layers that run as maximal runs of one kind: ``(kinds, first
    place in the attention stack, first place in the MLP stack, layers)``.
    The published nine are ``FD``, ``WE`` x 3, ``FE``, ``WE`` x 3, ``FE``."""
    runs, seen = [], dict.fromkeys("FWDE", 0)
    for kinds in zip(cfg.attn_kinds, cfg.mlp_kinds):
        if runs and runs[-1][0] == kinds:
            runs[-1][3] += 1
        else:
            runs.append([kinds, seen[kinds[0]], seen[kinds[1]], 1])
        for kind in kinds:
            seen[kind] += 1
    return [tuple(run) for run in runs]


def layer_plan(cfg: LagunaConfig):
    """``layer_runs`` with what repeats folded: ``(group of runs, times it
    repeats)``, a group being consecutive runs whose kinds and lengths come
    again right after it.  The published nine layers are ``[FD] x 1`` and
    ``[WE x 3, FE] x 2``: three bodies, whatever the depth."""
    runs, plan, r = layer_runs(cfg), [], 0
    shape = [(kinds, layers) for kinds, _, _, layers in runs]
    while r < len(runs):
        best = (1, 1)  # (runs in the group, repeats), most layers folded
        for g in range(1, (len(runs) - r) // 2 + 1):
            c = 1
            while shape[r + c * g:r + (c + 1) * g] == shape[r:r + g]:
                c += 1
            if c > 1 and g * c > best[0] * best[1]:
                best = (g, c)
        plan.append((runs[r:r + best[0]], best[1]))
        r += best[0] * best[1]
    return plan


def laguna_forward(params, tokens, lengths, cfg: LagunaConfig):
    """tokens ``[B, S]``, lengths ``[B]`` -> (final normed state ``[B, S,
    d]``, what a cache holds of it, head-major: ``k`` / ``v`` ``[Lf, B, Hkv, S,
    D]`` of the full layers and the rings ``k_win`` / ``v_win`` ``[Lw, B, Hkv, w,
    D]`` of the window layers at each row's TRUE length (``ring_of``), routing
    counts of the positions ``< length``).  Rows at or beyond the longest
    prompt's last query block carry no attention (``blocked_attention``).  A
    run of layers of one kind is ONE loop's body, and so is a group of runs
    that repeats (``layer_plan``; ``lax.scan`` in ``lax.scan``): a sequence's
    products are bound by compute, so a layer's weights may be sliced out of
    their stacks as they are needed, the program is as long as the kinds
    that differ (three bodies for the published nine layers or for all 48;
    seven rungs are compiled a replica) and a rung's temporaries are one
    layer's."""
    with jax.named_scope("laguna.embed"):
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        live = positions[None] < lengths[:, None]
        longest = jnp.max(lengths)

    def one_run(carry, run, period, strides):
        """The ``layers`` layers of one run in period ``period`` of its
        group -> (carry, what the cache keeps of each: two ``[layers, B,
        Hkv, S or w, D]``)."""
        kinds, first_i, first_j, layers = run
        full = kinds[0] == "F"

        def one_layer(carry, t):
            x, total = carry
            i = first_i + period * strides[kinds[0]] + t
            j = first_j + period * strides[kinds[1]] + t
            held = []

            def attend(att, y):
                q, k, v = attention_project(y, att, i, positions, kinds[0],
                                            cfg)
                held.extend((k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
                            if full else
                            (ring_of(k, lengths, cfg.window),
                             ring_of(v, lengths, cfg.window)))
                return blocked_attention(
                    q, k, v, longest, window=None if full else cfg.window)

            x, counts = block(params, x, live, kinds, i, j, attend, cfg)
            if counts is not None:
                with jax.named_scope("laguna.moe"):
                    total = add_counts(total, counts)
            return (x, total), tuple(held)

        return scan_or_call(one_layer, carry, layers)

    carry = (x, dict.fromkeys(COUNT_NAMES, jnp.zeros((), jnp.int32)))
    kept = {"F": [], "W": []}
    for group, repeats in layer_plan(cfg):
        strides = {kind: sum(layers for kinds, _, _, layers in group
                             if kind in kinds) for kind in "FWDE"}

        def one_period(carry, period):
            held = {"F": [], "W": []}
            for run in group:
                carry, kv = one_run(carry, run, period, strides)
                held[run[0][0]].append(kv)
            return carry, {kind: tuple(jnp.concatenate(part)
                                       for part in zip(*kvs))
                           for kind, kvs in held.items() if kvs}

        carry, held = scan_or_call(one_period, carry, repeats)
        for kind, kv in held.items():  # [repeats, layers of the kind, ...]
            with jax.named_scope("laguna.attn_" + STACK[kind]):
                kept[kind].append(tuple(a.reshape((-1,) + a.shape[2:])
                                        for a in kv))

    x, total = carry
    with jax.named_scope("laguna.head"):  # the final norm is the head's
        x = rmsnorm(x, params["rms_f"], cfg.rms_eps).astype(
            jnp.dtype(cfg.dtype))
    cache = {}
    for kind, names in LEAVES.items():
        with jax.named_scope("laguna.attn_" + STACK[kind]):
            for name, parts in zip(names, zip(*kept[kind])):
                cache[name] = jnp.concatenate(parts)
    return x, cache, total


def laguna_apply(params, tokens, cfg: LagunaConfig, mesh=None):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, V]``.  One chip's program:
    ``mesh`` is accepted for the family's signature and must be ``None``
    (experts exchanged across chips are not written yet)."""
    if mesh is not None:
        raise NotImplementedError(
            "laguna runs one chip's share of a layer; no mesh yet")
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = laguna_forward(params, tokens, lengths, cfg)
    with jax.named_scope("laguna.head"):
        return matmul("bse,ve->bsv", x, params["lm_head"])


def laguna_loss(params, tokens, cfg: LagunaConfig, mesh=None):
    """Next-token cross-entropy; tokens ``[B, S+1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = laguna_apply(params, inputs, cfg, mesh).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
