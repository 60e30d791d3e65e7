"""Operations and bytes a Kimi-Linear step needs, from its shapes (``model``:
the kwargs of ``KimiLinearConfig`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics for this chip's share of a layer (the experts
held here and the shared expert), not of the implementation: the KDA state
is read once and written once a step, a decode step needs a slot's LIVE
latents, a prefill its prompt's TRUE length with attention counted below
the diagonal, whatever the program reads or pads.  Matrices only: the
convolution's taps, the norms and the per-head and per-channel vectors
(``A_log``, ``dt_bias``) are a thousandth of a layer.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0
# a letter of the program's ``kinds`` -> (its mixer, its FFN)
STACKS = {"K": ("kda", "moe"), "M": ("mla", "moe"),
          "k": ("kda", "dense"), "m": ("mla", "dense")}


def kinds(m: dict) -> str:
    """The layers that run, a letter each (``KimiLinearConfig.kinds``)."""
    return "".join(c.lower() if i < m["first_k_dense"] else c
                   for i, c in enumerate(m["layer_pattern"][:m["n_layer"]]))


def count(m: dict, stack: str) -> int:
    """Sub-blocks of one kind among the layers that run."""
    return sum(stack in STACKS[c] for c in kinds(m))


def d_key(m: dict) -> int:
    return m["linear_num_heads"] * m["linear_head_dim"]


def kda_params(m: dict) -> int:
    """``Wqkv``, the two bottlenecks (``Wfa Wfb``, ``Wga Wgb``), ``Wb``,
    ``Wo``."""
    d, r = m["d_model"], m["gate_rank"]
    return (d * 3 * d_key(m) + 2 * (d * r + r * d_key(m))
            + d * m["linear_num_heads"] + d_key(m) * d)


def mla_params(m: dict) -> int:
    """``Wq`` (no bottleneck), ``Wkva``, ``Wkb + Wvb``, ``Wo``."""
    d, h = m["d_model"], m["n_head"]
    return (d * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def dense_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def expert_params(m: dict) -> int:
    """One expert, routed or shared."""
    return 3 * m["d_model"] * m["d_expert"]


def router_params(m: dict) -> int:
    return m["d_model"] * m["n_routed_experts"]


def held_expert_slots(m: dict) -> int:
    """Held experts, all EXPERT layers (the first layer holds none)."""
    return m["experts_held"] * count(m, "moe")


def nonexpert_params(m: dict) -> float:
    """Every bf16 matrix outside the routed experts and the head: mixers,
    the dense MLP, the shared experts."""
    return (count(m, "kda") * kda_params(m) + count(m, "mla") * mla_params(m)
            + count(m, "dense") * dense_params(m)
            + count(m, "moe") * expert_params(m))


def nonexpert_weight_bytes(m: dict) -> float:
    """Every weight a decode step reads whatever was routed (routers
    float32) and the head.  The embedding is gathered, not read whole."""
    return (BF16 * nonexpert_params(m)
            + F32 * count(m, "moe") * router_params(m)
            + BF16 * m["vocab_size"] * m["d_model"])


def kda_state_bytes(m: dict) -> float:
    """ONE KDA layer's state of one slot, float32: ``S [H, dk, dv]`` alone
    (what ``ops.delta_update`` reads once and writes once)."""
    return F32 * d_key(m) * m["linear_head_dim"]


def state_bytes_per_slot(m: dict) -> float:
    """A slot's recurrent state, all KDA layers, float32: ``S`` and the
    convolution's last ``K - 1`` inputs of ``3 H dk`` channels."""
    conv = F32 * (m["conv_kernel"] - 1) * 3 * d_key(m)
    return count(m, "kda") * (kda_state_bytes(m) + conv)


def latent_bytes_per_position(m: dict) -> float:
    """The latent cache of one token, all latent layers: ``[ckv | kr]``."""
    return BF16 * count(m, "mla") * (
        m["kv_lora_rank"] + m["qk_rope_head_dim"])


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight outside the routed
    experts and the head once, each held expert that a live token chose once
    (``counts["experts_touched"]``: summed over layers, a step's mean), the
    occupied slots' recurrent state read AND written (every element changes
    every step) and their latents at ``context`` positions (LIVE positions).
    Activations are negligible beside these."""
    return (nonexpert_weight_bytes(m)
            + BF16 * counts["experts_touched"] * expert_params(m)
            + occupied * (2 * state_bytes_per_slot(m)
                          + context * latent_bytes_per_position(m)))


def kda_update_bytes(m: dict, occupied: float) -> float:
    """What the one-token update of ALL KDA layers must move a step: the
    occupied slots' ``S`` read once and written once (the kernel's small
    operands, 0.2 % of it, are not counted)."""
    return occupied * count(m, "kda") * 2 * kda_state_bytes(m)


def routed_params_per_token(m: dict) -> float:
    """The held experts' expected share of a token's choices, all layers."""
    return (count(m, "moe") * expert_params(m) * m["top_k"]
            * m["experts_held"] / m["n_routed_experts"])


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per parameter outside the routed experts, of the held
    experts' expected share, of the routers and of the head; the absorbed
    attention over the latents (scores over ``rkv + dr``, values over ``rkv``,
    a head, a position, a latent layer); the delta rule's update and
    read-outs (the decay, ``S^T k``, ``S^T q`` and the rank-one update: 7 an
    element of ``S``)."""
    attn = count(m, "mla") * 2.0 * context * m["n_head"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    rule = count(m, "kda") * 7.0 * d_key(m) * m["linear_head_dim"]
    return 2.0 * (nonexpert_params(m) + count(m, "moe") * router_params(m)
                  + routed_params_per_token(m)
                  + m["vocab_size"] * m["d_model"]) + attn + rule


def kda_chunk_flops(m: dict, tokens: int, chunk: int) -> float:
    """The chunked rule with a vector gate over ``tokens`` positions of one
    KDA layer, all heads, as the mathematics has it at chunk ``C``: a
    position's row of the two pairwise sums below the diagonal (``C / 2``
    pairs of ``dk`` channels: a decay's exponential, a product and a
    multiply-add for each of the two, 5 a channel a pair), of the solve (``C
    (dk + dv)``: forward substitution's triangle), of ``W S``, ``Q S`` and
    ``K^T V'`` (``2 dk dv`` each) and of ``lower(Q K^T) V'`` (``C dv``)."""
    dk = dv = m["linear_head_dim"]
    per_position = (5 * (chunk / 2) * dk + chunk * (dk + dv)
                    + 3 * 2 * dk * dv + chunk * dv)
    return float(m["linear_num_heads"] * tokens * per_position)


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request on this chip's
    share: the products of every token (held experts in expectation), a
    latent layer's expanded scores (``dn+dr`` a head) and values (``dv``)
    BELOW the diagonal, the chunked rule of a KDA layer, the head once."""
    per_token = 2.0 * (nonexpert_params(m)
                       + count(m, "moe") * router_params(m)
                       + routed_params_per_token(m))
    attn = count(m, "mla") * 2.0 * (tokens * tokens / 2) * m["n_head"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    rule = count(m, "kda") * kda_chunk_flops(m, tokens, m["chunk_size"])
    return per_token * tokens + attn + rule + (
        2.0 * m["vocab_size"] * m["d_model"])
