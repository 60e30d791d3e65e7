"""Operations and bytes a LongCat-Flash step needs, from its shapes
(``model``: the kwargs of ``LongcatConfig`` as a configuration file's
``model`` has them).  Kept with the benchmark, as ``flops.py`` is: "needs"
is the arithmetic of the mathematics for this chip's share of a layer (the
experts held here), not of the implementation.
"""

from __future__ import annotations

BF16 = 2.0


def mla_params(m: dict) -> int:
    """One attention: Wqa, Wqb, Wkva, Wkvb, Wo."""
    d, h = m["d_model"], m["n_head"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def nonexpert_layer_params(m: dict) -> int:
    """A double layer outside its experts: two attentions, two dense FFNs,
    the router (kept in float32: counted as two bf16 parameters each)."""
    d = m["d_model"]
    router = 2 * d * (m["n_routed_experts"] + m["zero_expert_num"])
    return 2 * mla_params(m) + 2 * 3 * d * m["d_ff"] + router


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_expert"]


def latent_bytes_per_token(m: dict) -> float:
    """The latent cache of one token, all attentions: [ckv | kr] each."""
    return BF16 * 2 * m["n_layer"] * (m["kv_lora_rank"]
                                      + m["qk_rope_head_dim"])


def decode_step_bytes(m: dict, experts_touched: float, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must read: every non-expert weight and the
    head once, each held expert that a live token chose once
    (``experts_touched``: summed over layers), and the latents of the
    occupied slots' contexts.  The embedding is gathered, not read whole;
    activations are negligible beside these."""
    weights = m["n_layer"] * nonexpert_layer_params(m) + (
        m["vocab_size"] * m["d_model"])
    return (BF16 * (weights + experts_touched * expert_params(m))
            + occupied * context * latent_bytes_per_token(m))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per dense parameter and per parameter of the held experts'
    expected share of the token's choices (as ``prefill_flops`` counts it),
    the head, and the absorbed attention over the latents (scores over
    ``kv_lora_rank + qk_rope_head_dim``, values over ``kv_lora_rank``, a
    head, a position, two attentions a layer)."""
    dense = m["n_layer"] * nonexpert_layer_params(m)
    routed = m["n_layer"] * expert_params(m) * m["top_k"] * m["experts_held"] / (
        m["n_routed_experts"] + m["zero_expert_num"])
    attn = 2 * m["n_layer"] * 2.0 * context * m["n_head"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return 2.0 * (dense + routed + m["vocab_size"] * m["d_model"]) + attn


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request on this chip's
    share: dense parts for every token, held experts for the share of the
    choices that falls on them in expectation (held / (routed + zero) of
    ``top_k``), causal attention scores and values."""
    h = m["n_head"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dense = m["n_layer"] * nonexpert_layer_params(m)
    routed = m["n_layer"] * expert_params(m) * m["top_k"] * m["experts_held"] / (
        m["n_routed_experts"] + m["zero_expert_num"])
    attn = 2 * m["n_layer"] * 2.0 * tokens * tokens * h * (
        qk + m["v_head_dim"]) * 0.5
    return (2.0 * (dense + routed) * tokens + attn
            + 2.0 * m["vocab_size"] * m["d_model"])
