"""Operations and bytes a Mistral-4 step needs, from its shapes (``model``:
the kwargs of ``Mistral4Config`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics for this chip's share of a layer (the experts
held here and the shared expert), not of the implementation: a decode step
needs a slot's LIVE latents, a prefill its prompt's TRUE length with
attention counted below the diagonal, whatever the program reads or pads.
Matrices only: norms, rotary and the softmax are a thousandth.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0


def mla_params(m: dict) -> int:
    """One attention: Wqa, Wqb, Wkva, Wkb + Wvb, Wo."""
    d, h = m["d_model"], m["n_head"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_params(m: dict) -> int:
    """One expert, routed or shared."""
    return 3 * m["d_model"] * m["d_expert"]


def router_params(m: dict) -> int:
    return m["d_model"] * m["n_routed_experts"]


def nonexpert_layer_params(m: dict) -> int:
    """A layer outside its routed experts: attention, shared expert,
    router."""
    return mla_params(m) + expert_params(m) + router_params(m)


def held_expert_slots(m: dict) -> int:
    """Held experts, all layers (every layer is an expert layer)."""
    return m["experts_held"] * m["n_layer"]


def model_params(m: dict, experts: int) -> int:
    """The whole model as ``m`` describes it with ``experts`` routed experts
    a layer: layers, embedding and untied head."""
    return (m["n_layer"] * (nonexpert_layer_params(m)
                            + experts * expert_params(m))
            + 2 * m["vocab_size"] * m["d_model"])


def nonexpert_weight_bytes(m: dict) -> float:
    """Every weight a decode step reads whatever was routed: attentions and
    shared experts (bf16), routers (float32) and the head.  The embedding is
    gathered, not read whole."""
    return (m["n_layer"] * (BF16 * (mla_params(m) + expert_params(m))
                            + F32 * router_params(m))
            + BF16 * m["vocab_size"] * m["d_model"])


def latent_bytes_per_position(m: dict) -> float:
    """The latent cache of one token, all layers: ``[ckv | kr]`` each."""
    return BF16 * m["n_layer"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight outside the routed
    experts and the head once, each held expert that a live token chose once
    (``counts["experts_touched"]``: summed over layers, a step's mean), and
    the occupied slots' latents at ``context`` positions (LIVE positions; the
    dead tail an implementation reads is its own).  Activations are
    negligible."""
    return (nonexpert_weight_bytes(m)
            + BF16 * counts["experts_touched"] * expert_params(m)
            + occupied * context * latent_bytes_per_position(m))


def routed_params_per_token(m: dict) -> float:
    """The held experts' expected share of a token's choices, all layers."""
    return (m["n_layer"] * expert_params(m) * m["top_k"]
            * m["experts_held"] / m["n_routed_experts"])


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per parameter outside the routed experts, of the held
    experts' expected share and of the head; the absorbed attention over the
    latents (scores over ``kv_lora_rank + qk_rope_head_dim``, values over
    ``kv_lora_rank``, a head, a position, a layer)."""
    attn = m["n_layer"] * 2.0 * context * m["n_head"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return 2.0 * (m["n_layer"] * nonexpert_layer_params(m)
                  + routed_params_per_token(m)
                  + m["vocab_size"] * m["d_model"]) + attn


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request on this chip's
    share: the products of every token (held experts in expectation), the
    expanded attention's scores (``dn+dr`` a head) and values (``dv``) BELOW
    the diagonal (``tokens^2 / 2`` pairs), the head once."""
    per_token = 2.0 * (m["n_layer"] * nonexpert_layer_params(m)
                       + routed_params_per_token(m))
    attn = m["n_layer"] * 2.0 * (tokens * tokens / 2) * m["n_head"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return per_token * tokens + attn + 2.0 * m["vocab_size"] * m["d_model"]
