"""Operations and bytes a Nemotron-H step needs, from its shapes (``model``:
the kwargs of ``NemotronHConfig`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics for this chip's share of a layer (the experts
held here), not of the implementation.  Matrices only: the convolution's
taps, the norms and the per-head scalars (``A``, ``D``, ``dt_bias``) are a
thousandth of a layer.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0


def kinds(m: dict) -> str:
    """The letters of the layers that run: M Mamba-2, * attention, E
    experts."""
    return m["layer_pattern"][:m["n_layer"]]


def d_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def d_conv(m: dict) -> int:
    return d_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_params(m: dict) -> int:
    """in_proj (z | xBC | dt) and out_proj."""
    d = m["d_model"]
    return (d * (d_inner(m) + d_conv(m) + m["mamba_num_heads"])
            + d_inner(m) * d)


def attention_params(m: dict) -> int:
    return 2 * m["d_model"] * m["head_dim"] * (m["n_head"] + m["n_kv_head"])


def router_params(m: dict) -> int:
    return m["d_model"] * m["n_routed_experts"]


def moe_dense_params(m: dict) -> int:
    """An expert layer outside its experts and its router: the latent pair
    and the shared expert."""
    d = m["d_model"]
    return 2 * d * m["moe_latent_size"] + 2 * d * m["d_shared"]


def expert_params(m: dict) -> int:
    return 2 * m["moe_latent_size"] * m["d_expert"]


def layer_params(m: dict, kind: str) -> int:
    """One layer outside its routed experts."""
    if kind == "M":
        return mamba_params(m)
    if kind == "*":
        return attention_params(m)
    return router_params(m) + moe_dense_params(m)


def held_expert_slots(m: dict) -> int:
    """Held experts, all expert layers."""
    return m["experts_held"] * kinds(m).count("E")


def nonexpert_weight_bytes(m: dict) -> float:
    """Every weight a decode step reads whatever was routed: the layers
    outside their experts (bf16; the router float32) and the head.  The
    embedding is gathered, not read whole."""
    ks = kinds(m)
    return (BF16 * (ks.count("M") * mamba_params(m)
                    + ks.count("*") * attention_params(m)
                    + ks.count("E") * moe_dense_params(m)
                    + m["vocab_size"] * m["d_model"])
            + F32 * ks.count("E") * router_params(m))


def state_bytes_per_slot(m: dict) -> float:
    """A slot's recurrent state, all Mamba-2 layers, float32: ``S [H, P,
    N]`` and the convolution's last ``K - 1`` inputs."""
    per_layer = (d_inner(m) * m["ssm_state_size"]
                 + (m["conv_kernel"] - 1) * d_conv(m))
    return F32 * kinds(m).count("M") * per_layer


def kv_bytes_per_token(m: dict) -> float:
    """Keys and values of one token, all attention layers, bf16."""
    return BF16 * kinds(m).count("*") * 2 * m["n_kv_head"] * m["head_dim"]


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every non-expert weight and the head
    once, each held expert that a live token chose once
    (``counts["experts_touched"]``: summed over layers, a step's mean), the
    occupied slots' recurrent state read AND written (every element changes
    every step), and their keys and values at ``context`` positions.
    Activations are negligible beside these."""
    return (nonexpert_weight_bytes(m)
            + BF16 * counts["experts_touched"] * expert_params(m)
            + occupied * (2 * state_bytes_per_slot(m)
                          + context * kv_bytes_per_token(m)))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per parameter of the layers outside their experts, of the
    held experts' expected share of the token's choices (held / routed of
    ``top_k``) and of the head; attention's scores and values over the
    context; the recurrence's update and read-out (5 an element of ``S``)."""
    ks = kinds(m)
    dense = sum(layer_params(m, kind) for kind in ks)
    routed = ks.count("E") * expert_params(m) * m["top_k"] * (
        m["experts_held"] / m["n_routed_experts"])
    attn = ks.count("*") * 2 * 2.0 * context * m["n_head"] * m["head_dim"]
    scan = ks.count("M") * 5.0 * d_inner(m) * m["ssm_state_size"]
    return (2.0 * (dense + routed + m["vocab_size"] * m["d_model"])
            + attn + scan)
