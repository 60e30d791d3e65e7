"""Operations and bytes a MiMo-V2 step needs, from its shapes (``model``:
the kwargs of ``MimoV2Config`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics for this chip's share of a layer (the experts
held here), not of the implementation: a window layer needs the last
``window`` positions of a slot and a full layer its LIVE positions, whatever
the program reads.  Matrices only: norms and sinks are a millionth.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0


def attn_kinds(m: dict) -> str:
    """The letters of the attentions that run: F full, W window."""
    return m["attn_pattern"][:m["n_layer"]]


def mlp_kinds(m: dict) -> str:
    """D dense, E experts."""
    return m["mlp_pattern"][:m["n_layer"]]


def kv_heads(m: dict, kind: str) -> int:
    return m["n_kv_head"] if kind == "F" else m["n_kv_head_window"]


def attention_params(m: dict, kind: str) -> int:
    """Wq, Wk, Wv and Wo of one attention of ``kind``."""
    d, h = m["d_model"], m["n_head"]
    kv = kv_heads(m, kind)
    return (d * h * m["head_dim"] + d * kv * m["head_dim"]
            + d * kv * m["v_head_dim"] + h * m["v_head_dim"] * d)


def router_params(m: dict) -> int:
    return m["d_model"] * m["n_routed_experts"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_expert"]


def held_expert_slots(m: dict) -> int:
    """Held experts, all expert layers."""
    return m["experts_held"] * mlp_kinds(m).count("E")


def model_params(m: dict, experts: int) -> int:
    """The whole model as ``m`` describes it with ``experts`` experts a
    layer: attentions, dense layers, routers, experts, embedding and head."""
    return (sum(attention_params(m, kind) for kind in attn_kinds(m))
            + mlp_kinds(m).count("D") * dense_mlp_params(m)
            + mlp_kinds(m).count("E") * (router_params(m)
                                         + experts * expert_params(m))
            + 2 * m["vocab_size"] * m["d_model"])


def nonexpert_weight_bytes(m: dict) -> float:
    """Every weight a decode step reads whatever was routed: attentions and
    dense layers (bf16), routers (float32) and the head.  The embedding is
    gathered, not read whole."""
    return (BF16 * (sum(attention_params(m, kind) for kind in attn_kinds(m))
                    + mlp_kinds(m).count("D") * dense_mlp_params(m)
                    + m["vocab_size"] * m["d_model"])
            + F32 * mlp_kinds(m).count("E") * router_params(m))


def kv_bytes_per_position(m: dict, kind: str) -> float:
    """Keys and values of one position of ONE layer of ``kind``, bf16."""
    return BF16 * kv_heads(m, kind) * (m["head_dim"] + m["v_head_dim"])


def cache_bytes_per_slot(m: dict, context: float) -> float:
    """What a slot at ``context`` positions must have read of its cache: a
    full layer's live positions, a window layer's last ``window``."""
    kinds = attn_kinds(m)
    return (kinds.count("F") * context * kv_bytes_per_position(m, "F")
            + kinds.count("W") * min(context, m["window"])
            * kv_bytes_per_position(m, "W"))


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every non-expert weight and the head
    once, each held expert that a live token chose once
    (``counts["experts_touched"]``: summed over layers, a step's mean), and
    the occupied slots' cache at ``context`` positions
    (``cache_bytes_per_slot``: LIVE positions; the dead tail an
    implementation reads is its own).  Activations are negligible."""
    return (nonexpert_weight_bytes(m)
            + BF16 * counts["experts_touched"] * expert_params(m)
            + occupied * cache_bytes_per_slot(m, context))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per parameter of the attentions, dense layers and routers,
    of the held experts' expected share of the token's choices (held /
    routed of ``top_k``) and of the head; attention's scores (``head_dim``)
    and values (``v_head_dim``) over the positions a layer sees."""
    kinds = attn_kinds(m)
    dense = (sum(attention_params(m, kind) for kind in kinds)
             + mlp_kinds(m).count("D") * dense_mlp_params(m)
             + mlp_kinds(m).count("E") * router_params(m))
    routed = mlp_kinds(m).count("E") * expert_params(m) * m["top_k"] * (
        m["experts_held"] / m["n_routed_experts"])
    seen = (kinds.count("F") * context
            + kinds.count("W") * min(context, m["window"]))
    attn = 2.0 * seen * m["n_head"] * (m["head_dim"] + m["v_head_dim"])
    return 2.0 * (dense + routed + m["vocab_size"] * m["d_model"]) + attn
