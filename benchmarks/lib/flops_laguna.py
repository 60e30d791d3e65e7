"""Operations and bytes a Laguna step needs, from its shapes (``model``: the
kwargs of ``LagunaConfig`` as a configuration file's ``model`` has them).
Kept with the benchmark, as ``flops.py`` is: "needs" is the arithmetic of
the mathematics for this chip's share of a layer (the experts held here and
the shared expert), not of the implementation: a window layer needs the
last ``window`` positions of a slot and a full layer its LIVE positions, a
prefill its prompt's TRUE length with attention counted below the diagonal
and, in a window layer, inside the window, whatever the program reads, pads
or computes of a tile.  Matrices only: norms, rotary, the gate's sigmoid and
the softmax are a thousandth.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0


def attn_kinds(m: dict) -> str:
    """The letters of the attentions that run: F full, W window."""
    return m["attn_pattern"][:m["n_layer"]]


def mlp_kinds(m: dict) -> str:
    """D dense, E experts."""
    return m["mlp_pattern"][:m["n_layer"]]


def heads(m: dict, kind: str) -> int:
    return m["n_head"] if kind == "F" else m["n_head_window"]


def attention_params(m: dict, kind: str) -> int:
    """Wq, Wk, Wv, the gate and Wo of one attention of ``kind``."""
    d, h, dh = m["d_model"], heads(m, kind), m["head_dim"]
    return 2 * d * h * dh + 2 * d * m["n_kv_head"] * dh + d * h


def router_params(m: dict) -> int:
    return m["d_model"] * m["n_routed_experts"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def expert_params(m: dict) -> int:
    """One expert, routed or shared."""
    return 3 * m["d_model"] * m["d_expert"]


def held_expert_slots(m: dict) -> int:
    """Held experts, all expert layers."""
    return m["experts_held"] * mlp_kinds(m).count("E")


def nonexpert_params(m: dict) -> int:
    """Every matrix outside the routed experts, the embedding and the head:
    attentions, dense layers, routers and shared experts."""
    return (sum(attention_params(m, kind) for kind in attn_kinds(m))
            + mlp_kinds(m).count("D") * dense_mlp_params(m)
            + mlp_kinds(m).count("E") * (router_params(m) + expert_params(m)))


def model_params(m: dict, experts: int) -> int:
    """The whole model as ``m`` describes it with ``experts`` routed experts
    a layer: layers, embedding and untied head."""
    return (nonexpert_params(m)
            + mlp_kinds(m).count("E") * experts * expert_params(m)
            + 2 * m["vocab_size"] * m["d_model"])


def nonexpert_weight_bytes(m: dict) -> float:
    """Every weight a decode step reads whatever was routed: attentions,
    dense layers and shared experts (bf16), routers (float32) and the head.
    The embedding is gathered, not read whole."""
    routers = mlp_kinds(m).count("E") * router_params(m)
    return (BF16 * (nonexpert_params(m) - routers
                    + m["vocab_size"] * m["d_model"]) + F32 * routers)


def kv_bytes_per_position(m: dict) -> float:
    """Keys and values of one position of ONE layer (either kind), bf16."""
    return BF16 * m["n_kv_head"] * 2 * m["head_dim"]


def positions_seen(m: dict, context: float) -> float:
    """Positions a token at ``context`` attends, summed over the layers: a
    full layer's live positions, a window layer's last ``window``."""
    kinds = attn_kinds(m)
    return (kinds.count("F") * context
            + kinds.count("W") * min(context, m["window"]))


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight outside the routed
    experts and the head once, each held expert that a live token chose once
    (``counts["experts_touched"]``: summed over layers, a step's mean), and
    the occupied slots' keys and values at ``context`` positions
    (``positions_seen``: LIVE positions; the dead tail an implementation
    reads is its own).  Activations are negligible."""
    return (nonexpert_weight_bytes(m)
            + BF16 * counts["experts_touched"] * expert_params(m)
            + occupied * positions_seen(m, context) * kv_bytes_per_position(m))


def routed_params_per_token(m: dict) -> float:
    """The held experts' expected share of a token's choices, all layers."""
    return (mlp_kinds(m).count("E") * expert_params(m) * m["top_k"]
            * m["experts_held"] / m["n_routed_experts"])


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token on this chip's share at ``context`` cached
    positions: 2 per parameter outside the routed experts, of the held
    experts' expected share and of the head; attention's scores and values
    (``head_dim`` each) a query head over the positions its layer sees."""
    kinds = attn_kinds(m)
    attn = 2.0 * 2 * m["head_dim"] * (
        kinds.count("F") * heads(m, "F") * context
        + kinds.count("W") * heads(m, "W") * min(context, m["window"]))
    return 2.0 * (nonexpert_params(m) + routed_params_per_token(m)
                  + m["vocab_size"] * m["d_model"]) + attn


def pairs_seen(tokens: int, window=None) -> float:
    """(query, key) pairs of ``tokens`` positions below the diagonal, itself
    included; with a window, those at most ``window - 1`` behind."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) / 2
    return window * (window + 1) / 2 + (tokens - window) * window


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request on this chip's
    share: the products of every token (held experts in expectation), the
    attention's scores and values over the pairs a layer's mask leaves
    (``pairs_seen``: a full layer's triangle, a window layer's band INSIDE
    the window: the half of the two tiles a query tile meets that the mask
    keeps), the head once."""
    kinds = attn_kinds(m)
    per_token = 2.0 * (nonexpert_params(m) + routed_params_per_token(m))
    attn = 2.0 * 2 * m["head_dim"] * (
        kinds.count("F") * heads(m, "F") * pairs_seen(tokens)
        + kinds.count("W") * heads(m, "W") * pairs_seen(tokens, m["window"]))
    return per_token * tokens + attn + 2.0 * m["vocab_size"] * m["d_model"]
