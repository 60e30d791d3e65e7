"""Operations and bytes an Olmo-Hybrid step needs, from its shapes (``model``:
the kwargs of ``OlmoHybridConfig`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics, not of the implementation (the state is
counted unpadded, read once and written once a step; keys and values at the
slots' LIVE positions).  Matrices only: the convolution's taps, the norms
and the per-head scalars (``A``, ``dt_bias``) are a thousandth of a layer.
"""

from __future__ import annotations

BF16, F32 = 2.0, 4.0


def kinds(m: dict) -> str:
    """The letters of the layers that run: L linear, F full attention."""
    return m["layer_pattern"][:m["n_layer"]]


def d_key(m: dict) -> int:
    return m["linear_num_heads"] * m["linear_key_head_dim"]


def d_value(m: dict) -> int:
    return m["linear_num_heads"] * m["linear_value_head_dim"]


def d_conv(m: dict) -> int:
    return 2 * d_key(m) + d_value(m)


def mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def delta_params(m: dict) -> int:
    """``Wqkv``, ``Wg``, ``Wa``, ``Wb`` and ``Wo``."""
    d = m["d_model"]
    return d * (d_conv(m) + d_value(m) + 2 * m["linear_num_heads"]) + (
        d_value(m) * d)


def attention_params(m: dict) -> int:
    """``Wq``, ``Wk``, ``Wv``, ``Wo``: as many key-value heads as queries."""
    return 4 * m["d_model"] * m["n_head"] * m["head_dim"]


def layer_params(m: dict, kind: str) -> int:
    return mlp_params(m) + (
        delta_params(m) if kind == "L" else attention_params(m))


def weight_bytes(m: dict) -> float:
    """Every weight a decode step reads: the layers and the head, bf16.  The
    embedding is gathered, not read whole."""
    return BF16 * (sum(layer_params(m, kind) for kind in kinds(m))
                   + m["vocab_size"] * m["d_model"])


def state_bytes_per_slot(m: dict) -> float:
    """A slot's recurrent state, all linear layers, float32: ``S [H, dk,
    dv]`` and the convolution's last ``K - 1`` inputs."""
    per_layer = (d_key(m) * m["linear_value_head_dim"]
                 + (m["conv_kernel"] - 1) * d_conv(m))
    return F32 * kinds(m).count("L") * per_layer


def kv_bytes_per_token(m: dict) -> float:
    """Keys and values of one token, all full layers, bf16."""
    return BF16 * kinds(m).count("F") * 2 * m["n_head"] * m["head_dim"]


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight and the head once, the
    occupied slots' recurrent state read AND written (every element changes
    every step), and their keys and values at ``context`` positions.
    Activations are negligible beside these.  ``counts`` (the program's) is
    not needed: nothing here is routed."""
    return weight_bytes(m) + occupied * (
        2 * state_bytes_per_slot(m) + context * kv_bytes_per_token(m))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token at ``context`` cached positions: 2 per parameter of
    the layers and of the head; attention's scores and values over the
    context; the delta rule's update and read-outs (``S^T k``, ``S^T q``, the
    decay and the rank-one update: 7 an element of ``S``)."""
    ks = kinds(m)
    dense = sum(layer_params(m, kind) for kind in ks)
    attn = ks.count("F") * 2 * 2.0 * context * m["n_head"] * m["head_dim"]
    rule = ks.count("L") * 7.0 * d_key(m) * m["linear_value_head_dim"]
    return 2.0 * (dense + m["vocab_size"] * m["d_model"]) + attn + rule


def delta_chunk_flops(m: dict, tokens: int, chunk: int) -> float:
    """The chunked rule over ``tokens`` positions of one linear layer, all
    heads, as the mathematics has it at chunk ``C``: a position's row of ``K
    K^T`` and of ``Q K^T`` (``2 C dk`` each, half of it below the diagonal),
    of the solve (``C (dk + dv)``: forward substitution's triangle), of ``W
    S``, ``Q S`` and ``K^T V'`` (``2 dk dv`` each) and of ``lower(Q K^T) V'``
    (``C dv``: the triangle)."""
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_position = (2 * chunk * dk + chunk * (dk + dv) + 3 * 2 * dk * dv
                    + chunk * dv)
    return float(m["linear_num_heads"] * tokens * per_position)


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request: the products of
    every token, a full layer's scores and values below the diagonal, the
    chunked rule of a linear layer, the head once."""
    ks = kinds(m)
    per_token = 2.0 * sum(layer_params(m, kind) for kind in ks)
    attn = ks.count("F") * 2.0 * 2 * m["head_dim"] * m["n_head"] * (
        tokens * (tokens + 1) / 2.0)
    rule = ks.count("L") * delta_chunk_flops(m, tokens, m["chunk_size"])
    return per_token * tokens + attn + rule + (
        2.0 * m["vocab_size"] * m["d_model"])
