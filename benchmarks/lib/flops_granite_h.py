"""Operations and bytes a Granite-4.0-H step needs, from its shapes
(``model``: the kwargs of ``GraniteHConfig`` as a configuration file's
``model`` has them).  Kept with the benchmark, as ``flops.py`` is: "needs" is
the arithmetic of the mathematics, not of the implementation (the state is
read once and written once a step; keys and values at the slots' LIVE
positions; the table of the tied head is read once).  Matrices only: the
convolution's taps, the norms and the per-head scalars (``A``, ``D``,
``dt_bias``) are a thousandth of a layer.
"""

from __future__ import annotations

# The Mamba-2 mixer's and the grouped-query attention's counts are
# Nemotron-H's, as the program's functions are: the same keys of ``model``.
from benchmarks.lib.flops_nemotron_h import (  # noqa: F401
    BF16, attention_params, d_conv, d_inner, kinds, kv_bytes_per_token,
    mamba_params, state_bytes_per_slot)


def mlp_params(m: dict) -> int:
    """``input_linear`` (gate | up) and ``output_linear``."""
    return 3 * m["d_model"] * m["d_ff"]


def layer_params(m: dict, kind: str) -> int:
    """A layer's mixer and its MLP."""
    return mlp_params(m) + (
        mamba_params(m) if kind == "M" else attention_params(m))


def table_params(m: dict) -> int:
    """The embedding table, which is the head too."""
    return m["vocab_size"] * m["d_model"]


def total_params(m: dict) -> int:
    """Every matrix of the model, the tied table once."""
    return sum(layer_params(m, kind) for kind in kinds(m)) + table_params(m)


def weight_bytes(m: dict) -> float:
    """Every weight a decode step reads: the layers and the table (as the
    head: the embedding's gather is a few rows of it), bf16."""
    return BF16 * total_params(m)


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight and the table once,
    the occupied slots' recurrent state read AND written (every element
    changes every step), and their keys and values at ``context``
    positions.  Activations are negligible beside these.  ``counts`` (the
    program's) is not needed: nothing here is routed."""
    return weight_bytes(m) + occupied * (
        2 * state_bytes_per_slot(m) + context * kv_bytes_per_token(m))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token at ``context`` cached positions: 2 per parameter of
    the layers and of the head; attention's scores and values over the
    context; the recurrence's update and read-out (5 an element of ``S``)."""
    ks = kinds(m)
    attn = ks.count("*") * 2 * 2.0 * context * m["n_head"] * m["head_dim"]
    scan = ks.count("M") * 5.0 * d_inner(m) * m["ssm_state_size"]
    return 2.0 * total_params(m) + attn + scan


def ssd_chunk_flops(m: dict, tokens: int, chunk: int) -> float:
    """The chunked scan over ``tokens`` positions of one Mamba-2 layer, all
    heads, as the mathematics has it at chunk ``Q``: a position's row of ``C
    B^T`` a group (``2 Q N``, half of it below the diagonal), of the masked
    product with ``x`` a head (``2 Q P``, the triangle again), of its part
    in the chunk's end state (``x (x) B``: ``2 P N`` a head) and of the
    read-out of the state before the chunk (``2 P N`` a head)."""
    n, p = m["ssm_state_size"], m["mamba_head_dim"]
    per_position = (m["n_groups"] * chunk * n
                    + m["mamba_num_heads"] * (chunk * p + 4 * p * n))
    return float(tokens * per_position)


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request: the products of
    every token, an attention layer's scores and values below the diagonal,
    the chunked scan of a Mamba-2 layer, the head once."""
    ks = kinds(m)
    per_token = 2.0 * sum(layer_params(m, kind) for kind in ks)
    attn = ks.count("*") * 2.0 * 2 * m["head_dim"] * m["n_head"] * (
        tokens * (tokens + 1) / 2.0)
    scan = ks.count("M") * ssd_chunk_flops(m, tokens, m["chunk_size"])
    return per_token * tokens + attn + scan + 2.0 * table_params(m)
