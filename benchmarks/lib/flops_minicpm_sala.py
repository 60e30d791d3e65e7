"""Operations and bytes a MiniCPM-SALA step needs, from its shapes (``model``:
the kwargs of ``MinicpmSalaConfig`` as a configuration file's ``model`` has
them).  Kept with the benchmark, as ``flops.py`` is: "needs" is the
arithmetic of the mathematics, not of the implementation (a lightning
layer's state is read once and written once a step; a sparse layer reads
the blocks its rule lists, ``topk`` of them once the context reaches
``dense_len``, and the pooled keys a position can see; a prefill's masked
tiles, which score every key below the diagonal, count as the positions the
rule reads).  Matrices only: norms, the rotary term and the decay are a
thousandth of a layer.
"""

from __future__ import annotations

# The lightning layers run the Mamba-2 scan and update (nemotron_h's
# functions) and are counted by the same arithmetic, under its keys.
from benchmarks.lib.flops_granite_h import ssd_chunk_flops
from benchmarks.lib.flops_nemotron_h import BF16, F32, kinds


def as_mamba(m: dict) -> dict:
    """A lightning layer under the Mamba-2 counts' keys: ``x = v``, ``B = k``,
    ``C = q``, one group a head, a state of ``D x D``."""
    return {"mamba_num_heads": m["lightning_heads"],
            "mamba_head_dim": m["lightning_head_dim"],
            "ssm_state_size": m["lightning_head_dim"],
            "n_groups": m["lightning_heads"]}


def lightning_params(m: dict) -> int:
    """``Wq``, ``Wk``, ``Wv``, the gate and ``Wo``."""
    return 5 * m["d_model"] * m["lightning_heads"] * m["lightning_head_dim"]


def sparse_params(m: dict) -> int:
    """``Wq``, the gate and ``Wo`` over the query heads, ``Wk`` and ``Wv``
    over the key-value heads."""
    return m["d_model"] * m["head_dim"] * (
        3 * m["n_head"] + 2 * m["n_kv_head"])


def mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def layer_params(m: dict, kind: str) -> int:
    """A layer's mixer and its MLP."""
    return mlp_params(m) + (
        lightning_params(m) if kind == "L" else sparse_params(m))


def table_params(m: dict) -> int:
    """The embedding table, or the head: one each, untied."""
    return m["vocab_size"] * m["d_model"]


def total_params(m: dict) -> int:
    """Every matrix of the model: the layers, the embedding and the head."""
    return sum(layer_params(m, kind) for kind in kinds(m)) + 2 * table_params(m)


def weight_bytes(m: dict) -> float:
    """Every weight a decode step reads: the layers and the head (the
    embedding's gather is a few rows of its table), bf16."""
    return BF16 * (total_params(m) - table_params(m))


def state_bytes_per_slot(m: dict) -> float:
    """A slot's lightning state, all lightning layers, float32."""
    return F32 * kinds(m).count("L") * (
        m["lightning_heads"] * m["lightning_head_dim"] ** 2)


def read_positions(m: dict, context: float) -> float:
    """Positions a sparse layer's query reads at ``context``: all of them
    under ``dense_len``, ``topk`` blocks from there on."""
    if context < m["dense_len"]:
        return context
    return min(context, m["topk"] * m["block_size"])


def pooled_windows(m: dict, context: float) -> float:
    """Pooled keys a query scores at ``context``: one every ``kernel_stride``
    positions where the rule selects, none under ``dense_len``."""
    return (context / m["kernel_stride"]
            if context >= m["dense_len"] else 0.0)


def sparse_bytes_per_slot(m: dict, context: float) -> float:
    """What the sparse layers read of a slot's cache a step, bf16: keys and
    values of the positions read and, where the rule selects, the pooled
    keys of the windows inside the context."""
    row = BF16 * m["n_kv_head"] * m["head_dim"]
    return kinds(m).count("S") * row * (
        2 * read_positions(m, context) + pooled_windows(m, context))


def decode_step_bytes(m: dict, counts: dict, occupied: float,
                      context: float) -> float:
    """Bytes one decode step must move: every weight and the head once, the
    occupied slots' lightning state read AND written (every element changes
    every step), the sparse layers' listed blocks and visible pooled keys.
    Activations are negligible beside these.  ``counts`` (the program's) is
    not needed: nothing here is routed."""
    return weight_bytes(m) + occupied * (
        2 * state_bytes_per_slot(m) + sparse_bytes_per_slot(m, context))


def decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token at ``context`` cached positions: 2 per parameter of
    the layers and of the head; a sparse layer's scores and values over the
    positions it reads and its scores against the pooled keys; the
    recurrence's update and read-out (5 an element of ``S``)."""
    ks = kinds(m)
    heads = m["n_head"] * m["head_dim"]
    attn = ks.count("S") * 2.0 * heads * (
        2 * read_positions(m, context) + pooled_windows(m, context))
    scan = ks.count("L") * 5.0 * (
        m["lightning_heads"] * m["lightning_head_dim"] ** 2)
    return 2.0 * (total_params(m) - table_params(m)) + attn + scan


def prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request: the products of
    every token; a sparse layer's scores and values over what each query
    reads (the triangle under ``dense_len``; from there on ``topk`` blocks a
    query, clipped at the query, and its scores against the pooled keys
    before it); the chunked scan of a lightning layer; the head once."""
    ks = kinds(m)
    heads = m["n_head"] * m["head_dim"]
    per_token = 2.0 * sum(layer_params(m, kind) for kind in ks)
    if tokens < m["dense_len"]:
        read, pooled = tokens * (tokens + 1) / 2.0, 0.0
    else:
        cap = m["topk"] * m["block_size"]
        read = cap * (cap + 1) / 2.0 + max(tokens - cap, 0) * float(cap)
        pooled = tokens * (tokens + 1) / 2.0 / m["kernel_stride"]
    attn = ks.count("S") * 2.0 * heads * (2 * read + pooled)
    scan = ks.count("L") * ssd_chunk_flops(as_mamba(m), tokens, m["chunk_size"])
    return per_token * tokens + attn + scan + 2.0 * table_params(m)
