"""Operations and bytes an algorithm needs, from its shapes.

Kept with the benchmark: no PR that claims a gain can change how its gain
is counted.  "Needs" means the arithmetic of the mathematics, not of the
implementation: recomputation (remat, a backward kernel that rebuilds the
scores twice) is not counted, a causal mask halves the score work.
"""

from __future__ import annotations


def gpt2_matmul_params(m: dict) -> int:
    """Parameters that enter a matrix multiplication once per token: the
    blocks and the (tied) unembedding.  Position table and the embedding
    gather do none."""
    e, L, v = m["d_model"], m["n_layer"], m["vocab_size"]
    return L * (3 * e * e + e * e + 8 * e * e) + v * e


def gpt2_train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward of one token at context ``seq``: 6 per matmul
    parameter, + attention scores and values (2 matmuls of 2*seq*e each a
    layer, x3 for forward + backward; the causal half not discounted, as
    docs/mfu_methodology.md and the usual 6N + 12*L*s*e have it)."""
    return 6.0 * gpt2_matmul_params(m) + 12.0 * m["n_layer"] * seq * m["d_model"]


def flash_step_need(m: dict, rows: int, seq: int, remat: bool) -> dict:
    """Causal attention forward + backward for ``rows`` sequences on one
    chip, all layers, one optimizer step: forward 2 matmuls, backward 5
    (scores again, dV, dP, dQ, dK), each 2*seq*seq*head_dim a head, halved by
    the mask.  With remat the forward's second run is recomputation and is
    not counted.  Bytes: q, k, v, o read or written once each way."""
    h, d, L = m["n_head"], m["d_model"] // m["n_head"], m["n_layer"]
    per_matmul = 2.0 * seq * seq * d * 0.5
    flops = rows * h * L * per_matmul * (2 + 5)
    # fwd: read q,k,v write o; bwd: read q,k,v,o,do write dq,dk,dv (bf16).
    nbytes = rows * h * L * seq * d * 2.0 * (4 + 8)
    return {"flops": flops, "bytes": nbytes}


def llama_matmul_params(m: dict) -> int:
    e, f, L = m["d_model"], m["d_ff"], m["n_layer"]
    hd = e // m["n_head"]
    attn = e * m["n_head"] * hd * 2 + e * m["n_kv_head"] * hd * 2
    return L * (attn + 3 * e * f) + m["vocab_size"] * e


def llama_weight_bytes(m: dict) -> float:
    """bf16 weights a decode step must read: every matmul parameter (the
    embedding table is gathered, not read whole)."""
    return 2.0 * llama_matmul_params(m)


def llama_prefill_flops(m: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt tokens of one request (causal)."""
    e, L = m["d_model"], m["n_layer"]
    blocks = llama_matmul_params(m) - m["vocab_size"] * e
    attn = L * 2 * 2.0 * tokens * tokens * e * 0.5
    return 2.0 * blocks * tokens + attn + 2.0 * m["vocab_size"] * e


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> dict:
    """The least time the chip could take over the time it took, in %."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(t_compute, t_memory) / seconds,
            "bound": "compute" if t_compute >= t_memory else "memory"}


def llama_decode_flops_per_token(m: dict, context: float) -> float:
    """One decoded token at ``context`` cached positions: 2 per matmul
    parameter, + scores and values over the context (2 matmuls of
    2*context*e a layer)."""
    return (2.0 * llama_matmul_params(m)
            + 4.0 * m["n_layer"] * context * m["d_model"])


def mean_decode_context(sizes: list) -> float:
    """Mean context of an occupied slot over the decode steps of a
    population of (prompt_tokens, output_tokens): a request decodes
    ``out`` steps at contexts ``prompt .. prompt + out``."""
    steps = sum(out for _p, out in sizes)
    return sum(out * (p + out / 2.0) for p, out in sizes) / steps
