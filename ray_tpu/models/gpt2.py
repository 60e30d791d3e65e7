"""GPT-2 family — the flagship LM (BASELINE.md north-star config #4:
GPT-2-medium LM with streaming data + sharded optimizer).

TPU-first design decisions:
  - plain-JAX pytree params with *logical* sharding axes
    (``gpt2_param_axes``) mapped through ``ray_tpu.parallel.sharding`` rules
    — the same model runs DP, FSDP, TP, and SP by changing the rule table;
  - layers are stacked on a leading axis and applied with ``lax.scan``
    (one trace/compile regardless of depth; XLA pipelines the layer loop);
  - attention is pluggable: dense (XLA-fused), Pallas flash kernel, ring
    (context parallel over ``seq`` axis), or Ulysses all-to-all;
  - ``remat=True`` wraps each layer in ``jax.checkpoint`` to trade FLOPs
    for HBM;
  - bf16 activations/params with f32 layernorm + softmax accumulation.

Device operations carry ``jax.named_scope``s ``gpt2.embed``, ``gpt2.attn``
(first norm to the residual), ``gpt2.mlp`` and ``gpt2.head`` (final norm,
logits, cross-entropy); a backward pass keeps them inside ``jvp(...)`` /
``transpose(jvp(...))`` (docs/observability.md, "Which part of the model").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import layernorm


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # 50257 padded up for lane tiling
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    dtype: str = "bfloat16"
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = False
    # "full" recomputes the whole block in backward (measured FASTEST on
    # bandwidth-poor parts — storing activations costs more than
    # recomputing them); "dots" = jax dots_with_no_batch_dims_saveable
    # (saves nothing for our batched einsums — degenerates to full);
    # "dots_all" saves every contraction result (dots_saveable);
    # "matmuls" saves the tagged projection outputs + attention residual;
    # "save_mlp" saves only the tagged MLP hidden activations.  Unknown
    # values fall through to "full".
    remat_policy: str = "full"  # full | dots | dots_all | matmuls | save_mlp

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @classmethod
    def medium(cls, **kw) -> "GPT2Config":
        return cls(n_layer=24, n_head=16, d_model=1024, **kw)

    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        return cls(n_layer=12, n_head=12, d_model=768, **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        return cls(n_layer=2, n_head=4, d_model=64, **kw)


def gpt2_init(key, cfg: GPT2Config):
    e, h, d, L = cfg.d_model, cfg.n_head, cfg.head_dim, cfg.n_layer
    k = iter(jax.random.split(key, 16))
    dt = jnp.dtype(cfg.dtype)
    init = lambda kk, shape, scale: (jax.random.normal(kk, shape) * scale).astype(dt)
    s = 0.02
    so = s / (2 * L) ** 0.5  # gpt-2 residual-out scaling
    params = {
        "wte": init(next(k), (cfg.vocab_size, e), s),
        "wpe": init(next(k), (cfg.max_seq, e), s),
        "blocks": {
            "ln1_g": jnp.ones((L, e), dt),
            "ln1_b": jnp.zeros((L, e), dt),
            "wqkv": init(next(k), (L, e, 3, h, d), s),
            "bqkv": jnp.zeros((L, 3, h, d), dt),
            "wo": init(next(k), (L, h, d, e), so),
            "bo": jnp.zeros((L, e), dt),
            "ln2_g": jnp.ones((L, e), dt),
            "ln2_b": jnp.zeros((L, e), dt),
            "wi": init(next(k), (L, e, 4 * e), s),
            "bi": jnp.zeros((L, 4 * e), dt),
            "wo2": init(next(k), (L, 4 * e, e), so),
            "bo2": jnp.zeros((L, e), dt),
        },
        "lnf_g": jnp.ones((e,), dt),
        "lnf_b": jnp.zeros((e,), dt),
    }
    return params


def gpt2_param_axes():
    """Logical sharding axes per parameter (leading None = layer-stack axis)."""
    return {
        # NOTE: the vocab axis of the embedding table is deliberately NOT
        # sharded: ``wte[tokens]`` gathers along it, and a vocab-sharded
        # table forces XLA SPMD into "involuntary full rematerialization"
        # (replicate-then-repartition) on every step.  Sharding embed over
        # fsdp keeps the ZeRO-3 memory win; the unembedding matmul still
        # produces vocab(model)-sharded logits by slicing.
        "wte": P(None, "embed"),
        "wpe": P(None, "embed"),
        "blocks": {
            "ln1_g": P(None, "norm"),
            "ln1_b": P(None, "norm"),
            "wqkv": P(None, "embed", None, "heads", "kv"),
            "bqkv": P(None, None, "heads", "kv"),
            "wo": P(None, "heads", "kv", "embed"),
            "bo": P(None, "norm"),
            "ln2_g": P(None, "norm"),
            "ln2_b": P(None, "norm"),
            "wi": P(None, "embed", "mlp"),
            "bi": P(None, "mlp"),
            "wo2": P(None, "mlp", "embed"),
            "bo2": P(None, "norm"),
        },
        "lnf_g": P("norm"),
        "lnf_b": P("norm"),
    }


def _attention(q, k, v, cfg: GPT2Config, mesh):
    if cfg.attention == "dense_remat":
        # Dense XLA attention (fastest at moderate S on this chip: the
        # einsum-softmax fusion runs at the matmul roofline) with
        # ``jax.checkpoint`` so the [B,H,S,S] probs are recomputed in
        # backward instead of stored — flash-attention's memory profile at
        # dense-attention speed.  Long S still wants the Pallas kernel.
        from ..ops.attention import reference_attention

        return jax.checkpoint(
            lambda q, k, v: reference_attention(q, k, v, causal=True)
        )(q, k, v)
    if cfg.attention == "ring":
        from ..parallel.ring_attention import ring_attention

        assert mesh is not None, "ring attention requires a mesh"
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.attention == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        assert mesh is not None, "ulysses attention requires a mesh"
        return ulysses_attention(q, k, v, mesh, causal=True)
    from ..ops.attention import reference_attention

    return reference_attention(q, k, v, causal=True)


def _block(x, layer, cfg: GPT2Config, mesh):
    from ..parallel.sharding import with_logical_constraint as wlc

    b, s, e = x.shape
    h, d = cfg.n_head, cfg.head_dim
    # checkpoint_name tags (no-ops outside a names-based remat policy):
    # "matmuls" saves every projection output so backward recomputes only
    # the cheap elementwise chains (LN/gelu/residual) — the sweet spot
    # between full remat (recompute a whole forward, ~8/6 executed FLOPs)
    # and no remat (stored-activation reads dominate a bandwidth-poor bwd).
    from jax.ad_checkpoint import checkpoint_name as _ckpt_name

    with jax.named_scope("gpt2.attn"):
        y = layernorm(x, layer["ln1_g"], layer["ln1_b"])
        qkv = jnp.einsum("bse,ethd->bsthd", y, layer["wqkv"]) + layer["bqkv"]
        qkv = _ckpt_name(qkv, "qkv")
        if cfg.attention == "flash":
            # the kernels address q, k and v inside the projection's result
            # and write ONE d(qkv): q, k, v are never formed
            from ..ops.attention import flash_attention_packed

            qkv = wlc(qkv, P("batch", "seq", None, "heads", "kv"), mesh)
            o = flash_attention_packed(qkv, causal=True)
        else:
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            q = wlc(q, P("batch", "seq", "heads", "kv"), mesh)
            k = wlc(k, P("batch", "seq", "heads", "kv"), mesh)
            v = wlc(v, P("batch", "seq", "heads", "kv"), mesh)
            o = _attention(q, k, v, cfg, mesh)
        o = _ckpt_name(o, "attn_out")
        x = x + (jnp.einsum("bshd,hde->bse", o, layer["wo"]) + layer["bo"]).astype(x.dtype)
        x = _ckpt_name(x, "attn_resid")
    with jax.named_scope("gpt2.mlp"):
        y = layernorm(x, layer["ln2_g"], layer["ln2_b"])
        hdn = jax.nn.gelu(jnp.einsum("bse,ef->bsf", y, layer["wi"]) + layer["bi"])
        hdn = _ckpt_name(hdn, "mlp_hidden")
        hdn = wlc(hdn, P("batch", "seq", "mlp"), mesh)
        x = x + (jnp.einsum("bsf,fe->bse", hdn, layer["wo2"]) + layer["bo2"]).astype(x.dtype)
        return wlc(x, P("batch", "seq", "act_embed"), mesh)


def gpt2_hidden(params, tokens, cfg: GPT2Config, mesh=None):
    """tokens: [B, S] int32 → final layernormed hidden states [B, S, E]."""
    from ..parallel.sharding import with_logical_constraint as wlc

    b, s = tokens.shape
    # Gather from an explicitly replicated view of the table: the ZeRO-3
    # all-gather of wte happens as one clean collective, the token gather
    # then has a replicated operand and output, and the batch/seq constraint
    # below is a free slice.  Gathering from the fsdp-sharded table instead
    # makes SPMD reshard the gather output embed→batch, which it can only do
    # by full rematerialization (round-1 MULTICHIP finding).
    with jax.named_scope("gpt2.embed"):
        wte = wlc(params["wte"], P(None, "act_embed"), mesh)
        x = wte[tokens] + params["wpe"][:s][None]
        x = wlc(x, P("batch", "seq", "act_embed"), mesh)

    block = functools.partial(_block, cfg=cfg, mesh=mesh)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            block = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif cfg.remat_policy == "dots_all":
            # Save EVERY contraction result (batched included — our
            # einsums all carry a batch dim, so the no-batch-dims variant
            # saves nothing and degenerates to full remat).  Backward then
            # recomputes only elementwise chains (LN/gelu/residual): a few
            # percent of executed FLOPs instead of a full second forward.
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.dots_saveable
            )
        elif cfg.remat_policy == "matmuls":
            # Save the tagged projection outputs (+ the attention-branch
            # residual so bwd needn't replay attention to rebuild the MLP
            # branch input); recompute only elementwise chains.
            block = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "qkv", "attn_out", "attn_resid", "mlp_hidden"
                ),
            )
        elif cfg.remat_policy == "save_mlp":
            block = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "mlp_hidden"
                ),
            )
        else:
            block = jax.checkpoint(block)

    def scan_body(x, layer):
        return block(x, layer), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("gpt2.head"):  # the final norm is the head's
        return layernorm(x, params["lnf_g"], params["lnf_b"])


def gpt2_apply(params, tokens, cfg: GPT2Config, mesh=None):
    """tokens: [B, S] int32 → logits [B, S, V]."""
    from ..parallel.sharding import with_logical_constraint as wlc

    x = gpt2_hidden(params, tokens, cfg, mesh)
    with jax.named_scope("gpt2.head"):
        logits = jnp.einsum("bse,ve->bsv", x, params["wte"])
        return wlc(logits, P("batch", "seq", "vocab"), mesh)


def _ce_from_logits(logits, targets, z_loss: float):
    """Summed (not mean) next-token NLL with f32 reduction arithmetic fused
    into the bf16 logits (no f32 [.., V] materialization)."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold.astype(jnp.float32)).sum()
    if z_loss > 0:
        nll = nll + z_loss * (logz ** 2).sum()
    return nll


def gpt2_loss(
    params, tokens, cfg: GPT2Config, mesh=None, z_loss: float = 0.0,
    ce_chunks: int = 0,
):
    """Next-token cross-entropy.  tokens: [B, S+1] (inputs = [:, :-1]).

    ``ce_chunks > 0`` evaluates the unembedding + CE in that many
    rematerialized sequence chunks: peak memory holds one [B, S/c, V]
    logits block instead of [B, S, V] (the classic blockwise-CE recipe;
    the unembed matmul is recomputed chunkwise in backward).  This is what
    lets the single-chip train batch double on a 16G-HBM chip.
    """
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = gpt2_hidden(params, inputs, cfg, mesh)
    b, s, e = x.shape
    if ce_chunks > 1 and s % ce_chunks != 0:
        raise ValueError(
            f"ce_chunks={ce_chunks} must divide the sequence length {s} "
            "(silently falling back would materialize the full [B,S,V] "
            "logits the caller asked to avoid)"
        )
    with jax.named_scope("gpt2.head"):  # logits and cross-entropy
        if ce_chunks <= 1:
            logits = jnp.einsum("bse,ve->bsv", x, params["wte"])
            from ..parallel.sharding import with_logical_constraint as wlc

            logits = wlc(logits, P("batch", "seq", "vocab"), mesh)
            return _ce_from_logits(logits, targets, z_loss) / (b * s)

        c = s // ce_chunks
        xs = x.reshape(b, ce_chunks, c, e).swapaxes(0, 1)  # [n, B, C, E]
        ts = targets.reshape(b, ce_chunks, c).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_nll(wte, x_c, t_c):
            logits = jnp.einsum("bce,ve->bcv", x_c, wte)
            return _ce_from_logits(logits, t_c, z_loss)

        def body(acc, xt):
            x_c, t_c = xt
            return acc + chunk_nll(params["wte"], x_c, t_c), None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ts))
        return total / (b * s)
