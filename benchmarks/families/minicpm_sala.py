"""Family ``minicpm_sala``: MiniCPM-SALA-style hybrid decoders through
``MinicpmSalaConfig`` (a lightning state beside a key/value cache and a cache
of pooled keys of a few position-free sparse attention layers, output gates,
a SwiGLU in every layer, three muP scales), found by the ``family`` key of a
file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes`` and ``prefill_flops``
(``lib/flops_minicpm_sala.py``).
"""

from __future__ import annotations

import dataclasses

from benchmarks.lib import flops_minicpm_sala
from benchmarks.reference.minicpm_sala_ref import minicpm_sala_ref_logits
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import MinicpmSalaConfig

# Standard deviations the weights are drawn at.  Weights are free; what is
# wanted of them is that the harness's check (``bench_server.
# check_reference``: the first two layers, ``S L``, the worst of four
# positions against 3 % of the logits' spread) and the all-layers script SEE
# the mixers, the state's carry and the SELECTION through the muP scales, and
# that rounding alone stays under the limit twelve layers deep.  Two things
# this model does to rounding that its siblings do not, both measured on the
# chip before this draw was settled (PERF.md, PR 62: with mixers of 0.35 and
# MLPs of 0.25 in the stream, Granite's shares, and scores of spread 2.5, a
# row that never selects read 2.4 % at the median and the rows that select
# 6.5-7 %, with the program right to 1e-6 in float32):
#   (a) a sparse layer's ``qk_norm`` makes its scores a function of the
#     stream's DIRECTION, and a softmax of spread ``s`` turns an error of
#     ``e`` in its input into ``~3 s e`` in its largest weights: what the
#     layers above left is amplified, not added to;
#   (b) the selection RANKS: an error ``e`` in a sparse layer's input moves a
#     block's score by ``~1.4 e`` of the scores' spread, whatever their scale,
#     and the 64th and 65th of ~110 ranked blocks lie ~2 % of the spread
#     apart: at ``e`` = 1 % most queries swap a block at the rank's edge,
#     and a swapped block is a 64th of the query's read (12 % of a layer's
#     output by the arithmetic of independent values).  That is rounding (a
#     trained model's edge blocks carry next to no weight; random values
#     do), and what it costs is set by the sparse branch's share of the
#     stream.
# So the branches are drawn SMALLER than the siblings' and the sparse one
# smallest; the scales are the published ones; the draw around them:
#   the table at 0.5 / scale_emb: the stream starts at RMS 0.5 (12 x
#     0.0417); the head (untied) at 0.25: logits of spread sqrt(4096) x 0.25
#     / 16 = 1;
#   every branch reaches the stream through r = 1.4 / sqrt(32) = 0.2475: a
#     lightning mixer's output is drawn at RMS ~0.8 (0.2 in the stream), an
#     MLP's at ~0.6 (0.15), a sparse mixer's at ~0.18 (0.044): twenty-four
#     branches take the stream from 0.5 to ~0.9, two thirds of its variance
#     theirs.  (With the sparse mixer at 0.1 in the stream the rows that
#     select read 2.9 % at the median and 6.8 % at the 99th percentile on
#     the chip, the row that does not 1.2 % / 1.3 %, and a reference that
#     selects by recency 43 %: the edge's cost follows the sparse share, and
#     a share of 0.044 still leaves that control six times the limit);
#   lightning: the normed, gated read-out has RMS ~0.5 whatever went in (the
#     output norm makes it 1, a gate of pre-activation spread 1.3 halves
#     it), so ``Wo [4096, 4096]`` at 0.025 gives 0.5 x 0.025 x 64 = 0.8; the
#     decay is the model's own (slopes of 0.0014-0.6 a position in layers
#     9-20: a head forgets over 2 to 700 positions), so a wrong carry across
#     a chunk, a rung's padding or a hundred decode steps is still there
#     when the check reads, and a state in bfloat16 drifts in the slow heads;
#   sparse attention: ``qk_norm`` makes q and k of RMS 1 a head whatever
#     ``Wq`` / ``Wk`` are drawn at, so the spread of the scores is set by the
#     norms' learned weights: q_norm = k_norm = 1.4 give ``q . k / sqrt(128)``
#     a spread of 2.0, neither uniform nor one-hot over 4096 positions (sum
#     of p^2 ~0.011: a read is worth ~90 positions), and against a POOLED key
#     (the mean of 32 keys: 1 / sqrt(32) of one) a spread of 0.35: the
#     softmax over the visible windows varies by e^+-0.35, a block's score
#     (sixteen heads summed, the best of five windows) by +-8 %, three
#     orders over the float32 scores' rounding: the selection RANKS, and
#     another rule (the most recent blocks) reads other positions, 30 of the
#     64 blocks a query; ``Wv`` at 0.02 (values of RMS 1.3), ``Wo`` at 0.04:
#     0.107 x 0.5 x 1.3 x 0.04 x 64 = 0.18;
#   the gates ``Wg`` at 0.02: pre-activations of spread 1.3, gates of
#     0.2-0.8: a program without its gate is wrong by half of every head;
#   MLP: pre-activations of spread 1.3 (``W_in`` at 0.02), ``silu(g) h`` of
#     RMS ~0.5, ``W_down [16384, 4096]`` at 0.0094 gives 0.6;
#   the head's row for the tokenizer's stop id is zero: a greedy stream
#     never ends before its ``max_tokens`` (Laguna's lesson).
SCALES = {"embed": 0.5, "head": 0.25, "in": 0.02, "lightning_out": 0.025,
          "attn_out": 0.04, "mlp_out": 0.0094, "qk_norm": 1.4}


def config(model: dict) -> MinicpmSalaConfig:
    return MinicpmSalaConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``minicpm_sala_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    ns, nl = (cfg.layer_pattern.count(c) for c in "SL")
    n, F = len(cfg.layer_pattern), cfg.d_ff
    H, D = cfg.lightning_heads, cfg.lightning_head_dim
    s = SCALES

    def build(key):
        k = iter(jax.random.split(key, 24))

        def stacked(shape, scale):
            """One matrix of the stack drawn at a time: small temporaries."""
            scale = jnp.asarray(scale, dt)
            return jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[1:], dt) * scale,
                jax.random.split(next(k), shape[0]))

        def table(scale):
            return jax.random.normal(next(k), (cfg.vocab_size, d), dt) * (
                jnp.asarray(scale, dt))

        return {
            "wte": table(s["embed"] / cfg.scale_emb),
            "blocks": {
                "sparse": {
                    "rms": jnp.ones((ns, d), dt),
                    "wq": stacked((ns, d, cfg.n_head, cfg.head_dim), s["in"]),
                    "wk": stacked((ns, d, cfg.n_kv_head, cfg.head_dim),
                                  s["in"]),
                    "wv": stacked((ns, d, cfg.n_kv_head, cfg.head_dim),
                                  s["in"]),
                    "wg": stacked((ns, d, cfg.n_head, cfg.head_dim), s["in"]),
                    "wo": stacked((ns, cfg.n_head, cfg.head_dim, d),
                                  s["attn_out"]),
                    "q_norm": jnp.full((ns, cfg.head_dim), s["qk_norm"], dt),
                    "k_norm": jnp.full((ns, cfg.head_dim), s["qk_norm"], dt),
                },
                "lightning": {
                    "rms": jnp.ones((nl, d), dt),
                    "wq": stacked((nl, d, H, D), s["in"]),
                    "wk": stacked((nl, d, H, D), s["in"]),
                    "wv": stacked((nl, d, H, D), s["in"]),
                    "wg": stacked((nl, d, H, D), s["in"]),
                    "wo": stacked((nl, H, D, d), s["lightning_out"]),
                    "q_norm": jnp.ones((nl, D), dt),
                    "k_norm": jnp.ones((nl, D), dt),
                    "o_norm": jnp.ones((nl, H, D), dt),
                },
                "mlp": {
                    "rms": jnp.ones((n, d), dt),
                    "w_gate": stacked((n, d, F), s["in"]),
                    "w_up": stacked((n, d, F), s["in"]),
                    "w_down": stacked((n, F, d), s["mlp_out"]),
                },
            },
            "rms_f": jnp.ones((d,), dt),
            # no greedy stream ends before its max_tokens (``SCALES``)
            "lm_head": table(s["head"]).at[ByteTokenizer.EOS].set(0),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: MinicpmSalaConfig) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: MinicpmSalaConfig, prompt_len=None):
    """``prompt_len``: positions before it by ONE prefill's rule, later ones
    by their own decode step's (the harness's 67 positions lie under
    ``dense_len`` either way)."""
    return minicpm_sala_ref_logits(params, tokens, sizes_of(cfg), cfg.kinds,
                                   prompt_len)


decode_flops_per_token = flops_minicpm_sala.decode_flops_per_token
decode_step_bytes = flops_minicpm_sala.decode_step_bytes
prefill_flops = flops_minicpm_sala.prefill_flops
