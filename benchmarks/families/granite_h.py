"""Family ``granite_h``: Granite-4.0-H-style hybrid decoders through
``GraniteHConfig`` (Mamba-2 state beside a key/value cache of a few
position-free attention layers, a SwiGLU in every layer, four multipliers, a
tied head), found by the ``family`` key of a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes`` and ``prefill_flops``
(``lib/flops_granite_h.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_granite_h
from benchmarks.reference.granite_h_ref import granite_h_ref_logits
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import GraniteHConfig

# Standard deviations the weights are drawn at.  Weights are free; what is
# wanted of them is that the harness's check (``bench_server.
# check_reference``: the first two layers, ``MM``, the worst of four
# positions against 3 % of the logits' spread) and the all-layers script SEE
# the layers and the state's carry THROUGH the multipliers, and that rounding
# alone stays under the limit forty layers deep (PERF.md, PR 52 and PR 56:
# branches as large as the stream carried layer 0's rounding to the logits
# fourteen times larger).  The multipliers are the published ones; the
# scales are chosen around them:
#   the table at 0.5 / embedding_multiplier: the stream starts at RMS 0.5
#     (12 x 0.0417), and the tied head's logits are ``RMSNorm(x) . E`` over
#     8: spread sqrt(2048) x 0.0417 / 8 = 0.24, but for the logit of a token
#     whose embedding is still in the stream, which stands far above the
#     rest: a greedy stream soon repeats one id.  That is what tying does to
#     random weights, not a fault, and it keeps a greedy stream's ids far
#     from any tie;
#   every branch reaches the stream through ``residual_multiplier`` 0.22: a
#     Mamba-2 mixer's output is drawn at RMS 1.6 (0.35 in the stream), an
#     MLP's and an attention's at 1.1 (0.25): eighty branches take the
#     stream from 0.5 to ~2.7, and the two layers the harness cuts are 0.6
#     of a stream of 0.8: matrices at three bits read 3.3-4.3 % there with
#     the table at RMS 1 and 4.7-5.2 % with it at 0.5 (CPU; d 512 for the
#     second pair), the program 0.24-0.45 %; forty layers deep the program
#     read 0.75 % and 1.4 % (CPU, d 512: PERF.md, PR 60);
#   Mamba-2: the gated, normed ``y`` has RMS 1 whatever went in, so ``W_out
#     [4096, 2048]`` at 0.025 gives 0.025 x 64 = 1.6;
#   MLP: pre-activations of spread 0.9 (``W_in`` at 0.02), ``silu(g) h`` of
#     RMS ~0.4, ``W_out [8192, 2048]`` at 0.025 gives 1.1;
#   attention: the published scale is 1/64, an eighth of ``D^-1/2``: ``Wq``,
#     ``Wk`` at 0.08 give q, k of spread 3.6 and scores of 3.6^2 x 8 / 64 =
#     1.6, neither uniform nor one-hot (at 0.02 they would be 0.1: uniform,
#     and the scale would not show); ``Wv`` at 0.02, ``Wo`` at 0.075;
#   Mamba-2's dynamics: a head's decay ``delta = dt A`` is drawn
#     log-uniformly in [1e-4, 0.1] (``exp(-delta)`` in 0.905-0.9999 before
#     ``W_dt`` at 0.01 moves it by the token: a head forgets over five to ten
#     thousand tokens, so a wrong carry across a chunk, a rung's padding or a
#     hundred decode steps is still there when the check reads) and its step
#     ``dt = step_gain sqrt(delta)``, ``A = delta / dt``: what a head's state
#     adds to ``y`` beside the skip ``D x`` (``D`` = 1) is then about the
#     same for a slow head as for a fast one (a sum of ``1 / delta`` terms of
#     ``dt``: ``dt / sqrt(delta)``), and the state is a good share of every
#     head's output: the all-layers script's bfloat16-state control has to
#     see it.  (The published init, ``A`` = 1..H and steps in 0.001-0.1,
#     leaves a slow head's state a hundredth of its skip.)
#   the convolution's taps at 0.3 (Nemotron's), its bias at 0.1 (the
#     published ``mamba_conv_bias`` is true: a bias of zero would not show a
#     bias left out);
#   the table's row for the tokenizer's stop id is zero: a greedy stream
#     never ends before its ``max_tokens`` (Laguna's lesson), and with a
#     tied table that id's embedding is zero too (no prompt holds it).
SCALES = {"embed": 0.5, "in": 0.02, "mamba_out": 0.025, "mlp_out": 0.025,
          "qk": 0.08, "attn_out": 0.075, "dt_in": 0.01, "conv": 0.3,
          "conv_bias": 0.1, "decay_min": 1e-4, "decay_max": 0.1,
          "step_gain": 1.0}


def config(model: dict) -> GraniteHConfig:
    return GraniteHConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``granite_h_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nm, na = (cfg.layer_pattern.count(c) for c in "M*")
    nl, H, C, F = (len(cfg.layer_pattern), cfg.mamba_num_heads, cfg.d_conv,
                   cfg.d_ff)
    s = SCALES

    def build(key):
        k = iter(jax.random.split(key, 24))

        def stacked(shape, scale, dtype=dt):
            """One matrix of the stack drawn at a time: small temporaries."""
            scale = jnp.asarray(scale, dtype)
            return jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[1:], dtype) * scale,
                jax.random.split(next(k), shape[0]))

        decay = jnp.exp(jax.random.uniform(
            next(k), (nm, H), minval=math.log(s["decay_min"]),
            maxval=math.log(s["decay_max"])))
        step = s["step_gain"] * jnp.sqrt(decay)
        table = jax.random.normal(next(k), (cfg.vocab_size, d), dt) * (
            jnp.asarray(s["embed"] / cfg.embedding_multiplier, dt))
        return {
            # no greedy stream ends before its max_tokens (``SCALES``)
            "wte": table.at[ByteTokenizer.EOS].set(0),
            "blocks": {
                "mamba": {
                    "rms": jnp.ones((nm, d), dt),
                    "w_z": stacked((nm, d, cfg.d_inner), s["in"]),
                    "w_xbc": stacked((nm, d, C), s["in"]),
                    "w_dt": stacked((nm, d, H), s["dt_in"]),
                    "conv_w": stacked((nm, cfg.conv_kernel, C), s["conv"],
                                      jnp.float32),
                    "conv_b": stacked((nm, C), s["conv_bias"], jnp.float32),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(decay / step),
                    "d_skip": jnp.ones((nm, H), jnp.float32),
                    "norm": jnp.ones((nm, cfg.d_inner), dt),
                    "w_out": stacked((nm, cfg.d_inner, d), s["mamba_out"]),
                },
                "attn": {
                    "rms": jnp.ones((na, d), dt),
                    "wq": stacked((na, d, cfg.n_head, cfg.head_dim), s["qk"]),
                    "wk": stacked((na, d, cfg.n_kv_head, cfg.head_dim),
                                  s["qk"]),
                    "wv": stacked((na, d, cfg.n_kv_head, cfg.head_dim),
                                  s["in"]),
                    "wo": stacked((na, cfg.n_head, cfg.head_dim, d),
                                  s["attn_out"]),
                },
                "mlp": {
                    "rms": jnp.ones((nl, d), dt),
                    "w_gate": stacked((nl, d, F), s["in"]),
                    "w_up": stacked((nl, d, F), s["in"]),
                    "w_down": stacked((nl, F, d), s["mlp_out"]),
                },
            },
            "rms_f": jnp.ones((d,), dt),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: GraniteHConfig) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: GraniteHConfig):
    return granite_h_ref_logits(params, tokens, sizes_of(cfg), cfg.kinds)


decode_flops_per_token = flops_granite_h.decode_flops_per_token
decode_step_bytes = flops_granite_h.decode_step_bytes
prefill_flops = flops_granite_h.prefill_flops
