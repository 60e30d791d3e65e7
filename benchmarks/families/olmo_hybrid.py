"""Family ``olmo_hybrid``: Olmo-Hybrid-style decoders through
``OlmoHybridConfig`` (gated-delta-rule linear attention, a matrix of state a
head beside a key/value cache of the full layers), found by the ``family`` key
of a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes`` and ``prefill_flops``
(``lib/flops_olmo_hybrid.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_olmo_hybrid
from benchmarks.reference.olmo_hybrid_ref import olmo_hybrid_ref_logits
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import OlmoHybridConfig

# Standard deviations the weights are drawn at.  Weights are free; what is
# wanted of them is that the harness's check (``bench_server.
# check_reference``: the first two layers, ``FL``, the worst of four
# positions against 3 % of the logits' spread) and the all-layers script SEE
# the layers and the state's carry, and that rounding alone stays under the
# limit twelve layers deep:
#   the embedding has RMS 1; a linear layer's mixer adds 0.5 to the stream
#     and every other branch 0.25.  The first draw had every branch add 1
#     (the siblings' rule) and read 3.9 % over all twelve layers on the chip
#     where two layers read 0.7 (PERF.md, PR 56): a branch's bfloat16
#     rounding (0.3-0.6 % of the branch) made in layer 0 reached the logits
#     FOURTEEN times larger (a relative perturbation of 1e-3 put into
#     layer 0's input moved the logits by 1.4 %, into layer 4's by 0.27 %,
#     into layer 11's by 0.11 %: CPU, width 1024, twelve layers): every later
#     branch reads a stream that is still small beside what the branch
#     adds, so errors added up with the depth, not in quadrature.
#     Smaller branches make every branch's error a smaller share of the
#     stream (RMS 1.2 after two layers, 2.0-2.5 after twelve) and lower the
#     gain (the same perturbation: 0.8 % from layer 0).  The linear mixer is
#     left the largest because its state is what the all-layers script's
#     bfloat16-state control has to see.
#     A full layer's two branches are normed on their way OUT (Olmo 3's
#     wiring): the norms' weights ARE their size, 0.25, whatever their
#     matrices' scales.  A linear layer's mixer: the gated, normed ``y`` has
#     RMS ~0.6 (``silu(z)`` at ``z`` of spread 1.2), so ``Wo [5760, 3840]`` at
#     0.011 gives 0.011 * sqrt(5760) * 0.6 = 0.5; its MLP: pre-activations of
#     spread 1.2, ``silu(g) * u`` of RMS ~0.75, ``W2 [11008, 3840]`` at 0.0032
#     gives 0.25;
#   full attention: q and k are normed over their whole projection, so a
#     score is a sum of 128 products of unit numbers over sqrt(128): spread 1,
#     neither uniform nor one-hot;
#   the delta rule's dynamics (the Gated DeltaNet's init, widened so that
#     the state is OLD when it is read): ``A = U(1, 16)``, and ``dt_bias``
#     the inverse softplus of ``-log(alpha) / A`` for a decay ``-log(alpha)``
#     drawn log-uniformly in [0.001, 0.1] a head: ``alpha`` in 0.905-0.999
#     before ``Wa`` (at 0.01: a factor of spread 0.6 in the exponent) moves
#     it by the token, so a head forgets over ten to a thousand tokens and a
#     wrong carry across a chunk or a prefill's end is not decayed away
#     before the check reads it; ``Wb`` at 0.02 gives ``b`` of spread 1.2 and
#     ``beta = 2 sigmoid(b)`` over (0.2, 1.8), half of it past 1: the
#     negative-eigenvalue branch is in every head;
#   the convolution's taps at 0.3 (Nemotron's);
#   the head's row for the tokenizer's stop id is zero: a greedy stream
#     never ends before its ``max_tokens`` (Laguna's lesson: a reply that
#     stops early makes the tokens of a window depend on the seed's prompts).
SCALES = {"embed": 1.0, "in": 0.02, "delta_out": 0.011, "mlp_out": 0.0032,
          "full_out": 0.25, "decay_in": 0.01, "conv": 0.3, "decay_min": 1e-3,
          "decay_max": 0.1}


def config(model: dict) -> OlmoHybridConfig:
    return OlmoHybridConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``olmo_hybrid_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nl, nf = (cfg.layer_pattern.count(c) for c in "LF")
    H, F, s = cfg.linear_num_heads, cfg.d_ff, SCALES

    def build(key):
        k = iter(jax.random.split(key, 32))

        def flat(shape, scale):
            return jax.random.normal(next(k), shape, dt) * jnp.asarray(scale, dt)

        def stacked(shape, scale, dtype=dt):
            """One matrix of the stack drawn at a time: small temporaries."""
            scale = jnp.asarray(scale, dtype)
            return jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[1:], dtype) * scale,
                jax.random.split(next(k), shape[0]))

        def mlp(n):
            return {"w_gate": stacked((n, d, F), s["in"]),
                    "w_up": stacked((n, d, F), s["in"]),
                    "w_down": stacked((n, F, d), s["mlp_out"])}

        def heads():
            return stacked((nf, d, cfg.n_head, cfg.head_dim), s["in"])

        a = jax.random.uniform(next(k), (nl, H), minval=1.0, maxval=16.0)
        step = jnp.exp(jax.random.uniform(
            next(k), (nl, H), minval=math.log(s["decay_min"]),
            maxval=math.log(s["decay_max"]))) / a
        return {
            "wte": flat((cfg.vocab_size, d), s["embed"]),
            "blocks": {
                "linear": {
                    "rms_mix": jnp.ones((nl, d), dt),
                    "rms_mlp": jnp.ones((nl, d), dt),
                    "w_qkv": stacked((nl, d, cfg.d_conv), s["in"]),
                    "w_g": stacked((nl, d, cfg.d_value), s["in"]),
                    "w_a": stacked((nl, d, H), s["decay_in"]),
                    "w_b": stacked((nl, d, H), s["in"]),
                    "conv_w": stacked((nl, cfg.conv_kernel, cfg.d_conv),
                                      s["conv"], jnp.float32),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(a),
                    "norm": jnp.ones((nl, cfg.linear_value_head_dim), dt),
                    "w_o": stacked((nl, cfg.d_value, d), s["delta_out"]),
                    **mlp(nl),
                },
                "full": {
                    # norms on the branches' OUTPUTS: their size
                    "rms_mix": jnp.full((nf, d), s["full_out"], dt),
                    "rms_mlp": jnp.full((nf, d), s["full_out"], dt),
                    "wq": heads(), "wk": heads(), "wv": heads(),
                    "q_norm": jnp.ones((nf, cfg.n_head, cfg.head_dim), dt),
                    "k_norm": jnp.ones((nf, cfg.n_head, cfg.head_dim), dt),
                    "wo": stacked((nf, cfg.n_head, cfg.head_dim, d), s["in"]),
                    **mlp(nf),
                },
            },
            "rms_f": jnp.ones((d,), dt),
            # no greedy stream ends before its max_tokens (``SCALES``)
            "lm_head": flat((cfg.vocab_size, d), s["in"]).at[
                ByteTokenizer.EOS].set(0),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: OlmoHybridConfig) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: OlmoHybridConfig):
    return olmo_hybrid_ref_logits(params, tokens, sizes_of(cfg), cfg.kinds)


decode_flops_per_token = flops_olmo_hybrid.decode_flops_per_token
decode_step_bytes = flops_olmo_hybrid.decode_step_bytes
prefill_flops = flops_olmo_hybrid.prefill_flops
