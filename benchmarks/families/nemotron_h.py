"""Family ``nemotron_h``: Nemotron-H-style hybrid decoders through
``NemotronHConfig`` (Mamba-2 state beside a key/value cache, LatentMoE
expert layers with a held share of the experts), found by the ``family`` key
of a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes`` and ``held_expert_slots``
(``lib/flops_nemotron_h.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_nemotron_h
from benchmarks.reference.nemotron_h_ref import nemotron_h_ref_logits
from ray_tpu.models import NemotronHConfig

# Standard deviations the weights are drawn at, and one piece of structure.
# Weights are free, and two things are wanted of them at once.  (1) The
# harness's check (``bench_server.check_reference``: the first two layers,
# ``ME``, the worst of four positions against 3 % of the logits' spread) must
# SEE the layers: the mixers are most of the stream (embedding RMS 1; a
# Mamba-2 layer adds 1.0, the shared expert 0.7, the held experts' part 0.7),
# so that the blocks' matrices at three bits of mantissa read 4.3-10 % at
# every one of 512 positions and the program 0.27-0.39 % (CPU, published
# widths; the chip's readings: PERF.md, PR 39).  (2) No routing choice may
# flip: sigmoid scores renormalised over the 22 chosen weigh all 22 about
# alike, the 22nd and the 23rd of 512 are near-tied at every token (their gap
# is 2 % of the logits' spread), and on a chip that holds a quarter of the
# experts a flipped choice adds or drops a WHOLE expert, here a sixth of the
# stream.  With routers that read the stream the mixers write, bf16 rounding
# upstream flipped the choice in 3-36 % of the tokens a layer (PERF.md, PR
# 39), and the first cure, an embedding at RMS 8 that made a flip small, made
# everything else small with it: float8 in the blocks read 0.43 % (the review
# of PR 39).  So the cure is in the ROUTERS' weights: the first ``d /
# router_share`` channels of the stream are the routers': every router's
# rows are zero elsewhere, and every mixer's output matrix (Mamba-2
# ``out_proj``, ``Wo``, ``W_ul``, ``W_s2``) has zero columns there.  Those
# channels carry the token's embedding, exactly, through every layer; what
# the program rounds upstream reaches a router only through the norm's one
# common factor, which moves all 512 logits alike and so no choice.  The
# routers then choose by the token alone (a hash layer's routing; every
# layer its own matrix, so its own choice), 22 of 512 with all experts in
# play, and the mixers read all 4096 channels as before.  The program is the
# same for any weights: this is a property of the draw.
#   Mamba-2: the gated, group-normed ``y`` has RMS 1 whatever went in, so
#     ``W_out [8192, 4096]`` at 0.011 gives 0.011 * sqrt(8192) = 1.0;
#   ``relu^2`` MLPs square their pre-activations: ``W_s1`` at 0.01 gives
#     pre-activations of 0.64, ``relu^2`` of RMS 1.22 * 0.64^2 = 0.50, and
#     ``W_s2 [5376, 4096]`` at 0.02 an output of 0.02 * sqrt(5376) * 0.50 =
#     0.73; the routed experts likewise: ``W_dl`` 0.02 (latent RMS 1.28),
#     ``W1`` 0.02, ``W2`` 0.02: one expert 0.85 in the latent, weighed 5 / 22,
#     the ~5.5 held of a token's 22 come to 0.45, and ``W_ul`` at 0.05 to 0.7;
#   attention: ``Wq/Wk/Wv`` 0.02 (scores of spread 1.6: neither uniform nor
#     one-hot), ``Wo`` 0.02;
#   router: 0.4 on its 256 channels, whose normed values are the embedding's
#     over the stream's RMS (1.7 after two layers, ~3.5 after eleven):
#     logits of spread 0.4 * 16 * (0.3 to 0.6) = 2 to 4 before the sigmoid.
# Eleven layers take the stream from RMS 1 to ~3.5: no layer is small beside
# it.  Mamba-2's dynamics are the family's own init: ``A`` in [1, 16], steps
# around 0.001-0.1 (``dt_bias``) widened by ``W_dt`` at 0.02, ``D`` 1, taps
# at 0.3: a head forgets over ten to a few hundred tokens.
SCALES = {"embed": 1.0, "in": 0.02, "out": 0.02, "mamba_out": 0.011,
          "shared_in": 0.01, "shared_out": 0.02, "latent_out": 0.05,
          "router": 0.4, "router_share": 16, "conv": 0.3}


def config(model: dict) -> NemotronHConfig:
    return NemotronHConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``nemotron_h_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    nm, na, ne = (cfg.layer_pattern.count(c) for c in "M*E")
    H, C, lat = cfg.mamba_num_heads, cfg.d_conv, cfg.moe_latent_size
    s = SCALES

    def build(key):
        k = iter(jax.random.split(key, 24))

        def flat(shape, scale):
            return jax.random.normal(next(k), shape, dt) * jnp.asarray(scale, dt)

        def stacked(shape, scale, lead=1, dtype=dt, mask=None):
            """``lead`` stacked axes (layer, then expert), one matrix drawn
            at a time: small temporaries.  ``mask`` multiplies each."""
            n = math.prod(shape[:lead])
            scale = jnp.asarray(scale, dtype) * (
                1 if mask is None else mask.astype(dtype))
            out = jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[lead:], dtype) * scale,
                jax.random.split(next(k), n))
            return out.reshape(shape)

        # The router's channels: read by the routers alone, written by no
        # mixer (``SCALES``' comment).
        routed_by = jnp.arange(d) < max(1, d // s["router_share"])
        mixed = ~routed_by

        step = jnp.exp(jax.random.uniform(
            next(k), (nm, H), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {
            "wte": flat((cfg.vocab_size, d), s["embed"]),
            "blocks": {
                "mamba": {
                    "rms": jnp.ones((nm, d), dt),
                    "w_z": stacked((nm, d, cfg.d_inner), s["in"]),
                    "w_xbc": stacked((nm, d, C), s["in"]),
                    "w_dt": stacked((nm, d, H), s["in"]),
                    "conv_w": stacked((nm, cfg.conv_kernel, C), s["conv"],
                                      1, jnp.float32),
                    "conv_b": jnp.zeros((nm, C), jnp.float32),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(jax.random.uniform(
                        next(k), (nm, H), minval=1.0, maxval=16.0)),
                    "d_skip": jnp.ones((nm, H), jnp.float32),
                    "norm": jnp.ones((nm, cfg.d_inner), dt),
                    "w_out": stacked((nm, cfg.d_inner, d), s["mamba_out"],
                                     mask=mixed),
                },
                "attn": {
                    "rms": jnp.ones((na, d), dt),
                    "wq": stacked((na, d, cfg.n_head, cfg.head_dim), s["in"]),
                    "wk": stacked((na, d, cfg.n_kv_head, cfg.head_dim),
                                  s["in"]),
                    "wv": stacked((na, d, cfg.n_kv_head, cfg.head_dim),
                                  s["in"]),
                    "wo": stacked((na, cfg.n_head, cfg.head_dim, d),
                                  s["out"], mask=mixed),
                },
                "moe": {
                    "rms": jnp.ones((ne, d), dt),
                    "router": stacked((ne, d, cfg.n_routed_experts),
                                      s["router"], 1, jnp.float32,
                                      mask=routed_by[:, None]),
                    "router_bias": jnp.zeros((ne, cfg.n_routed_experts),
                                             jnp.float32),
                    "w_dl": stacked((ne, d, lat), s["in"]),
                    "w_ul": stacked((ne, lat, d), s["latent_out"], mask=mixed),
                    "ws1": stacked((ne, d, cfg.d_shared), s["shared_in"]),
                    "ws2": stacked((ne, cfg.d_shared, d), s["shared_out"],
                                   mask=mixed),
                },
            },
            "experts": {
                "w1": stacked((ne, cfg.experts_held, lat, cfg.d_expert),
                              s["in"], 2),
                "w2": stacked((ne, cfg.experts_held, cfg.d_expert, lat),
                              s["out"], 2),
            },
            "rms_f": jnp.ones((d,), dt),
            "lm_head": flat((cfg.vocab_size, d), s["in"]),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: NemotronHConfig) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: NemotronHConfig):
    return nemotron_h_ref_logits(params, tokens, sizes_of(cfg), cfg.kinds,
                                 cfg.expert_offset)


decode_flops_per_token = flops_nemotron_h.decode_flops_per_token
decode_step_bytes = flops_nemotron_h.decode_step_bytes
held_expert_slots = flops_nemotron_h.held_expert_slots
