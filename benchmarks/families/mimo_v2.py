"""Family ``mimo_v2``: MiMo-V2-style decoders through ``MimoV2Config``
(window layers whose cache is a ring beside full layers, a learned sink,
key heads wider than value heads, expert layers with a held share of the
experts), found by the ``family`` key of a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes`` and ``held_expert_slots``
(``lib/flops_mimo_v2.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_mimo_v2
from benchmarks.reference.mimo_v2_ref import mimo_v2_ref_logits
from ray_tpu.models import MimoV2Config

# Standard deviations the weights are drawn at, and one piece of structure
# (``families/nemotron_h.py`` has the long form of the argument).  Two things
# are wanted at once.  (1) The harness's check (``bench_server.
# check_reference``: the first two layers, full attention + dense MLP then
# window attention + experts, the worst of four positions against 3 % of the
# logits' spread) must SEE the layers: the embedding has RMS 1 and every
# layer adds about as much again.  (2) No routing choice may flip: sigmoid
# scores renormalised over the 8 chosen weigh them about alike, the 8th and
# the 9th of 256 are near-tied at every token, and on a chip that holds a
# sixteenth of the experts a flipped choice adds or drops a WHOLE expert at
# an eighth of the routed mass.  So, as there, the first ``d /
# router_share`` channels of the stream are the routers': every router's
# rows are zero elsewhere, and every output matrix (``Wo``, the dense
# ``W_down``, the experts' ``W_down``) has zero columns there.  Those
# channels carry the token's embedding, exactly, through every layer; what
# the program rounds upstream reaches a router only through the norm's one
# common factor, which moves all 256 logits alike and so no choice.  The
# program is the same for any weights: this is a property of the draw.
#   attention: ``Wq/Wk/Wv`` 0.02 on a normed input of 4096: heads of spread
#     1.28, scores ``q k / sqrt(192)`` of spread 1.6 (neither uniform nor
#     one-hot), values 0.9; a softmax over tens of positions averages them
#     to 0.2-0.3, and ``Wo [8192, 4096]`` at 0.04 gives 3.6 x that: 0.7-1;
#   dense MLP: gate and up of spread 1.28, ``silu(g) u`` RMS 0.95, ``W_down
#     [16384, 4096]`` at 0.008: 1.0;
#   experts: the same hidden RMS, ``W_down [2048, 4096]`` at 0.13: one expert
#     5.6, weighed about 1/8: 0.7 for each of a token's choices that is held
#     here (0.5 a token in expectation: six tokens in ten choose none of the
#     sixteen, and the layer adds nothing to them);
#   router: 0.4 on its 256 channels, whose normed values are the embedding's
#     over the stream's RMS (1.4 after a layer, ~3 after seven): logits of
#     spread 0.4 * 16 * (0.3 to 0.7) = 2 to 4.5 before the sigmoid;
#   sink: normal around 5 a head.  A row's 128 scores of spread 1.6 have a
#     log-sum-exp of ln 128 + 1.6^2 / 2 = 6.1, so a sink of 5 takes a quarter
#     of the row's mass at a full window (most of it in a prompt's first
#     positions), as a trained sink does.  Drawn STANDARD normal, as the
#     family's init draws it, it takes 0.2 %: the all-layers comparison with
#     the sinks taken out then read 1.0 % against the program's 0.4 % and
#     the limit's 3 (my chip run, PR 45, call 2), and a program that forgot
#     the sink would have passed.
SCALES = {"embed": 1.0, "in": 0.02, "attn_out": 0.04, "dense_out": 0.008,
          "expert_out": 0.13, "router": 0.4, "router_share": 16,
          "sink_mean": 5.0, "sink": 1.0}


def config(model: dict) -> MimoV2Config:
    return MimoV2Config(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``mimo_v2_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    H, D, Dv, Eh = cfg.n_head, cfg.head_dim, cfg.v_head_dim, cfg.experts_held
    n = {kind: (cfg.attn_pattern + cfg.mlp_pattern).count(kind)
         for kind in "FWDE"}
    s = SCALES

    def build(key):
        k = iter(jax.random.split(key, 24))

        def flat(shape, scale):
            return jax.random.normal(next(k), shape, dt) * jnp.asarray(scale, dt)

        def stacked(shape, scale, lead=1, dtype=dt, mask=None):
            """``lead`` stacked axes (layer, then expert), one matrix drawn
            at a time: small temporaries.  ``mask`` multiplies each."""
            count = math.prod(shape[:lead])
            scale = jnp.asarray(scale, dtype) * (
                1 if mask is None else mask.astype(dtype))
            out = jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[lead:], dtype) * scale,
                jax.random.split(next(k), count))
            return out.reshape(shape)

        # The router's channels: read by the routers alone, written by no
        # layer (``SCALES``' comment).
        routed_by = jnp.arange(d) < max(1, d // s["router_share"])
        mixed = ~routed_by

        def attention(kind):
            layers, hkv = n[kind], cfg.kv_heads(kind)
            return {
                "rms": jnp.ones((layers, d), dt),
                "wq": stacked((layers, d, H, D), s["in"]),
                "wk": stacked((layers, d, hkv, D), s["in"]),
                "wv": stacked((layers, d, hkv, Dv), s["in"]),
                "wo": stacked((layers, H, Dv, d), s["attn_out"], mask=mixed),
            }

        return {
            "wte": flat((cfg.vocab_size, d), s["embed"]),
            "blocks": {
                "full": attention("F"),
                "window": dict(attention("W"), sink=s["sink_mean"]
                               + s["sink"] * jax.random.normal(
                                   next(k), (n["W"], H), jnp.float32)),
                "dense": {
                    "rms": jnp.ones((n["D"], d), dt),
                    "w_gate": stacked((n["D"], d, cfg.d_ff), s["in"]),
                    "w_up": stacked((n["D"], d, cfg.d_ff), s["in"]),
                    "w_down": stacked((n["D"], cfg.d_ff, d), s["dense_out"],
                                      mask=mixed),
                },
                "moe": {
                    "rms": jnp.ones((n["E"], d), dt),
                    "router": stacked((n["E"], d, cfg.n_routed_experts),
                                      s["router"], 1, jnp.float32,
                                      mask=routed_by[:, None]),
                    "router_bias": jnp.zeros((n["E"], cfg.n_routed_experts),
                                             jnp.float32),
                },
            },
            "experts": {
                "w_gate": stacked((n["E"], Eh, d, cfg.d_expert), s["in"], 2),
                "w_up": stacked((n["E"], Eh, d, cfg.d_expert), s["in"], 2),
                "w_down": stacked((n["E"], Eh, cfg.d_expert, d),
                                  s["expert_out"], 2, mask=mixed),
            },
            "rms_f": jnp.ones((d,), dt),
            "lm_head": flat((cfg.vocab_size, d), s["in"]),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: MimoV2Config) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: MimoV2Config):
    return mimo_v2_ref_logits(params, tokens, sizes_of(cfg), cfg.attn_kinds,
                              cfg.mlp_kinds, cfg.expert_offset)


decode_flops_per_token = flops_mimo_v2.decode_flops_per_token
decode_step_bytes = flops_mimo_v2.decode_step_bytes
held_expert_slots = flops_mimo_v2.held_expert_slots
