"""Family ``mistral4``: Mistral-Small-4-style decoders through
``Mistral4Config`` (latent attention with YaRN-scaled rotary and a query
scale that grows with position, a shared expert beside a held share of the
routed experts in every layer), found by the ``family`` key of a file under
``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
latent cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes``, ``prefill_flops`` and
``held_expert_slots`` (``lib/flops_mistral4.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_mistral4
from benchmarks.reference.mistral4_ref import mistral4_ref_logits
from ray_tpu.models import Mistral4Config

# Standard deviations the weights are drawn at, and one piece of structure
# (``families/nemotron_h.py`` has the long form of the argument).  Three
# things are wanted at once.  (1) The harness's check (``bench_server.
# check_reference``: the first two layers at 67 positions against 3 % of the
# logits' spread) must SEE the layers: the embedding has RMS 1 and every
# layer adds about as much again.  (2) No routing choice may flip: a softmax
# renormalised over its 4 chosen weighs them about alike, the 4th and the 5th
# of 128 are near-tied at every token, and on a chip that holds an eighth of
# the experts a flipped choice adds or drops a WHOLE expert at a quarter of
# the routed mass.  So, as there, the first ``d / router_share`` channels of
# the stream are the routers': every router's rows are zero elsewhere, and
# every output matrix (``Wo``, the shared and the routed experts' ``W_down``)
# has zero columns there.  Those channels carry the token's embedding,
# exactly, through every layer; what the program rounds upstream reaches a
# router only through the norm's one common factor, which moves all 128
# logits alike and so no choice.  (3) Attention over 8,000-16,000 positions
# must not be so flat that nothing of the long context shows: a row's
# scores have a spread of about 2 (``Wqb`` 0.028 on a normed latent of 1024:
# query heads of spread 0.9; ``Wkb`` 0.05 on a normed latent of 256: keys 0.8
# beside a rotary key of 1.28; 128 terms, times 128^-0.5 m^2 = 0.195), so of
# 8,000 keys the largest score holds ~8 % of a row's mass (``exp(4.24 s - ln
# n - s^2 / 2)``) and a hundred or so keys matter: moving them (YaRN left
# out) or re-weighing them (the query scale, 7 % on every score beyond 8192)
# moves the output.  The program is the same for any weights: this is a
# property of the draw.
#   attention out: values ``Wvb`` 0.05 on the latent: 0.8 a head; a softmax
#     over a hundred effective keys averages them to ~0.08 (0.4 at the
#     harness's 67 positions), ``Wo [4096, 4096]`` at 0.06 gives 3.8 x that:
#     0.3 a layer at 9,000 positions, 1.5 at 67;
#   shared expert: gate and up of spread 1.28, ``silu(g) u`` RMS 0.95,
#     ``W_down [2048, 4096]`` at 0.016: 0.7, every token;
#   routed experts: the same hidden RMS, ``W_down`` at 0.065: one expert
#     2.8, weighed about 1/4: 0.7 for each of a token's choices that is held
#     here (0.5 a token in expectation: six tokens in ten choose none of the
#     sixteen);
#   router: 0.4 on its 256 channels, whose normed values are the embedding's
#     over the stream's RMS (1.4 after a layer, ~3 after nine): logits of
#     spread 0.4 * 16 * (0.3 to 0.7) = 2 to 4.5 before the softmax.
SCALES = {"embed": 1.0, "in": 0.02, "wq_b": 0.028, "wkv_b": 0.05,
          "attn_out": 0.06, "shared_out": 0.016, "expert_out": 0.065,
          "router": 0.4, "router_share": 16}


def config(model: dict) -> Mistral4Config:
    return Mistral4Config(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``mistral4_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, L, H, Fe = cfg.d_model, cfg.n_layer, cfg.n_head, cfg.d_expert
    rq, rkv, Eh = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.experts_held
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
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
        return {
            "wte": flat((cfg.vocab_size, d), s["embed"]),
            "blocks": {
                "rms_attn": jnp.ones((L, d), dt),
                "wq_a": stacked((L, d, rq), s["in"]),
                "rms_q": jnp.ones((L, rq), dt),
                "wq_b": stacked((L, rq, H, dn + dr), s["wq_b"]),
                "wkv_a": stacked((L, d, rkv + dr), s["in"]),
                "rms_kv": jnp.ones((L, rkv), dt),
                "wk_b": stacked((L, rkv, H, dn), s["wkv_b"]),
                "wv_b": stacked((L, rkv, H, dv), s["wkv_b"]),
                "wo": stacked((L, H, dv, d), s["attn_out"], mask=mixed),
                "rms_ffn": jnp.ones((L, d), dt),
                "router": stacked((L, d, cfg.n_routed_experts), s["router"],
                                  1, jnp.float32, mask=routed_by[:, None]),
                "w_gate": stacked((L, d, Fe), s["in"]),
                "w_up": stacked((L, d, Fe), s["in"]),
                "w_down": stacked((L, Fe, d), s["shared_out"], mask=mixed),
            },
            "experts": {
                "w_gate": stacked((L, Eh, d, Fe), s["in"], 2),
                "w_up": stacked((L, Eh, d, Fe), s["in"], 2),
                "w_down": stacked((L, Eh, Fe, d), s["expert_out"], 2,
                                  mask=mixed),
            },
            "rms_f": jnp.ones((d,), dt),
            "lm_head": flat((cfg.vocab_size, d), s["in"]),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: Mistral4Config) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: Mistral4Config):
    return mistral4_ref_logits(params, tokens, sizes_of(cfg), cfg.n_layer,
                               cfg.expert_offset)


decode_flops_per_token = flops_mistral4.decode_flops_per_token
decode_step_bytes = flops_mistral4.decode_step_bytes
prefill_flops = flops_mistral4.prefill_flops
held_expert_slots = flops_mistral4.held_expert_slots
