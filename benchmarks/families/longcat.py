"""Family ``longcat``: LongCat-Flash-style decoders through ``LongcatConfig``
(latent attention, shortcut-connected expert layer, zero-compute experts, a
held share of the experts), found by the ``family`` key of a file under
``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
latent cache are the program's own (``engine.family``).
"""

from __future__ import annotations

import dataclasses

from benchmarks.lib import flops_longcat
from benchmarks.reference.longcat_ref import longcat_ref_logits
from ray_tpu.models import LongcatConfig

# Standard deviations the weights are drawn at.  Weights are free, and the
# family's GPT-2-style init (every matrix 0.02, output projections divided
# by sqrt(4 L), ``longcat_init``) is a poor stand-in here: an embedding of
# RMS 0.02 is swamped by the first attention's output, the next RMSNorm
# blows that output's bf16 rounding up to the whole stream, and a router of
# logit spread 1.6 then weighs its twelve choices within bf16's reach of each
# other.  On the chip the benchmark's reference check (two double layers, 30
# seeds, worst of its four positions; PERF.md, PR 29) read 1.6-3.6 % with
# that init and the router already at 0.05 (1 of 30 over the 3 % limit, and
# 1 of 8 whole runs), and 1.33-1.91 % with the values below: an embedding of RMS 1 (the sub-layers
# perturb the stream, as in a trained model, instead of being it), output
# projections at 0.02 (a flipped near-tie among a token's choices is small
# beside the stream) and router logits of spread 0.05 * sqrt(6144) = 3.9.
EMBED_SCALE = 1.0
INIT_SCALE = 0.02
OUT_SCALE = 0.02
ROUTER_SCALE = 0.05


def config(model: dict) -> LongcatConfig:
    return LongcatConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``longcat_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    d, L, H, Eh = cfg.d_model, cfg.n_layer, cfg.n_head, cfg.experts_held
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    s, so = INIT_SCALE, OUT_SCALE

    def build(key):
        k = iter(jax.random.split(key, 16))

        def flat(shape, scale):
            return jax.random.normal(next(k), shape, dt) * jnp.asarray(scale, dt)

        def stacked(shape, scale, lead=2, dtype=dt):
            """``lead`` stacked axes (layer, then half or expert), one
            matrix drawn at a time: small temporaries."""
            n = 1
            for size in shape[:lead]:
                n *= size
            out = jax.lax.map(
                lambda kk: jax.random.normal(kk, shape[lead:], dtype)
                * jnp.asarray(scale, dtype), jax.random.split(next(k), n))
            return out.reshape(shape)

        return {
            "wte": flat((cfg.vocab_size, d), EMBED_SCALE),
            "blocks": {
                "rms_attn": jnp.ones((L, 2, d), dt),
                "wq_a": stacked((L, 2, d, rq), s),
                "rms_q": jnp.ones((L, 2, rq), dt),
                "wq_b": stacked((L, 2, rq, H, dn + dr), s),
                "wkv_a": stacked((L, 2, d, rkv + dr), s),
                "rms_kv": jnp.ones((L, 2, rkv), dt),
                "wkv_b": stacked((L, 2, rkv, H, dn + dv), s),
                "wo": stacked((L, 2, H, dv, d), so),
                "rms_ffn": jnp.ones((L, 2, d), dt),
                "w_gate": stacked((L, 2, d, cfg.d_ff), s),
                "w_up": stacked((L, 2, d, cfg.d_ff), s),
                "w_down": stacked((L, 2, cfg.d_ff, d), so),
                "router": stacked((L, d, cfg.n_router), ROUTER_SCALE, 1,
                                  jnp.float32),
                "router_bias": jnp.zeros((L, cfg.n_router), jnp.float32),
            },
            "experts": {
                "w_gate": stacked((L, Eh, d, cfg.d_expert), s),
                "w_up": stacked((L, Eh, d, cfg.d_expert), s),
                "w_down": stacked((L, Eh, cfg.d_expert, d), so),
            },
            "rms_f": jnp.ones((d,), dt),
            "lm_head": flat((cfg.vocab_size, d), s),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def reference_logits(params, tokens, cfg: LongcatConfig):
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return longcat_ref_logits(params, tokens, sizes, cfg.n_layer,
                              cfg.expert_offset)


decode_flops_per_token = flops_longcat.decode_flops_per_token
