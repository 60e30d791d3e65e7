"""Family ``llama``: Llama / Mistral-style decoders through ``LlamaConfig``,
found by the ``family`` key of a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).
"""

from __future__ import annotations

from benchmarks.lib import flops
from benchmarks.reference.llama_ref import llama_ref_logits
from ray_tpu.models import LlamaConfig


def config(model: dict) -> LlamaConfig:
    return LlamaConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``llama_init``'s scales.  The key is
    an argument: closed over, every seed would compile the program anew."""
    import jax
    import jax.numpy as jnp

    cfg = config(model)
    e, hd = cfg.d_model, cfg.head_dim
    L, H, KV, F = cfg.n_layer, cfg.n_head, cfg.n_kv_head, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    s, so = 0.02, 0.02 / (2 * L) ** 0.5

    def build(key):
        k = iter(jax.random.split(key, 12))

        def flat(shape, scale):
            return jax.random.normal(next(k), shape, dt) * jnp.asarray(scale, dt)

        def stacked(shape, scale):  # one layer at a time: small temporaries
            return jax.lax.map(
                lambda kk: jax.random.normal(kk, shape, dt)
                * jnp.asarray(scale, dt), jax.random.split(next(k), L))

        return {
            "wte": flat((cfg.vocab_size, e), s),
            "blocks": {
                "rms1": jnp.ones((L, e), dt),
                "wq": stacked((e, H, hd), s),
                "wk": stacked((e, KV, hd), s),
                "wv": stacked((e, KV, hd), s),
                "wo": stacked((H, hd, e), so),
                "rms2": jnp.ones((L, e), dt),
                "w_gate": stacked((e, F), s),
                "w_up": stacked((e, F), s),
                "w_down": stacked((F, e), so),
            },
            "rms_f": jnp.ones((e,), dt),
            "lm_head": flat((cfg.vocab_size, e), s),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def reference_logits(params, tokens, cfg: LlamaConfig):
    return llama_ref_logits(params, tokens, cfg.n_head, cfg.n_kv_head,
                            cfg.rope_theta, cfg.rms_eps)


decode_flops_per_token = flops.llama_decode_flops_per_token
