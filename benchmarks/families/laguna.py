"""Family ``laguna``: Laguna-style decoders through ``LagunaConfig`` (window
layers with 72 query heads whose cache is a ring beside full layers with 48,
both over 8 key-value heads; a gate a head; rotary by kind; a shared expert
beside a held share of the routed experts), found by the ``family`` key of
a file under ``configs/``.

``serve_stream`` reads: ``config``, ``load_params`` (the engine's
``param_loader``) and ``reference_logits``; prefill and decode through the
cache are the program's own (``engine.family``).  The readers read
``decode_flops_per_token``, ``decode_step_bytes``, ``prefill_flops`` and
``held_expert_slots`` (``lib/flops_laguna.py``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmarks.lib import flops_laguna
from benchmarks.reference.laguna_ref import laguna_ref_logits
from ray_tpu.models import LagunaConfig

# Standard deviations the weights are drawn at, and one piece of structure
# (``families/nemotron_h.py`` has the long form of the argument).  Four
# things are wanted at once.  (1) The harness's check (``bench_server.
# check_reference``: layer 0, full attention + dense MLP, and layer 1, window
# attention + experts, at 67 positions against 3 % of the logits' spread)
# must SEE the layers: the embedding has RMS 1 and every layer adds about as
# much again.  (2) No routing choice may flip: sigmoid scores renormalised
# over the 10 chosen weigh them about alike, the 10th and the 11th of 256
# are near-tied at every token, and on a chip that holds a sixteenth of the
# experts a flipped choice adds or drops a WHOLE expert at a tenth of the
# routed mass.  So, as there, the first ``d / router_share`` = 192 channels
# of the stream are the routers': every router's rows are zero elsewhere,
# and every output matrix (``Wo``, the dense, the shared and the routed
# experts' ``W_down``) has zero columns there.  Those channels carry the
# token's embedding, exactly, through every layer; what the program rounds
# upstream reaches a router only through the norm's one common factor,
# which moves all 256 logits alike and so no choice.  The margin that
# leaves: the 10th and 11th of 256 logits of spread ``s`` lie ``s / 22``
# apart on average (0.03-0.07 here), and the logits stay under ~4.5 so that
# float32's sigmoid still tells them apart (its step near 0.99 is 6e-8, one
# of 4e-6 in the logit; at a spread of 4 the largest of 256 would sit at
# 12, where a step of the sigmoid is one of 0.01 in the logit and the
# choice is the tie-break's).  (3) Attention must be SHARP, as a trained
# model's is, for two reasons the chip showed (my chip runs, PR 52).  ``q``
# and ``k`` are normed over the head, so with both weights 1 a window
# layer's scores have spread 1.0 and a full layer's 1.7 (its rotated half
# carries ``m^2`` = 2.2), and a softmax over hundreds of such keys averages
# the values' random part away (by the effective number of keys, 210 of a
# window's 512 and 660 of 9,000) while it keeps whatever the values have in
# COMMON: 57-85 % of a window layer's output was then one vector that does
# not depend on the position (mine, CPU, published widths).  First, the
# streams ran together: with attention carrying the stream (``Wo`` at 0.22 /
# 0.44) the logits hardly depended on the token, every greedy stream emitted
# the same ids and chose the same experts (call 1: 27 % of the held experts
# touched a step where independent rows touch 72, 0.89 tokens an expert
# where 1.25 are routed); with the token's own path carrying it (0.10 /
# 0.06) that common vector still tilted every argmax toward the same few
# ids (call 2: 10 %, 1.74).  Second, nothing at the window's edge showed:
# one key of 512 moved the logits by 0.2 %, under the rounding's 0.4.  So
# ``q_norm`` is 1.5 in a full layer and 2.2 in a window layer: scores of
# spread 2.6 and 2.2, of 9,000 keys the largest holds 7.8 % of a row's mass
# and 100 matter (call 3), attention's output is the keys it picked (2-5 %
# of it common) and differs by stream and by position.  (4) But a bfloat16
# cache rounds sharp scores harder (a score of 10 by 0.04): with ``Wo`` at
# 0.045 / 0.04 the program's worst position of the all-layers comparison
# read 3.26 % against the limit of 3 (median 2.06; call 3), so attention
# adds less than the token's own path does (``Wo`` at 0.0225 / 0.02), and
# the gate's pre-activation has spread 2 (gates of 0.12-0.88), so a program
# without its gate is wrong by half of every head.  (5) Every reply must run
# to its ``max_tokens``, as the mix says: the engine stops a stream at the
# tokenizer's ``EOS`` (id 257 of the 12,544 drawn), which a greedy stream of
# random weights happens on once in ~12,500 tokens, and a prompt whose stream
# does stops there at EVERY visit of the round: of one seed's 32 prompts two
# ended after 52 and 65 tokens of 266 and 157, eight short replies in a
# window, and ``serve_tokens_per_s`` read 686.4 where a seed with none read
# 744.7, each to 0.2 % when run again (calls 8 and 9): six seeds then spread
# 4.3 %, by how many of their prompts stop and by (6).  So the head's row
# for that id is zero: a logit of 0 among 12,543 of spread 1.1 is never the
# largest.  (6) The held experts must see the load their deployment gives
# them, in a prefill too.  A prompt is the generator's 66 characters and a
# router reads the token alone, so a layer's held share of a prompt's
# choices is 67 tokens' draw, not thousands': over twelve seeds 0.54-0.67
# held choices a token a layer where 10 x 16 / 256 = 0.625 are routed, and
# the prefill's loop over the held experts' chunks made a seed's
# ``serve_tokens_per_s`` 701-744 by it (PERF.md section 6, PR 52: calls
# 10-11).  A trained router is balanced (its
# correction bias exists for that); a random one is balanced over many
# tokens, not over 67.  So the routers are the one of ``ROUTER_DRAWS`` draws
# from the seed whose held share over the ids a prompt can hold is nearest
# the routed one, all expert layers together.
#   attention: ``Wq/Wk/Wv`` 0.02 on a normed input of 3072: values of spread
#     1.1, times a gate of RMS 0.59; ``Wo [9216, 3072]`` at 0.02 gives a
#     window layer 0.33 at a full window, ``Wo [6144, 3072]`` at 0.0225 a
#     full layer 0.38 at 200-700 positions (both about twice that at the
#     harness's 67 positions, where one or two keys hold a row);
#   dense MLP: gate and up of spread 1.1, ``silu(g) u`` RMS 0.75, ``W_down
#     [12288, 3072]`` at 0.012: 0.96;
#   shared expert: the same hidden RMS, ``W_down [1024, 3072]`` at 0.029:
#     0.7, every token;
#   routed experts: ``W_down`` at 0.117: one expert 2.8, weighed 2.5 / 10:
#     0.7 for each of a token's choices that is held here (0.6 a token in
#     expectation: half the tokens choose none of the sixteen); shared +
#     routed read 0.86 a layer;
#   router: 0.15 on its 192 channels, whose normed values are the
#     embedding's over the stream's RMS (~0.3-0.7): logits of spread 0.15 x
#     13.9 x (0.3 to 0.7) = 0.6 to 1.5 before the sigmoid.
SCALES = {"embed": 1.0, "in": 0.02, "gate": 0.036, "q_norm_full": 1.5,
          "q_norm_window": 2.2, "attn_out_full": 0.0225,
          "attn_out_window": 0.02, "dense_out": 0.012, "shared_out": 0.029,
          "expert_out": 0.117, "router": 0.15, "router_share": 16}
ROUTER_DRAWS = 32


def config(model: dict) -> LagunaConfig:
    return LagunaConfig(**model)


def load_params(model: dict, seed: int):
    """Weights drawn on the device, in the dtype they are served in, by one
    jitted program from the seed, with ``laguna_init``'s shapes and the
    scales above.  The key is an argument: closed over, every seed would
    compile the program anew."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib.traffic import PRINTABLE
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.models.laguna import kind_counts

    cfg = config(model)
    d, dt, D, Fe = cfg.d_model, jnp.dtype(cfg.dtype), cfg.head_dim, cfg.d_expert
    Eh, n, s = cfg.experts_held, kind_counts(cfg), SCALES

    def build(key):
        k = iter(jax.random.split(key, 32))

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
        own = max(1, d // s["router_share"])
        routed_by = jnp.arange(d) < own
        mixed = ~routed_by

        def routers(wte):
            """``[layers, d, E]`` float32, zero outside the routers' channels:
            of ``ROUTER_DRAWS`` draws the one under which the ids of a prompt
            (the generator's characters and BOS) send the share of their
            choices to the experts held here that is nearest the routed one
            (``SCALES``, 6).  The norm's factor and the sigmoid keep a row's
            order, so the choice is the logits' ``top_k``."""
            draws = jax.random.normal(
                next(k), (ROUTER_DRAWS, n["E"], own, cfg.n_routed_experts),
                jnp.float32) * s["router"]
            ids = jnp.asarray(sorted({ByteTokenizer.BOS, *PRINTABLE.encode()}))
            _, chosen = jax.lax.top_k(jnp.einsum(
                "vc,rlce->rlve", wte[ids, :own].astype(jnp.float32), draws),
                cfg.top_k)
            held = ((chosen >= cfg.expert_offset)
                    & (chosen < cfg.expert_offset + Eh)).mean((1, 2, 3))
            best = jnp.argmin(jnp.abs(held - Eh / cfg.n_routed_experts))
            return jnp.zeros((n["E"], d, cfg.n_routed_experts),
                             jnp.float32).at[:, :own].set(draws[best])

        def attention(kind, q_norm, out_scale):
            layers, h, hkv = n[kind], cfg.heads(kind), cfg.n_kv_head
            return {
                "rms": jnp.ones((layers, d), dt),
                "wq": stacked((layers, d, h, D), s["in"]),
                "wk": stacked((layers, d, hkv, D), s["in"]),
                "wv": stacked((layers, d, hkv, D), s["in"]),
                "q_norm": jnp.full((layers, D), q_norm, dt),
                "k_norm": jnp.ones((layers, D), dt),
                "wg": stacked((layers, d, h), s["gate"]),
                "wo": stacked((layers, h, D, d), out_scale, mask=mixed),
            }

        wte = flat((cfg.vocab_size, d), s["embed"])
        return {
            "wte": wte,
            "blocks": {
                "full": attention("F", s["q_norm_full"], s["attn_out_full"]),
                "window": attention("W", s["q_norm_window"],
                                    s["attn_out_window"]),
                "dense": {
                    "rms": jnp.ones((n["D"], d), dt),
                    "w_gate": stacked((n["D"], d, cfg.d_ff), s["in"]),
                    "w_up": stacked((n["D"], d, cfg.d_ff), s["in"]),
                    "w_down": stacked((n["D"], cfg.d_ff, d), s["dense_out"],
                                      mask=mixed),
                },
                "moe": {
                    "rms": jnp.ones((n["E"], d), dt),
                    "router": routers(wte),
                    "router_bias": jnp.zeros((n["E"], cfg.n_routed_experts),
                                             jnp.float32),
                    "w_gate": stacked((n["E"], d, Fe), s["in"]),
                    "w_up": stacked((n["E"], d, Fe), s["in"]),
                    "w_down": stacked((n["E"], Fe, d), s["shared_out"],
                                      mask=mixed),
                },
            },
            "experts": {
                "w_gate": stacked((n["E"], Eh, d, Fe), s["in"], 2),
                "w_up": stacked((n["E"], Eh, d, Fe), s["in"], 2),
                "w_down": stacked((n["E"], Eh, Fe, d), s["expert_out"], 2,
                                  mask=mixed),
            },
            "rms_f": jnp.ones((d,), dt),
            # no greedy stream ends before its max_tokens (``SCALES``, 5)
            "lm_head": flat((cfg.vocab_size, d), s["in"]).at[
                ByteTokenizer.EOS].set(0),
        }

    return jax.jit(build)(jax.random.PRNGKey(seed))


def sizes_of(cfg: LagunaConfig) -> dict:
    return dataclasses.asdict(cfg)


def reference_logits(params, tokens, cfg: LagunaConfig):
    return laguna_ref_logits(params, tokens, sizes_of(cfg), cfg.attn_kinds,
                             cfg.mlp_kinds, cfg.expert_offset)


decode_flops_per_token = flops_laguna.decode_flops_per_token
decode_step_bytes = flops_laguna.decode_step_bytes
prefill_flops = flops_laguna.prefill_flops
held_expert_slots = flops_laguna.held_expert_slots
