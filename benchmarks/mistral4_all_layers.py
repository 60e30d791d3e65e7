#!/usr/bin/env python3
"""A Mistral-4 cell's comparison over ALL its layers, through the engine's
own compiled programs, at contexts on both sides of the trained 8192
positions, on the chip (the builder's check beside the harness's two-layer
one, ``lib/bench_server.py`` ``check_reference``, whose 67 positions see
neither the query scale, which is 1 below 8192, nor a rotary pair that YaRN
slows, nor a second block of the prefill):

  python3 benchmarks/mistral4_all_layers.py [--config <name>] [--seed n]
      [--rehearse-cpu]

One process builds ``JaxLLMEngine`` at the configuration's widths and slots,
with the family's seeded weights.  Half the slots get a prompt of
``LONG`` = 8,900-9,100 random ids through ``jit_prefill_one`` at the 16,384
rung, the other half one of ``SHORT`` = 2,900-3,100 at the 4096 rung (both
padded, both over several blocks of the expanding prefill), then the engine's
decode program runs ``STEPS`` = 96 steps on the full batch, whose rows sit
beyond and below position 8192, fed a fixed token sequence (not what it
samples), so that the plain float32 reference can run the same tokens in one
full forward.  The reference runs ``ROWS`` of the slots (half long, half
short), layer by layer (``reference/mistral4_ref.py`` ``ref_layer``: one
jitted block at a time, per-head keys and values, dense scores computed for
512 query rows at a time against all keys, weights upcast matrix by matrix,
so it fits beside the engine's 11.5 GB).  Compared: the logits after prefill,
after the first decode step, a middle one and the last two (``CHECK``), at
each position the RMS of the difference over the vocabulary as a share of
the reference logits' standard deviation: the harness's statistic and the
harness's limit (``bench_server.LOGIT_TOL``, 3 %), which the program's WORST
position must keep.  Every layer adds about the embedding's RMS to the
stream and no routing choice flips (``families/mistral4.py``: the routers
read channels no layer writes), so what separates the program from the
reference is rounding alone: bfloat16 where a product reads its input, nine
layers deep.  Three controls whose MEDIAN position must come out over the
limit, each over the rows where the mechanism acts: the reference computed
with plain rotary at base 1e4 (YaRN left out: every row), the reference
with the query scale left out (``a`` = 1: the LONG rows, whose queries lie
beyond 8192), and the engine's programs on the same tokens with every matrix
of ``blocks`` and ``experts`` rounded in place to three bits of mantissa
(float8_e4m3's precision at bfloat16's range; ``reduce_precision``, because
the compiler folds a cast to float8 and back into nothing), the embedding
and the head as they are.  A comparison that passes the program and cannot
fail these proves nothing at this length.

Prints one JSON line (with the share of a row's attention mass that its
largest score holds, layer 0, the last query of a long row: what the
weights' scales were drawn for); exit code 1 when the comparison or a
control fails.  ``--rehearse-cpu`` walks the same code at the configuration's
tiny widths (where the scales, which are reckoned for the published widths,
leave the limit without meaning): its line says ``rehearsal_ok`` and its
exit code is 0.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS, ROWS = 96, 4
LONG, SHORT = (8900, 9100), (2900, 3100)
CHECK = (0, 1, 48, 95, 96)  # decode steps run before the logits compared


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mistral_small4_l9_ep8")
    ap.add_argument("--seed", type=int, default=4800000101)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu  # noqa: F401 - the compile cache's place
    from benchmarks.lib.bench_server import LOGIT_TOL, logit_errors
    from benchmarks.reference import mistral4_ref as ref
    from ray_tpu.llm import EngineConfig, JaxLLMEngine
    from ray_tpu.llm.engine import lay_out, prefill_rung

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        cell = json.load(f)
    fam = importlib.import_module("benchmarks.families." + cell["family"])
    tiny = args.rehearse_cpu
    model = cell["tiny"] if tiny else cell["model"]
    eng = cell["tiny_engine"] if tiny else cell["engine"]
    platform = jax.devices()[0].platform
    if not tiny and platform != "tpu":
        print(f"needs a TPU; jax came up on {platform}", file=sys.stderr)
        return 2
    cfg = fam.config(model)
    engine = JaxLLMEngine(EngineConfig(
        model=cfg, max_batch_size=eng["max_batch_size"],
        max_seq_len=eng["max_seq_len"], seed=args.seed % 2 ** 31,
        param_loader=lambda: fam.load_params(model, args.seed)))
    slots = eng["max_batch_size"]
    steps = STEPS if not tiny else 24
    check = CHECK if not tiny else (0, 1, 12, 23, 24)
    long_, short = (LONG, SHORT) if not tiny else ((70, 90), (20, 30))
    rng = np.random.default_rng(args.seed)
    is_long = np.arange(slots) % 2 == 0
    lengths = np.where(is_long, rng.integers(long_[0], long_[1] + 1, slots),
                       rng.integers(short[0], short[1] + 1, slots))
    toks = rng.integers(0, cfg.vocab_size, (slots, long_[1] + steps + 1),
                        dtype=np.int32)
    step_ms, prefill_s = [], []

    def through_the_engine():
        """[slots, len(check), V] logits of the engine's own programs."""
        out = np.zeros((slots, len(check), cfg.vocab_size), np.float32)
        start = time.perf_counter()
        for b in range(slots):
            rung = prefill_rung(engine._prefill_rungs, int(lengths[b]))
            padded = np.zeros(rung, np.int32)
            padded[:lengths[b]] = toks[b, :lengths[b]]
            logits, engine.cache, _ = engine._prefill_one[rung](
                engine.params, engine.cache, jnp.asarray(padded),
                np.int32(lengths[b]), np.int32(b))
            out[b, 0] = np.asarray(logits[0], np.float32)
        prefill_s.append(time.perf_counter() - start)
        rows = np.arange(slots)
        jax.block_until_ready(engine.cache)
        start = time.perf_counter()
        for i in range(steps):
            pos = (lengths + i).astype(np.int32)
            logits, engine.cache, _ = engine._decode(
                engine.params, engine.cache, jnp.asarray(toks[rows, pos]),
                jnp.asarray(pos))
            if i + 1 in check:
                out[:, check.index(i + 1)] = np.asarray(logits, np.float32)
        jax.block_until_ready(engine.cache)
        step_ms.append(1e3 * (time.perf_counter() - start) / steps)
        return out

    got = through_the_engine()
    picked = [int(b) for b in (*np.flatnonzero(is_long)[:ROWS // 2],
                               *np.flatnonzero(~is_long)[:ROWS // 2])]
    long_rows = [b for b in picked if is_long[b]]

    @functools.cache
    def layer(**switches):
        return jax.jit(functools.partial(
            ref.ref_layer, sizes=dict(fam.sizes_of(cfg), query_block=512,
                                      **switches),
            expert_offset=cfg.expert_offset))

    head = jax.jit(functools.partial(ref.ref_head, sizes=fam.sizes_of(cfg)))

    def reference(rows, **switches):
        want = {}
        for b in rows:
            n = int(lengths[b])
            x = jnp.asarray(engine.params["wte"][toks[b:b + 1, :n + steps]],
                            jnp.float32)
            for i in range(cfg.n_layer):
                x = layer(**switches)(
                    x, jax.tree.map(lambda a: a[i], engine.params["blocks"]),
                    jax.tree.map(lambda a: a[i], engine.params["experts"]))
            logits = np.asarray(head(x, engine.params))[0]
            want[b] = [logits[n - 1 + i] for i in check]
        return want

    def errors(got, want):
        errs = [logit_errors(list(got[b]), want[b]) for b in want]
        every = [r for e in errs for r in e["rel_errs"]]
        return {"median_rms": float(np.median(every)),
                "worst_rms": max(every),
                "by_step": {str(step): max(e["rel_errs"][j] for e in errs)
                            for j, step in enumerate(check)},
                "worst_logit": max(max(e["worst_logit"]) for e in errs)}

    def largest_share():
        """Of the last query of a long row, layer 0: the share of each head's
        attention mass that its largest score holds, mean over heads (plain
        float32, the program's own projection)."""
        from ray_tpu.models import mistral4

        b = long_rows[0]
        n = int(lengths[b])
        w = jax.tree.map(lambda a: a[0], engine.params["blocks"])
        sizes = fam.sizes_of(cfg)
        x = jnp.asarray(engine.params["wte"][toks[b:b + 1, :n]], jnp.float32)
        y = ref._rms(x, w["rms_attn"], sizes["rms_eps"])
        q, latent = mistral4.project(
            y, jax.tree.map(lambda a: a.astype(jnp.float32), w),
            jnp.arange(n), cfg)
        kn = jnp.einsum("bsc,chd->bshd", latent[..., :cfg.kv_lora_rank],
                        w["wk_b"].astype(jnp.float32))
        s = (jnp.einsum("hd,shd->hs", q[0, -1, :, :cfg.qk_nope_head_dim], kn[0])
             + jnp.einsum("hd,sd->hs", q[0, -1, :, cfg.qk_nope_head_dim:],
                          latent[0, :, cfg.kv_lora_rank:]))
        p = jax.nn.softmax(s * q.shape[-1] ** -0.5, axis=-1)
        return {"positions": n, "score_std": float(jnp.std(
            s * q.shape[-1] ** -0.5, axis=-1).mean()),
            "largest_share": float(p.max(-1).mean()),
            "effective_keys": float((1 / (p * p).sum(-1)).mean())}

    want = reference(picked)
    good = errors(got, want)
    no_yarn = errors(got, reference(picked, yarn=False))
    no_query_scale = errors(got, reference(long_rows, query_scale_beta=0.0))
    attention = largest_share()
    # Coarse matrices, rounded in place leaf by leaf (a second copy of 8 GB
    # does not fit); rounded leaves come back in the default layout: back
    # into the engine's, or its programs refuse them.
    coarse = jax.jit(lambda w: jax.lax.reduce_precision(
        w, exponent_bits=8, mantissa_bits=3), donate_argnums=0)
    engine.params, _ = lay_out(dict(engine.params, **{name: jax.tree.map(
        lambda w: coarse(w) if w.ndim >= 3 and w.dtype == jnp.dtype(
            cfg.dtype) else w, engine.params[name])
        for name in ("blocks", "experts")}),
        engine._decode.input_formats[0][0])
    coarse_run = errors(through_the_engine(), want)
    ok = good["worst_rms"] <= LOGIT_TOL < min(
        no_yarn["median_rms"], no_query_scale["median_rms"],
        coarse_run["median_rms"])
    dev = jax.devices()[0]
    print(json.dumps({
        "config": args.config, "tolerance": LOGIT_TOL,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        ("rehearsal_ok" if tiny else "ok"): bool(ok or tiny),
        "layers": cfg.n_layer, "slots": slots, "steps": steps,
        "steps_compared": list(check), "rows_compared": picked,
        "lengths": [int(lengths[b]) for b in picked],
        "positions": len(picked) * len(check),
        "prefill_all_slots_s": prefill_s, "decode_step_wall_ms": step_ms,
        "attention_layer0": attention, "program": good,
        "control_no_yarn": no_yarn,
        "control_no_query_scale_long_rows": no_query_scale,
        "control_coarse_matrices": coarse_run}))
    return 0 if ok or tiny else 1


if __name__ == "__main__":
    sys.exit(main())
