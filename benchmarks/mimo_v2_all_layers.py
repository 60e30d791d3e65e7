#!/usr/bin/env python3
"""A MiMo-V2 cell's comparison over ALL its layers, through the engine's own
compiled programs, across a wrapped ring, on the chip (the builder's check
beside the harness's two-layer one, ``lib/bench_server.py``
``check_reference``, whose 67 positions never reach the published window's
edge), and that two-layer check itself with its lower-precision control:

  python3 benchmarks/mimo_v2_all_layers.py [--config <name>] [--seed n]
      [--harness-cut N] [--rehearse-cpu]

*All layers.*  One process builds ``JaxLLMEngine`` at the configuration's
widths and slots, with the family's seeded weights.  Every slot gets a prompt
of 600-800 random ids through ``jit_prefill_one`` at the 1024 rung (padded:
the rings are gathered by the prompt's length, five wraps in), then the
engine's decode program runs ``STEPS`` = 160 steps on the full batch (every
ring wraps again, the write replacing the slot that held ``pos - 128``), fed
a fixed token sequence (not what it samples), so that the plain float32
reference can run the same tokens in one full forward.  The reference runs
``ROWS`` of the slots, layer by layer (``reference/mimo_v2_ref.py``
``ref_layer``: one jitted block at a time, dense ``[S, S]`` scores with the
window as a mask, weights upcast matrix by matrix, so it fits beside the
engine's 8.4 GB).  Compared: the logits after prefill, after the first
decode step, a middle one and the last two (``CHECK``), at each position the
RMS of the difference over the vocabulary as a share of the reference
logits' standard deviation: the harness's statistic and the harness's limit
(``bench_server.LOGIT_TOL``, 3 %), which the program's WORST position must
keep.  Every layer adds about the embedding's RMS to the stream and no
routing choice flips (``families/mimo_v2.py``: the routers read channels no
layer writes), so what separates the program from the reference is rounding
alone: bfloat16 where a product reads its input, seven layers deep.  Two
controls, the same programs on the same tokens, whose MEDIAN position must
come out over the limit: the window layers' sinks set to -1e9 (a softmax
without its sink column); and every matrix of ``blocks`` and ``experts``
rounded in place to three bits of mantissa (float8_e4m3's precision at
bfloat16's range; ``reduce_precision``, because the compiler folds a cast to
float8 and back into nothing), the embedding and the head as they are.

*``--harness-cut N``* instead runs what ``check_reference`` runs, with its
own functions (``through_the_cache``, ``logit_errors``: the first two layers
of the seed's weights, a prompt of 64 and three decode steps at one row, the
worst of the four positions against ``LOGIT_TOL``), for ``N`` seeds, each
with the program's weights and with the coarse ones: every program reading
must pass and every control reading must fail.

Prints one JSON line; exit code 1 when the comparison or a control fails.
``--rehearse-cpu`` walks the same code at the configuration's tiny widths
(where the scales, which are reckoned for the published widths, leave the
limit without meaning): its line says ``rehearsal_ok`` and its exit code is
0.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS, ROWS, RUNG = 160, 8, 1024
CHECK = (0, 1, 80, 159, 160)  # decode steps run before the logits compared


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mimo_v25_l7_ep16")
    ap.add_argument("--seed", type=int, default=4500000101)
    ap.add_argument("--harness-cut", type=int, default=0, metavar="N")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu  # noqa: F401 - the compile cache's place
    from benchmarks.lib.bench_server import (LOGIT_TOL, logit_errors,
                                             through_the_cache)
    from benchmarks.reference import mimo_v2_ref as ref
    from ray_tpu.llm import EngineConfig, JaxLLMEngine
    from ray_tpu.llm.engine import lay_out

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
    # Coarse matrices, rounded in place leaf by leaf (a second copy of 7 GB
    # does not fit).
    coarse = jax.jit(lambda w: jax.lax.reduce_precision(
        w, exponent_bits=8, mantissa_bits=3), donate_argnums=0)

    def coarse_matrices(params):
        return dict(params, **{name: jax.tree.map(
            lambda w: coarse(w) if w.ndim >= 3 and w.dtype == jnp.dtype(
                cfg.dtype) else w, params[name])
            for name in ("blocks", "experts")})

    dev = jax.devices()[0]
    line = {"config": args.config, "tolerance": LOGIT_TOL,
            "device": {"platform": dev.platform, "kind": dev.device_kind}}
    if args.harness_cut:
        from ray_tpu.models import model_family

        cut = dataclasses.replace(cfg, n_layer=2)
        reference = jax.jit(lambda p, t: fam.reference_logits(p, t, cut))
        program, control = [], []
        for seed in range(args.seed, args.seed + args.harness_cut):
            params = fam.load_params(model, seed)
            # as ``check_reference`` cuts them; the one expert layer's
            # experts alone, so that the control's copy fits
            params = dict(params, **{name: jax.tree.map(
                lambda a: a[:2], params[name])
                for name in ("blocks", "experts")})
            toks = np.random.default_rng(seed).integers(
                0, cut.vocab_size, (1, 64 + 3), dtype=np.int32)
            ref_all = np.asarray(reference(params, jnp.asarray(toks)))[0]
            want = [ref_all[63 + i] for i in range(4)]
            for out in (program, control):  # the rounding is in place
                out.append(max(logit_errors(through_the_cache(
                    model_family(cut), params, cut, toks, 64, 3),
                    want)["rel_errs"]))
                params = coarse_matrices(params)
        ok = max(program) <= LOGIT_TOL < min(control)
        print(json.dumps(dict(line, **{
            ("rehearsal_ok" if tiny else "ok"): bool(ok or tiny),
            "layers": [cut.attn_kinds, cut.mlp_kinds],
            "seeds": args.harness_cut, "program": program,
            "control_coarse_matrices": control})))
        return 0 if ok or tiny else 1
    engine = JaxLLMEngine(EngineConfig(
        model=cfg, max_batch_size=eng["max_batch_size"],
        max_seq_len=eng["max_seq_len"], seed=args.seed % 2 ** 31,
        param_loader=lambda: fam.load_params(model, args.seed)))
    slots = eng["max_batch_size"]
    rung = min(RUNG, engine._prefill_rungs[-1])
    steps = STEPS if not tiny else 5 * cfg.window
    check = CHECK if not tiny else (0, 1, steps // 2, steps - 1, steps)
    lo, hi = (600, 800) if not tiny else (rung // 4, rung // 2)
    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(lo, hi + 1, slots)
    toks = rng.integers(0, cfg.vocab_size, (slots, hi + steps + 1),
                        dtype=np.int32)
    step_ms = []

    def through_the_engine():
        """[slots, len(check), V] logits of the engine's own programs."""
        out = np.zeros((slots, len(check), cfg.vocab_size), np.float32)
        for b in range(slots):
            padded = np.zeros(rung, np.int32)
            padded[:lengths[b]] = toks[b, :lengths[b]]
            logits, engine.cache, _ = engine._prefill_one[rung](
                engine.params, engine.cache, jnp.asarray(padded),
                np.int32(lengths[b]), np.int32(b))
            out[b, 0] = np.asarray(logits[0], np.float32)
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
    sizes = fam.sizes_of(cfg)
    layer = functools.cache(lambda attn_kind, mlp_kind: jax.jit(
        functools.partial(ref.ref_layer, attn_kind=attn_kind,
                          mlp_kind=mlp_kind, sizes=sizes,
                          expert_offset=cfg.expert_offset)))
    head = jax.jit(functools.partial(ref.ref_head, sizes=sizes))
    picked = [int(b) for b in np.linspace(0, slots - 1, min(ROWS, slots))]
    want = {}
    for b in picked:
        n = int(lengths[b])
        x = jnp.asarray(engine.params["wte"][toks[b:b + 1, :n + steps]],
                        jnp.float32)
        for attn_kind, mlp_kind, *weights in ref.layer_weights(
                engine.params, cfg.attn_kinds, cfg.mlp_kinds):
            x = layer(attn_kind, mlp_kind)(x, *weights)
        logits = np.asarray(head(x, engine.params))[0]
        want[b] = [logits[n - 1 + i] for i in check]

    def errors(got):
        """Over the compared positions, prefill's (first of a row) apart."""
        errs = [logit_errors(list(got[b]), want[b]) for b in picked]
        every = [r for e in errs for r in e["rel_errs"]]
        return {"median_rms": float(np.median(every)),
                "worst_rms": max(every),
                "by_step": {str(step): max(e["rel_errs"][j] for e in errs)
                            for j, step in enumerate(check)},
                "worst_logit": max(max(e["worst_logit"]) for e in errs)}

    good = errors(got)
    window = engine.params["blocks"]["window"]
    sinks = window["sink"]
    window["sink"] = jax.device_put(jnp.full_like(sinks, -1e9), sinks.format)
    no_sink = errors(through_the_engine())
    window["sink"] = sinks
    # (rounded leaves come back in the default layout: back into the
    # engine's, or its programs refuse them)
    engine.params, _ = lay_out(coarse_matrices(engine.params),
                               engine._decode.input_formats[0][0])
    coarse_run = errors(through_the_engine())
    ok = good["worst_rms"] <= LOGIT_TOL < min(
        no_sink["median_rms"], coarse_run["median_rms"])
    print(json.dumps(dict(line, **{
        ("rehearsal_ok" if tiny else "ok"): bool(ok or tiny),
        "layers": [cfg.attn_kinds, cfg.mlp_kinds], "slots": slots,
        "rung": rung, "steps": steps, "steps_compared": list(check),
        "rows_compared": picked, "lengths": [int(lengths[b]) for b in picked],
        "positions": len(picked) * len(check), "decode_step_wall_ms": step_ms,
        "program": good, "control_no_sink": no_sink,
        "control_coarse_matrices": coarse_run})))
    return 0 if ok or tiny else 1


if __name__ == "__main__":
    sys.exit(main())
