#!/usr/bin/env python3
"""The Granite-4.0-H cell's comparison over ALL its forty layers, through the
engine's own compiled programs, on the chip (the builder's check beside the
harness's two-layer one, ``lib/bench_server.py`` ``check_reference``), and that
two-layer check itself with its control:

  python3 benchmarks/granite_h_all_layers.py [--config <name>] [--seed n]
      [--harness-cut N] [--rehearse-cpu]

*All layers.*  The harness's check runs 64 + 3 positions of the first two
layers, ``MM``: a quarter of one chunk of the scan, no attention layer at
all, three decode steps; nothing in it carries a state across a chunk, a
rung's padding or a hundred decode steps, and rounding that forty layers add
up stays out of its sight.  Here every slot of ``JaxLLMEngine`` at the
configuration's widths and slots gets a prompt through ``jit_prefill_one``:
half of them 280-320 random ids at the 512 rung (two chunks of 256, the
second mostly padding, which holds random ids too), half 1,400-1,500 at the
2048 rung (six chunks, three key blocks of 512 in prefill and in decode);
then the engine's decode program runs ``STEPS`` steps on the full batch, fed
a fixed token sequence (not what it samples), so that the plain float32
reference can run the same tokens in one full forward.  The reference runs
``ROWS`` of the slots BEFORE the engine is built (the weights alone beside
its float32 copies of a layer), layer by layer (``reference/granite_h_ref.py``
``ref_layer``: the token-by-token recurrence, dense scores, ``highest``
precision).  Compared: the logits that predict positions ``length .. length +
STEPS`` of each of those rows, at each position the RMS of the difference
over the vocabulary as a share of the reference logits' standard deviation
(the harness's statistic).  Two limits, each with its reason:

* ``bench_server.LOGIT_TOL`` (3 %), the harness's, which 99 of the program's
  100 positions must keep (its 99th percentile; the median is a fifth of
  it): what separates the program from the reference is rounding alone,
  bfloat16 where a product reads its input, forty layers deep.  NOT its
  worst position, as the siblings' scripts have it: on the chip two to four
  positions of 776 stand at 3.3-5.6 %, single positions where the error
  jumps inside a few layers and is gone four steps on (the convolution's
  taps).  They are no fault of the cache or of the decode step: the bfloat16
  FULL forward of one row, no cache at all, has as many at other positions
  (my chip runs, PR 60: PERF.md section 6), and the CPU has none at the same
  widths.  The worst position is reported and held to ``WORST_TOL``, twice
  the limit, which a state stepped twice (14-34 %) or coarse matrices (20 %
  at the MEDIAN) miss by far.
* ``STATE_TOL``, this script's, on the MEDIAN position of the last ``TAIL``
  decode steps: the program must keep it and the same programs with the
  Mamba-2 state rounded to bfloat16 wherever the cache holds it (after a
  prefill and after every decode step: ``reduce_precision`` in place, the
  leaf's type and the programs as they are; the published cache keeps the
  model's dtype, this configuration float32) must NOT: a state kept in a
  lower precision than the configuration states is seen.  The rounding
  accumulates (``exp(dt A)`` is 0.9-0.9999: a step's error is still there a
  hundred steps on), so the late steps carry it; PERF.md has both readings.

A third run rounds every matrix of ``blocks`` in place to three bits of
mantissa (float8_e4m3's precision at bfloat16's range): its MEDIAN position
must come out over ``LOGIT_TOL``.

*``--harness-cut N``* instead runs what ``check_reference`` runs, with its own
functions (``through_the_cache``, ``logit_errors``: the first two layers,
``MM``, of the seed's weights with the first two MLPs, a prompt of 64 and
three decode steps at one row, the worst of the four positions against
``LOGIT_TOL``), for ``N`` seeds, each with the program's weights and with the
coarse matrices: every program reading must pass and every coarse reading
must fail.

Prints one JSON line; exit code 1 when a comparison or a control fails.
``--rehearse-cpu`` walks the same code at the configuration's tiny widths
(where the scales, which are reckoned for the published widths, leave the
limits without meaning): its line says ``rehearsal_ok`` and its exit code is
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
STEPS, TAIL, ROWS = 96, 32, 8
# The median of the last TAIL steps' logit errors: the program (float32
# state) read 0.0056-0.0069 over three seeds on the chip, the same programs
# with the state rounded to bfloat16 wherever the cache holds it
# 0.0249-0.0279 (PERF.md, PR 60): the limit lies between, a factor of 1.9
# from either.
STATE_TOL = 0.013
# The program's worst position of 776: 0.033-0.056 over those seeds (above).
WORST_TOL = 0.06


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="granite4h_micro")
    ap.add_argument("--seed", type=int, default=6000000101)
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
    from benchmarks.reference import granite_h_ref as ref
    from ray_tpu.llm import EngineConfig, JaxLLMEngine
    from ray_tpu.models import model_family

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        cell = json.load(f)
    fam = importlib.import_module("benchmarks.families." + cell["family"])
    tiny = args.rehearse_cpu
    model = cell["tiny"] if tiny else cell["model"]
    eng = cell["tiny_engine"] if tiny else cell["engine"]
    dev = jax.devices()[0]
    if not tiny and dev.platform != "tpu":
        print(f"needs a TPU; jax came up on {dev.platform}", file=sys.stderr)
        return 2
    cfg = fam.config(model)
    verdict = "rehearsal_ok" if tiny else "ok"
    line = {"config": args.config, "tolerance": LOGIT_TOL,
            "device": {"platform": dev.platform, "kind": dev.device_kind}}
    # Coarse matrices, rounded in place leaf by leaf (a second copy of 5 GB
    # does not fit).
    coarse = jax.jit(lambda w: jax.lax.reduce_precision(
        w, exponent_bits=8, mantissa_bits=3), donate_argnums=0)

    def coarse_matrices(params):
        return dict(params, blocks=jax.tree.map(
            lambda w: coarse(w) if w.ndim >= 3 and w.dtype == jnp.dtype(
                cfg.dtype) else w, params["blocks"]))

    if args.harness_cut:
        cut = dataclasses.replace(cfg, n_layer=2)
        reference = jax.jit(lambda p, t: fam.reference_logits(p, t, cut))
        program, control = [], []
        for seed in range(args.seed, args.seed + args.harness_cut):
            params = fam.load_params(model, seed)
            params = dict(params, blocks=jax.tree.map(
                lambda a: a[:2], params["blocks"]))  # as the harness cuts
            toks = np.random.default_rng(seed).integers(
                0, cut.vocab_size, (1, 64 + 3), dtype=np.int32)
            ref_all = np.asarray(reference(params, jnp.asarray(toks)))[0]
            want = [ref_all[63 + i] for i in range(4)]

            def worst(params):
                return max(logit_errors(through_the_cache(
                    model_family(cut), params, cut, toks, 64, 3),
                    want)["rel_errs"])

            program.append(worst(params))
            control.append(worst(coarse_matrices(params)))  # in place: last
        ok = max(program) <= LOGIT_TOL < min(control)
        print(json.dumps(dict(line, **{
            verdict: bool(ok or tiny), "layers": cut.kinds,
            "seeds": args.harness_cut, "program": program,
            "control_coarse_matrices": control})))
        return 0 if ok or tiny else 1

    slots = eng["max_batch_size"]
    steps, tail = (STEPS, TAIL) if not tiny else (12, 4)
    top = eng["max_seq_len"]
    rng = np.random.default_rng(args.seed)
    if tiny:  # one rung of 128: prompts of 20-30 and of 80-100
        short, long = (20, 30), (80, 100)
    else:
        short, long = (280, 320), (1400, 1500)
    lengths = np.where(np.arange(slots) % 2 == 0,
                       rng.integers(*short, slots), rng.integers(*long, slots))
    toks = rng.integers(0, cfg.vocab_size, (slots, long[1] + steps + 1),
                        dtype=np.int32)
    assert long[1] + steps + 1 < top
    picked = [int(b) for b in np.linspace(0, slots - 1, min(ROWS, slots))]

    # The reference first: its float32 layers beside the weights alone.
    params = fam.load_params(model, args.seed)
    sizes = fam.sizes_of(cfg)
    layer = {kind: jax.jit(functools.partial(
        ref.ref_layer, kind=kind, sizes=sizes)) for kind in set(cfg.kinds)}
    head = jax.jit(functools.partial(ref.ref_head, sizes=sizes))
    embed = jax.jit(functools.partial(ref.ref_embed, sizes=sizes))
    want = {}
    t0 = time.perf_counter()
    for b in picked:
        n = int(lengths[b])
        x = embed(params, jnp.asarray(toks[b:b + 1, :n + steps]))
        for kind, w, w_mlp in ref.layer_weights(params, cfg.kinds):
            x = layer[kind](x, w=w, w_mlp=w_mlp)
        want[b] = np.asarray(head(x[:, n - 1:], params))[0]
    reference_s = time.perf_counter() - t0
    del layer, head, embed, x

    engine = JaxLLMEngine(EngineConfig(
        model=cfg, max_batch_size=slots, max_seq_len=top,
        seed=args.seed % 2 ** 31, param_loader=lambda: params))
    del params
    round_state = jax.jit(lambda s: jax.lax.reduce_precision(
        s, exponent_bits=8, mantissa_bits=7), donate_argnums=0)

    def through_the_engine(state_in_bfloat16=False):
        """{row: [steps + 1, V]} logits of the engine's own programs."""
        def keep(cache):
            if state_in_bfloat16:
                cache = dict(cache, ssm=round_state(cache["ssm"]))
            return cache

        out = {b: np.zeros((steps + 1, cfg.vocab_size), np.float32)
               for b in picked}
        for b in range(slots):
            rung = next(r for r in engine._prefill_rungs if r >= lengths[b])
            padded = rng.integers(0, cfg.vocab_size, rung, dtype=np.int32)
            padded[:lengths[b]] = toks[b, :lengths[b]]  # the rest: anything
            logits, cache, _ = engine._prefill_one[rung](
                engine.params, engine.cache, jnp.asarray(padded),
                np.int32(lengths[b]), np.int32(b))
            engine.cache = keep(cache)
            if b in out:
                out[b][0] = np.asarray(logits[0], np.float32)
        rows = np.arange(slots)
        for i in range(steps):
            pos = (lengths + i).astype(np.int32)
            logits, cache, _ = engine._decode(
                engine.params, engine.cache, jnp.asarray(toks[rows, pos]),
                jnp.asarray(pos))
            engine.cache = keep(cache)
            logits = np.asarray(logits, np.float32)
            for b in out:
                out[b][i + 1] = logits[b]
        return out

    def errors(got):
        """Over the compared positions; the last ``tail`` steps apart."""
        errs = {b: logit_errors(list(got[b]), list(want[b]))["rel_errs"]
                for b in picked}
        every = [r for e in errs.values() for r in e]
        late = [r for e in errs.values() for r in e[-tail:]]
        by_rung = {"short_rows": [errs[b] for b in picked if b % 2 == 0],
                   "long_rows": [errs[b] for b in picked if b % 2]}
        worst = max((r, b, i) for b, e in errs.items()
                    for i, r in enumerate(e))
        return {"median_rms": float(np.median(every)),
                "worst_rms": max(every),
                # where: (row, step), and how far out it stands
                "worst_at": [worst[1], worst[2]],
                "positions_over_tolerance": sum(
                    r > LOGIT_TOL for r in every),
                "worst_rows_errs": [round(r, 4) for r in errs[worst[1]]],
                "p99_rms": float(np.percentile(every, 99)),
                "prefill_median_rms": float(np.median(
                    [e[0] for e in errs.values()])),
                "tail_median_rms": float(np.median(late)),
                **{name + "_tail_median_rms": float(np.median(
                    [r for e in rows for r in e[-tail:]]))
                   for name, rows in by_rung.items() if rows}}

    good = errors(through_the_engine())
    b, i = good["worst_at"]  # the token fed there and the one before it
    good["worst_tokens"] = [int(t) for t in toks[
        b, lengths[b] + i - 2:lengths[b] + i + 1]]
    rounded = errors(through_the_engine(state_in_bfloat16=True))
    engine.params = coarse_matrices(engine.params)
    control = errors(through_the_engine())
    ok = (good["p99_rms"] <= LOGIT_TOL < control["median_rms"]
          and good["worst_rms"] <= WORST_TOL
          and good["tail_median_rms"] <= STATE_TOL
          < rounded["tail_median_rms"])
    print(json.dumps(dict(line, **{
        verdict: bool(ok or tiny), "state_tolerance": STATE_TOL,
        "worst_tolerance": WORST_TOL,
        "layers": cfg.kinds, "slots": slots, "steps": steps, "tail": tail,
        "rungs": sorted({int(next(r for r in engine._prefill_rungs
                                  if r >= n)) for n in lengths}),
        "rows_compared": picked,
        "lengths": [int(lengths[b]) for b in picked],
        "positions": len(picked) * (steps + 1),
        "reference_s": round(reference_s, 1), "program": good,
        "control_state_in_bfloat16": rounded,
        "control_coarse_matrices": control})))
    return 0 if ok or tiny else 1


if __name__ == "__main__":
    sys.exit(main())
