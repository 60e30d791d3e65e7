#!/usr/bin/env python3
"""The MiniCPM-SALA cell's comparison over ALL its twelve layers, through the
engine's own compiled programs, on the chip, at the served slots and from
prompts BEYOND ``dense_len`` (the builder's check beside the harness's
two-layer one, ``lib/bench_server.py`` ``check_reference``, whose 67 positions
lie under ``dense_len`` and never select), and that two-layer check itself
with its control:

  python3 benchmarks/minicpm_sala_all_layers.py [--config <name>] [--seed n]
      [--harness-cut N] [--rehearse-cpu]

*All layers.*  Every slot of ``JaxLLMEngine`` at the configuration's widths
and slots gets a prompt through ``jit_prefill_one``: all but the last 8,500-
9,000 random ids at the 16,384 rung (past ``dense_len`` 8192: every query of
the prefill selects its 64 blocks, a tile at a time; the rung's padding holds
random ids too), the last ~3,000 at the 4096 rung (the dense branch, in the
same batch as rows that select); then the engine's decode program runs
``STEPS`` (768) steps on the full batch, fed a fixed token sequence (not what it
samples), so that the plain float32 reference can run the same tokens in one
full forward.  The reference runs ``ROWS`` of the slots BEFORE the engine is
built (the weights alone beside its float32 copies of a layer), layer by
layer (``reference/minicpm_sala_ref.py`` ``ref_layer``: the token-by-token
recurrence, dense masked scores with the selection written out, ``highest``
precision, 512 queries at a time).  Compared: the logits that predict
positions ``length .. length + STEPS`` of each of those rows, at each
position the RMS of the difference over the vocabulary as a share of the
reference logits' standard deviation (the harness's statistic).  The limits,
each with its reason:

* ``bench_server.LOGIT_TOL`` (3 %), the harness's, on the 99th percentile
  position of the row that reads DENSELY and on the MEDIAN position of the
  rows that SELECT: what separates the program from the reference is
  rounding alone, bfloat16 where a product reads its input, twelve layers
  deep.  In a row that selects, rounding does one thing more: an error of
  1 % in a sparse layer's input moves a block's score by ~1.4 % of the
  scores' spread, whatever their scale, and the 64th and 65th of ~110 ranked
  blocks lie ~2 % of it apart, so most queries swap a block at the rank's
  edge somewhere in the six selections above a logit; with random values a
  swapped block is a 64th of the read, not the nothing it is to a trained
  model.  That is rounding and no other rule, so those rows' 99th percentile
  is held to ``EDGE_TOL`` and the worst position to ``WORST_TOL``; what
  tells it from another rule is the next limit.
* the SELECTION's control, on the reference's side: the same rows against a
  reference that reads block 0 and the 63 most RECENT blocks where the rule
  ranks by score (30 of a query's 64 blocks are then others): the program's
  MEDIAN position of a row past ``dense_len`` must come out over
  ``EDGE_TOL`` against it.
* ``STATE_TOL``, this script's, on the MEDIAN position of the last ``TAIL``
  decode steps of the row that reads densely (where no edge block adds its
  own): the program must keep it and the same programs with the lightning
  state rounded to bfloat16 wherever the cache holds it (after a prefill and
  after every decode step: ``reduce_precision`` in place) must NOT: the slow
  heads (a decay of 0.0014 a position) carry a step's rounding for hundreds
  of steps, which is why ``STEPS`` is 768: the control's reading grows for
  ~600 steps and then stands still (1.59, 1.75, 1.80, 1.81 % after 192, 384,
  576, 768), the program's does not move (0.94-0.97 %).
* every matrix of ``blocks`` rounded in place to three bits of mantissa
  (float8_e4m3's precision at bfloat16's range): the MEDIAN position must
  come out over ``EDGE_TOL``.

*``--harness-cut N``* instead runs what ``check_reference`` runs, with its own
functions (the first two layers, ``S L``, a prompt of 64 and three decode
steps at one row, the worst of the four positions against ``LOGIT_TOL``),
for ``N`` seeds, each with the program's weights and with the coarse
matrices: every program reading must pass and every coarse reading must
fail.

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
STEPS, TAIL, ROWS = 768, 96, 3
# The median of the last TAIL steps' logit errors of the row that reads
# densely (my chip runs, PR 62, three seeds; PERF.md has the readings): the
# program's largest 0.951 %, the smallest of the same programs' with the state
# rounded to bfloat16 wherever the cache holds it 1.802 %; the seeds agree to
# a hundredth of either, and the limit is the two readings' geometric mean.
STATE_TOL = 0.013
# The 99th percentile position of the rows that SELECT (PERF.md, PR 62): a
# block at the rank's edge chosen the other way is a 64th of a query's read.
EDGE_TOL = 0.06
# The program's worst position of 2 x 769 a seed read 6.6, 8.4, 8.5 % on three
# seeds (PERF.md, PR 62): one query's swapped edge blocks, as EDGE_TOL's.
WORST_TOL = 0.10


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="minicpm_sala_l12")
    ap.add_argument("--seed", type=int, default=6200000101)
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
    from benchmarks.reference import minicpm_sala_ref as ref
    from ray_tpu.llm import EngineConfig, JaxLLMEngine
    from ray_tpu.llm.engine import lay_out
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
    if tiny:  # one rung of 256: prompts of 80-200 past the dense_len of 64
        long, short = (80, 200), (20, 40)
    else:
        long, short = (8500, 9000), (2900, 3100)
    lengths = rng.integers(*long, slots)
    lengths[-1] = rng.integers(*short)  # the dense branch, in the same batch
    assert lengths[:-1].min() >= cfg.dense_len > lengths[-1] + steps
    toks = rng.integers(0, cfg.vocab_size, (slots, long[1] + steps + 1),
                        dtype=np.int32)
    assert long[1] + steps + 1 < top
    picked = sorted({0, slots // 2, slots - 1})[-ROWS:]
    selecting = [b for b in picked if lengths[b] >= cfg.dense_len]

    # The reference first: its float32 layers beside the weights alone.
    params = fam.load_params(model, args.seed)
    sizes = fam.sizes_of(cfg)
    layer = jax.jit(functools.partial(ref.ref_layer, sizes=sizes),
                    static_argnames=("kind", "layer", "prompt_len"))
    head = jax.jit(functools.partial(ref.ref_head, sizes=sizes))
    embed = jax.jit(functools.partial(ref.ref_embed, sizes=sizes))

    def reference(rows):
        out = {}
        for b in rows:
            n = int(lengths[b])
            x = embed(params, jnp.asarray(toks[b:b + 1, :n + steps]))
            for j, (kind, w, w_mlp) in enumerate(
                    ref.layer_weights(params, cfg.kinds)):
                x = layer(x, w=w, w_mlp=w_mlp, kind=kind,
                          layer=cfg.first_layer + j, prompt_len=n)
            out[b] = np.asarray(head(x[:, n - 1:], params))[0]
        return out

    t0 = time.perf_counter()
    want = reference(picked)
    reference_s = time.perf_counter() - t0
    # The selection's control: block 0 and the most recent blocks.
    by_rule = ref.selection

    def most_recent(q, k, rows, sizes):
        s, block = k.shape[1], sizes["block_size"]
        blocks = np.arange(-(-s // block))[None]
        last = rows[:, None] // block
        chosen = (blocks <= last) & ((blocks > last - sizes["topk"] + 1)
                                     | (blocks < sizes["init_blocks"]))
        return jnp.asarray(np.repeat(chosen, block, axis=-1)[:, :s])[
            None, None]

    ref.selection = most_recent
    layer = jax.jit(functools.partial(ref.ref_layer, sizes=sizes),
                    static_argnames=("kind", "layer", "prompt_len"))
    want_recent = reference(selecting[:1])
    ref.selection = by_rule
    del layer, head, embed

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
                cache = dict(cache, state=round_state(cache["state"]))
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

    def errors(got, want):
        """Over the compared positions of the rows ``want`` holds; the last
        ``tail`` steps apart; the rows that select and the row that reads
        densely apart."""
        errs = {b: logit_errors(list(got[b]), list(want[b]))["rel_errs"]
                for b in picked if b in want}

        def of(rows):
            every = [r for b in rows for r in errs[b]]
            late = [r for b in rows for r in errs[b][-tail:]]
            worst = max((r, b, i) for b in rows
                        for i, r in enumerate(errs[b]))
            return {"median_rms": float(np.median(every)),
                    "p99_rms": float(np.percentile(every, 99)),
                    "worst_rms": max(every),
                    # where: (row, step)
                    "worst_at": [worst[1], worst[2]],
                    "positions_over_tolerance": sum(
                        r > LOGIT_TOL for r in every),
                    "prefill_rms": [errs[b][0] for b in rows],
                    "tail_median_rms": float(np.median(late)),
                    # the same median had the run ended earlier
                    "tail_median_rms_after": {
                        n: float(np.median([r for b in rows for r in
                                            errs[b][n + 1 - tail:n + 1]]))
                        for n in range(steps // 4, steps + 1, steps // 4)}}

        out = of(list(errs))
        for name, rows in (("selecting", [b for b in errs if b in selecting]),
                           ("dense", [b for b in errs if b not in selecting])):
            if rows:
                out[name] = of(rows)
        return out

    got = through_the_engine()
    good = errors(got, want)
    against_recency = errors(got, want_recent)
    rounded = errors(through_the_engine(state_in_bfloat16=True), want)
    engine.params, _ = lay_out(coarse_matrices(engine.params),
                               engine._decode.input_formats[0][0])
    control = errors(through_the_engine(), want)
    ok = (good["dense"]["p99_rms"] <= LOGIT_TOL
          and good["selecting"]["median_rms"] <= LOGIT_TOL
          and good["selecting"]["p99_rms"] <= EDGE_TOL
          and good["worst_rms"] <= WORST_TOL
          and against_recency["median_rms"] > EDGE_TOL
          and control["median_rms"] > EDGE_TOL
          and good["dense"]["tail_median_rms"] <= STATE_TOL
          < rounded["dense"]["tail_median_rms"])
    print(json.dumps(dict(line, **{
        verdict: bool(ok or tiny), "state_tolerance": STATE_TOL,
        "edge_tolerance": EDGE_TOL, "worst_tolerance": WORST_TOL,
        "layers": cfg.kinds, "slots": slots, "steps": steps, "tail": tail,
        "rungs": sorted({int(next(r for r in engine._prefill_rungs
                                  if r >= n)) for n in lengths}),
        "rows_compared": picked,
        "lengths": [int(lengths[b]) for b in picked],
        "positions": len(picked) * (steps + 1),
        "reference_s": round(reference_s, 1), "program": good,
        "control_selection_by_recency": against_recency,
        "control_state_in_bfloat16": rounded,
        "control_coarse_matrices": control})))
    return 0 if ok or tiny else 1


if __name__ == "__main__":
    sys.exit(main())
