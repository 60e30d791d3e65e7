#!/usr/bin/env python3
"""The Olmo-Hybrid cell's comparison over ALL its layers, through the engine's
own compiled programs, on the chip (the builder's check beside the harness's
two-layer one, ``lib/bench_server.py`` ``check_reference``), that two-layer
check itself with its controls, and the linear layer's two programs timed
alone:

  python3 benchmarks/olmo_hybrid_all_layers.py [--config <name>] [--seed n]
      [--harness-cut N | --time-delta] [--rehearse-cpu]

*All layers.*  The harness's check runs 64 + 3 positions of two layers: two
chunks of the scan, no second key block, three decode steps; nothing in it
carries a state across a rung's padding or a hundred decode steps, and
rounding that twelve layers add up stays out of its sight.  Here every slot
of ``JaxLLMEngine`` at the configuration's widths and slots gets a prompt
through ``jit_prefill_one``: half of them 280-320 random ids at the 512 rung
(ten chunks of 32, two fifths of the rung padding, which holds random ids
too), half 1,400-1,500 at the 2048 rung (forty-odd chunks, three key blocks
of 512 in prefill and in decode); then the engine's decode program runs
``STEPS`` steps on the full batch, fed a fixed token sequence (not what it
samples), so that the plain float32 reference can run the same tokens in one
full forward.  The reference runs ``ROWS`` of the slots BEFORE the engine is
built (its float32 copies of a layer do not fit beside 13.9 GB), layer by
layer (``reference/olmo_hybrid_ref.py`` ``ref_layer``: the token-by-token
recurrence, dense scores, ``highest`` precision).  Compared: the logits that
predict positions ``length .. length + STEPS`` of each of those rows, at each
position the RMS of the difference over the vocabulary as a share of the
reference logits' standard deviation (the harness's statistic).  Two limits,
each with its reason:

* ``bench_server.LOGIT_TOL`` (3 %), the harness's, which the program's WORST
  position must keep: what separates the program from the reference is
  rounding alone, bfloat16 where a product reads its input, twelve layers
  deep.
* ``STATE_TOL``, this script's, on the MEDIAN position of the last ``TAIL``
  decode steps: the program must keep it and the same programs with the
  delta rule's state rounded to bfloat16 wherever the cache holds it (after
  a prefill and after every decode step: ``reduce_precision`` in place, the
  leaf's type and the programs as they are) must NOT: a state kept in a
  lower precision than the configuration states is seen.  The rounding
  accumulates (``alpha`` is 0.9-0.999: a step's error is still there a
  hundred steps on), so the late steps carry it; PERF.md has both readings.

A third run rounds every matrix of ``blocks`` in place to three bits of
mantissa (float8_e4m3's precision at bfloat16's range): its MEDIAN position
must come out over ``LOGIT_TOL``.

*``--harness-cut N``* instead runs what ``check_reference`` runs, with its own
functions (``through_the_cache``, ``logit_errors``: the first two layers,
``FL``, of the seed's weights, a prompt of 64 and three decode steps at one
row, the worst of the four positions against ``LOGIT_TOL``), for ``N`` seeds,
each with the program's weights, with the coarse matrices and with the
decode positions off by one: every program reading must pass and every
coarse reading must fail; the shifted reading is reported (without a rotary
term a position only says where the causal mask ends: one position too far
reads one empty row of the cache).

*``--time-delta``* times one linear layer alone at the published widths: the
mixer over a sequence (``olmo_hybrid.delta_sequence``) at 512 and at 2,048
rows for chunks of 32 / 64 / 128, the chunked rule inside it
(``delta_chunked``) on its own, and the one-token update
(``olmo_hybrid_decode.delta_step``) at the configuration's slots against the
bytes it must move: the numbers the next ``perf_opt`` issue starts from.

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
# state) read 0.0211-0.0228 over five seeds on the chip, the same programs
# with the state rounded to bfloat16 wherever the cache holds it
# 0.0315-0.0332 (PERF.md, PR 56): the limit lies between, a sixth above the
# one and a sixth below the other.
STATE_TOL = 0.027


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="olmo_hybrid7b_l12")
    ap.add_argument("--seed", type=int, default=5600000101)
    ap.add_argument("--harness-cut", type=int, default=0, metavar="N")
    ap.add_argument("--time-delta", action="store_true")
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
    from benchmarks.reference import olmo_hybrid_ref as ref
    from ray_tpu.llm import EngineConfig, JaxLLMEngine
    from ray_tpu.models import model_family, olmo_hybrid, olmo_hybrid_decode

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

    if args.time_delta:
        print(json.dumps(dict(line, **time_delta(
            cfg, eng["max_batch_size"], tiny, olmo_hybrid,
            olmo_hybrid_decode), **{verdict: True})))
        return 0

    if args.harness_cut:
        cut = dataclasses.replace(cfg, n_layer=2)
        reference = jax.jit(lambda p, t: fam.reference_logits(p, t, cut))
        program, control, shifted = [], [], []
        for seed in range(args.seed, args.seed + args.harness_cut):
            params = fam.load_params(model, seed)
            params = dict(params, blocks=jax.tree.map(
                lambda a: a[:2], params["blocks"]))  # as the harness cuts
            toks = np.random.default_rng(seed).integers(
                0, cut.vocab_size, (1, 64 + 3), dtype=np.int32)
            ref_all = np.asarray(reference(params, jnp.asarray(toks)))[0]
            want = [ref_all[63 + i] for i in range(4)]

            def worst(params, shift=0):
                return max(logit_errors(through_the_cache(
                    model_family(cut), params, cut, toks, 64, 3,
                    shift=shift), want)["rel_errs"])

            program.append(worst(params))
            shifted.append(worst(params, shift=1))
            control.append(worst(coarse_matrices(params)))  # in place: last
        ok = max(program) <= LOGIT_TOL < min(control)
        print(json.dumps(dict(line, **{
            verdict: bool(ok or tiny), "layers": cut.kinds,
            "seeds": args.harness_cut, "program": program,
            "control_coarse_matrices": control,
            "reported_position_off_by_one": shifted})))
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
    want = {}
    t0 = time.perf_counter()
    for b in picked:
        n = int(lengths[b])
        x = jnp.asarray(params["wte"][toks[b:b + 1, :n + steps]], jnp.float32)
        for kind, w in ref.layer_weights(params, cfg.kinds):
            x = layer[kind](x, w=w)
        want[b] = np.asarray(head(x[:, n - 1:], params))[0]
    reference_s = time.perf_counter() - t0
    del layer, head, x

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

    def errors(got):
        """Over the compared positions; the last ``tail`` steps apart."""
        errs = {b: logit_errors(list(got[b]), list(want[b]))["rel_errs"]
                for b in picked}
        every = [r for e in errs.values() for r in e]
        late = [r for e in errs.values() for r in e[-tail:]]
        by_rung = {"short_rows": [errs[b] for b in picked if b % 2 == 0],
                   "long_rows": [errs[b] for b in picked if b % 2]}
        return {"median_rms": float(np.median(every)),
                "worst_rms": max(every),
                "prefill_median_rms": float(np.median(
                    [e[0] for e in errs.values()])),
                "tail_median_rms": float(np.median(late)),
                **{name + "_tail_median_rms": float(np.median(
                    [r for e in rows for r in e[-tail:]]))
                   for name, rows in by_rung.items() if rows}}

    good = errors(through_the_engine())
    rounded = errors(through_the_engine(state_in_bfloat16=True))
    engine.params = coarse_matrices(engine.params)
    control = errors(through_the_engine())
    ok = (good["worst_rms"] <= LOGIT_TOL < control["median_rms"]
          and good["tail_median_rms"] <= STATE_TOL
          < rounded["tail_median_rms"])
    print(json.dumps(dict(line, **{
        verdict: bool(ok or tiny), "state_tolerance": STATE_TOL,
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


def time_delta(cfg, slots, tiny, olmo_hybrid, olmo_hybrid_decode) -> dict:
    """One linear layer alone, on this device: milliseconds a call, the
    best of three batches of ten after a warm-up."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import flops_olmo_hybrid as fl

    one = dataclasses.replace(cfg, layer_pattern="L", n_layer=1,
                              vocab_size=256)
    m = olmo_hybrid.olmo_hybrid_init(
        jax.random.PRNGKey(0), one)["blocks"]["linear"]
    dt, key = jnp.dtype(cfg.dtype), jax.random.PRNGKey(1)
    model = dataclasses.asdict(one)

    def ms(fn, *xs, donated=None):
        out = fn(*xs)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                if donated is not None:  # the state goes round
                    xs = (*xs[:donated], out[donated], *xs[donated + 1:])
                out = fn(*xs)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / 10 * 1e3)
        return round(best, 3)

    out = {"sequence_ms": {}, "chunked_rule_ms": {}}
    h, dk, dv = (one.linear_num_heads, one.linear_key_head_dim,
                 one.linear_value_head_dim)
    for rows in ((512, 2048) if not tiny else (32,)):
        y = jax.random.normal(key, (1, rows, one.d_model), dt)
        lengths = jnp.asarray([rows - 3])
        q, k = (jax.random.normal(kk, (1, rows, h, dk), jnp.float32) / dk ** 0.5
                for kk in jax.random.split(key))
        v = jax.random.normal(key, (1, rows, h, dv), jnp.float32)
        g = -jnp.full((1, rows, h), 0.02, jnp.float32)
        beta = jnp.ones((1, rows, h), jnp.float32)
        for chunk in ((32, 64, 128) if not tiny else (8,)):
            c = dataclasses.replace(one, chunk_size=chunk)
            name = f"rows{rows}_chunk{chunk}"
            out["sequence_ms"][name] = ms(jax.jit(
                lambda y, n, m, c=c: olmo_hybrid.delta_sequence(
                    y, n, m, 0, c)), y, lengths, m)
            out["chunked_rule_ms"][name] = ms(jax.jit(
                lambda *a, c=c: olmo_hybrid.delta_chunked(
                    *a, c.chunk_size)), q, k, v, g, beta)
        out.setdefault("chunked_rule_gflop", {})[f"rows{rows}"] = round(
            fl.delta_chunk_flops(model, rows, one.chunk_size) / 1e9, 2)
    cache = olmo_hybrid_decode.olmo_hybrid_init_cache(one, slots, 8)
    y = jax.random.normal(key, (slots, one.d_model), dt)
    step = jax.jit(lambda y, conv, state, m: olmo_hybrid_decode.delta_step(
        y, conv, state, m, 0, one), donate_argnums=(2,))

    # (out, conv, state): the new state, at 2, is the next call's, at 2
    update_ms = ms(step, y, cache["conv"][0], cache["state"][0], m, donated=2)
    state_bytes = 2 * slots * fl.state_bytes_per_slot(model)
    weight_bytes = 2.0 * fl.delta_params(model)
    out["one_token_update"] = {
        "slots": slots, "ms": update_ms,
        "state_read_and_written_mb": round(state_bytes / 1e6, 1),
        "mixer_weights_mb": round(weight_bytes / 1e6, 1),
        "gb_per_s": round((state_bytes + weight_bytes) / update_ms / 1e6, 1)}
    return out


if __name__ == "__main__":
    sys.exit(main())
