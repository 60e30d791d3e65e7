"""A Laguna cell's comparison over ALL its layers, through the engine's own
compiled programs, at contexts on both sides of the window's 512 positions
and of the trained 8192, on the chip (the builder's check beside the
harness's two-layer one, ``lib/bench_server.py`` ``check_reference``, whose 67
positions see neither the window's edge, nor a wrapped ring, nor a rotary
pair that YaRN slows, nor a second tile of the prefill):

  python3 benchmarks/laguna_all_layers.py [--config <name>] [--seed n]
      [--rehearse-cpu]

One process builds ``JaxLLMEngine`` at the configuration's widths and slots,
with the family's seeded weights.  Half the slots get a prompt of ``LONG`` =
8,900-9,100 random ids through ``jit_prefill_one`` at the 16,384 rung (18
query tiles of the full layers, the band of the window layers, rings that
wrapped seventeen times), the other half one of ``SHORT`` = 440-500 at the
512 rung (inside the window), then the engine's decode program runs
``STEPS`` = 96 steps on the full batch, whose long rows sit beyond position
8192 and whose short rows CROSS 512 on the way (their rings wrap under the
decode step's own writes), fed a fixed token sequence (not what it samples),
so that the plain float32 reference can run the same tokens in one full
forward.  The reference runs ``ROWS`` of the slots (half long, half short),
layer by layer (``reference/laguna_ref.py`` ``ref_layer``: one jitted block
at a time, keys and values repeated to the query heads, dense scores
computed for 512 query rows at a time against all keys with the window as a
mask, weights upcast matrix by matrix, so it fits beside the engine's 10.8
GB).  Compared: the logits after prefill, after the first decode step, a
middle one and the last two (``CHECK``), at each position the RMS of the
difference over the vocabulary as a share of the reference logits' standard
deviation: the harness's statistic and the harness's limit
(``bench_server.LOGIT_TOL``, 3 %), which the program's WORST position must
keep.  No routing choice flips (``families/laguna.py``: the routers read
channels no layer writes), so what separates the program from the reference
is rounding alone: bfloat16 where a product reads its input, nine layers
deep.  Four controls whose MEDIAN position must come out over the limit,
each over the rows where the mechanism acts: the reference computed without
the gate (every row), with plain rotary at base 5e5 in the full layers
(YaRN left out: the LONG rows, whose slowed pairs have turned by radians),
with a window of 576 (an eighth too wide: the LONG rows; a short row meets
the edge in its last steps only), and the engine's programs on the same
tokens with every matrix of ``blocks`` and ``experts`` rounded in place to
three bits of mantissa (float8_e4m3's precision at bfloat16's range;
``reduce_precision``, because the compiler folds a cast to float8 and back
into nothing), the embedding and the head as they are.  A comparison that
passes the program and cannot fail these proves nothing at this length.
Reported beside them and NOT part of the verdict: the window ONE too wide
(ISSUE 52 asked for it as a control; one key of 512 moves the logits by
about what the rounding does, so a comparison of logits at 3 % cannot tell
them apart here: median 2.5 %, worst 3.6, against the program's 1.35 and 1.7
at the cell's draw; a draw sharp enough to fail it by its median, 3.7 %,
took the program's own worst position to 3.26 %; with flat attention it
read 0.46 against 0.39: my chip runs, PR 52, calls 8, 3 and 1; the tiny
tests fail it at a window of 8, ``tests/test_decode_live_extent.py`` holds a
ring of 512 to its last position, op by op).

Prints one JSON line (with the share of a row's attention mass that its
largest score holds, layer 0, the last query of a long row: what the
weights' scales were reckoned for); exit code 1 when the comparison or a
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
LONG, SHORT = (8900, 9100), (440, 500)
CHECK = (0, 1, 48, 95, 96)  # decode steps run before the logits compared


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="laguna_s21_l9_ep16")
    ap.add_argument("--seed", type=int, default=5200000101)
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
    from benchmarks.reference import laguna_ref as ref
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
    long_, short = (LONG, SHORT) if not tiny else ((70, 90), (3, 6))
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
    def layer(attn_kind, mlp_kind, **switches):
        return jax.jit(functools.partial(
            ref.ref_layer, attn_kind=attn_kind, mlp_kind=mlp_kind,
            sizes=dict(fam.sizes_of(cfg), query_block=512, **switches),
            expert_offset=cfg.expert_offset))

    head = jax.jit(functools.partial(ref.ref_head, sizes=fam.sizes_of(cfg)))

    def reference(rows, **switches):
        want = {}
        for b in rows:
            n = int(lengths[b])
            x = jnp.asarray(engine.params["wte"][toks[b:b + 1, :n + steps]],
                            jnp.float32)
            for attn_kind, mlp_kind, attn, mlp, experts in ref.layer_weights(
                    engine.params, cfg.attn_kinds, cfg.mlp_kinds):
                x = layer(attn_kind, mlp_kind, **switches)(
                    x, attn, mlp, experts)
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
        """Of the last query of a long row, layer 0 (full): the share of each
        head's attention mass that its largest score holds, mean over heads
        (plain float32, the program's own projection)."""
        from ray_tpu.models import laguna

        b = long_rows[0]
        n = int(lengths[b])
        att = jax.tree.map(lambda a: a[:1].astype(jnp.float32),
                           engine.params["blocks"]["full"])
        x = jnp.asarray(engine.params["wte"][toks[b:b + 1, :n]], jnp.float32)
        y = ref._rms(x, att["rms"][0], cfg.rms_eps)
        q, k, _ = laguna.attention_project(y, att, 0, jnp.arange(n), "F", cfg)
        groups = cfg.n_head // cfg.n_kv_head
        s = jnp.einsum("kgd,skd->kgs", q[0, -1].reshape(
            cfg.n_kv_head, groups, cfg.head_dim), k[0]) * cfg.head_dim ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        return {"positions": n, "score_std": float(jnp.std(s, axis=-1).mean()),
                "largest_share": float(p.max(-1).mean()),
                "effective_keys": float((1 / (p * p).sum(-1)).mean())}

    want = reference(picked)
    good = errors(got, want)
    no_gate = errors(got, reference(picked, gate=False))
    no_yarn = errors(got, reference(long_rows, yarn=False))
    wide = errors(got, reference(long_rows, window=cfg.window + max(
        cfg.window // 8, 2)))
    one_wide = errors(got, reference(long_rows, window=cfg.window + 1))
    attention = largest_share()
    # Coarse matrices, rounded in place leaf by leaf (a second copy of 4 GB
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
        no_gate["median_rms"], no_yarn["median_rms"], wide["median_rms"],
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
        "control_no_gate": no_gate,
        "control_no_yarn_long_rows": no_yarn,
        "control_window_an_eighth_too_wide_long_rows": wide,
        "reported_window_one_too_wide_long_rows": one_wide,
        "control_coarse_matrices": coarse_run}))
    return 0 if ok or tiny else 1


if __name__ == "__main__":
    sys.exit(main())
