#!/usr/bin/env python3
"""The benchmark's own checks, run by hand on the CPU (not under tests/):

  python3 benchmarks/selftest.py [--rehearse]

* every name in BENCHMARK.json resolves to its files, readers and job kind;
* the traffic generator is a pure function of the seed, and every seed offers
  the same sizes in the same cyclic order;
* the serving window's arithmetic, the training start's two numbers, which
  metrics a cell's line carries and the no-op test's arithmetic
  (``tests/test_bench_window.py``, under pytest);
* the trace reduction gives the pinned busy / idle / kernel numbers on the
  small recorded trace (``lib/trace_sample.json``, a slice of a chip trace),
  and agrees with a brute-force count;
* both plain references agree with the program at tiny widths, and the
  serving check's limit passes bf16 and fails float8 weights and a decode
  position off by one;
* ``--rehearse``: a CPU rehearsal of one cell of each job kind ends in a
  well-formed last line that cannot be mistaken for a run.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load(path):
    with open(path) as f:
        return json.load(f)


def test_files_resolve():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        cfg = load(os.path.join(ROOT, configs[cell["config"]]["file"]))
        assert cfg["reduced"] == configs[cell["config"]]["reduced"], cell
        assert hasattr(importlib.import_module(
            "benchmarks.families." + cfg["family"]), "config")
        mix = load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
        kind = importlib.import_module("benchmarks.jobs." + mix["kind"])
        assert hasattr(kind, "run")
        # The job kind gives every end-to-end number that lists the cell.
        if mix["kind"] == "serve_stream":
            gives = set(kind.end_to_end(kind.reduce_window(
                {"t0": 0.0, "t_end": 1.0, "requests": []}, 1.0)))
        else:
            gives = {"train_tokens_per_s", *kind.split_setup(0, 0, 0, 0)}
        listed = {m["name"] for m in bench["end_to_end"]
                  if cell["name"] in m.get("workloads", [])}
        assert listed and listed <= gives | {"setup_s"}, (cell, listed, gives)
    for m in bench["per_layer"]:
        spec = load(os.path.join(HERE, "layer_metrics", m["name"] + ".json"))
        assert spec["name"] == m["name"]
        assert hasattr(importlib.import_module(
            "benchmarks.readers." + spec["reader"]), "read")
        moved = e2e[m["moves"]]
        cells = m.get("workloads", [c["name"] for c in bench["workloads"]])
        assert all("workloads" not in moved or c in moved["workloads"]
                   for c in cells), m["name"]
    assert sorted(n[:-5] for n in os.listdir(
        os.path.join(HERE, "layer_metrics"))) == sorted(
            m["name"] for m in bench["per_layer"])  # none left reading null
    peaks = load(os.path.join(HERE, "lib", "peaks.json"))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


def test_traffic_is_a_function_of_the_seed():
    from benchmarks.lib import traffic

    mix = load(os.path.join(HERE, "traffic", "chat_closed16.json"))
    a, b = traffic.requests(mix, 3000000019), traffic.requests(mix, 3000000019)
    c = traffic.requests(mix, 7)
    assert a == b and a != c
    size = lambda rs: sorted((r["prompt_tokens"], r["max_tokens"]) for r in rs)
    assert size(a) == size(c) == sorted(traffic.sizes(mix))
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= r["prompt_tokens"] <= hi
               and len(r["prompt"].encode()) == r["prompt_tokens"] - 1
               for r in a)


def test_window_arithmetic():
    out = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(HERE, "tests"), "-q",
         "-p", "no:cacheprovider"], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:]
    print("   ", out.stdout.strip().splitlines()[-1])


def brute_busy(events):
    """Covered length by testing every elementary segment: O(n^2), no merge."""
    cuts = sorted({t for _n, s, d in events for t in (s, s + d)})
    return sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s <= a and b <= s + d for _n, s, d in events))


def test_trace_reduction():
    from benchmarks.lib import trace_reduce as tr

    # By hand: two overlapping ops, a gap, a kernel nested in a while.
    ops = [("fusion.1", 0, 100), ("copy.2", 50, 100), ("while.3", 300, 200),
           ("k.4 tpu_custom_call", 320, 80), ("all-reduce.5", 450, 100)]
    chip = tr.ChipTrace("/device:TPU:0", {
        tr.OPS_LINE: ops, tr.MODULES_LINE: [("jit_step(1)", 0, 550)]})
    assert chip.busy_ns == 150 + 250 == brute_busy(ops)
    assert chip.window_ns == 550
    assert dict(tr.self_times(ops))["while.3"] == 200 - 80 - 50
    assert chip.exposed_ns("all-reduce") == 100  # the while is a wrapper
    hidden = tr.ChipTrace("/device:TPU:0", {
        tr.OPS_LINE: ops + [("fusion.6", 430, 50)], tr.MODULES_LINE: []})
    assert hidden.exposed_ns("all-reduce") == 70  # 450..480 is behind fusion.6
    assert [g for g in chip.idle_gaps()] == [("jit_step -> jit_step", 150)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]

    sample = load(os.path.join(HERE, "lib", "trace_sample.json"))
    trace = tr.Trace.from_planes(sample)
    pinned = load(os.path.join(HERE, "lib", "trace_sample_expected.json"))
    chip = trace.chips[0]
    got = {
        "ops": len(chip.ops), "window_ns": chip.window_ns,
        "busy_ns": chip.busy_ns,
        "kernel_ns": sum(d for _n, _s, d in
                         chip.matching(chip.ops, "tpu_custom_call")),
        "top_op": trace.breakdown()["device_ops"][0][0],
    }
    assert got == pinned, (got, pinned)
    assert chip.busy_ns == brute_busy(chip.ops)


def test_module_reader_wants_one_program():
    from benchmarks.lib import trace_reduce as tr
    from benchmarks.readers import module_ms
    from benchmarks.run import ReadContext

    def ctx(modules):
        chip = tr.ChipTrace("/device:TPU:0", {
            tr.OPS_LINE: [("fusion.1", 0, 10)], tr.MODULES_LINE: modules})
        return ReadContext(trace=tr.Trace([chip]), stats={}, config={},
                           mix={}, peaks={}, chips=1)

    one = ctx([("jit__lambda(3)", 0, 2_000_000), ("jit__lambda(4)", 0, 4_000_000),
               ("jit_prefill_one(5)", 0, 9_000_000)])
    assert module_ms.read(one, "^jit__lambda") == 3.0
    assert module_ms.read(one, "^jit_nothing") is None
    two = ctx([("jit__lambda(3)", 0, 2_000_000), ("jit__lambda_1(4)", 0, 4_000_000)])
    try:
        module_ms.read(two, "^jit__lambda")
    except ValueError:
        pass
    else:
        raise AssertionError("two programs were merged into one median")


def test_references_agree_with_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import gpt2, llama
    from benchmarks.lib import bench_server
    from ray_tpu.models import model_family

    g = load(os.path.join(HERE, "configs", "gpt2_medium.json"))["tiny"]
    cfg = gpt2.config(dict(g, dtype="float32"))
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65), dtype=np.int32))
    a = float(gpt2.loss(params, toks, cfg))
    b = float(gpt2.reference_loss(params, toks, cfg))
    assert abs(a - b) / b < 1e-5, (a, b)  # float32 both sides

    # Wider than the rehearsal's widths: at d 64 the blocks add a tenth of
    # what the embedding carries to the logits (weights of scale 0.02), and
    # no fault inside a block shows; from d 512 on they add as much.
    m = dict(load(os.path.join(HERE, "configs", "mistral7b_l16.json"))["tiny"],
             d_model=512, d_ff=1024, n_head=8, n_kv_head=2)
    t = np.random.default_rng(1).integers(0, 512, (1, 35), dtype=np.int32)

    def errors(dtype, weights=None, **fault):
        model = dict(m, dtype=dtype)
        lcfg = llama.config(model)
        lp = llama.load_params(model, 3000000019)
        ref = np.asarray(llama.reference_logits(lp, jnp.asarray(t), lcfg))[0]
        served = lp if weights is None else jax.tree.map(
            lambda w: w.astype(weights).astype(w.dtype), lp)
        got = bench_server.through_the_cache(
            model_family(lcfg), served, lcfg, t, 32, 3, **fault)
        return lp, bench_server.logit_errors(
            got, [ref[31 + i] for i in range(4)])

    lp, f32 = errors("float32")
    assert lp["blocks"]["w_up"].shape == (2, 512, 1024)
    assert 0.015 < float(lp["blocks"]["w_up"].std()) < 0.025
    assert max(f32["worst_logit"]) < 1e-4, f32  # float32 both sides
    # What the serving check's limit (LOGIT_TOL) tells apart, in the served
    # type: bf16 is inside it; 8-bit float weights and a wrong position are not.
    _lp, bf16 = errors("bfloat16")
    assert bf16["ok"], bf16
    for fault in ({"weights": jnp.float8_e4m3fn}, {"shift": 1}):
        _lp, bad = errors("bfloat16", **fault)
        assert not bad["ok"], (fault, bad)
        print("   ", {k: str(v) for k, v in fault.items()}, "->",
              [round(e, 4) for e in bad["rel_errs"]], "against bf16",
              [round(e, 4) for e in bf16["rel_errs"]])


def test_rehearsals():
    for cell in ("gpt2m_dp_1chip", "mistral16_chat_closed16"):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
             "--seed", "3000000019", "--seconds", "3", "--trace", "1",
             "--rehearse-cpu"], capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        last = json.loads(out.stdout.strip().splitlines()[-1])
        assert last["rehearsal_ok"] is True and last["attempted"] > 0
        assert not {"metrics", "correct", "device"} & set(last), last


if __name__ == "__main__":
    tests = [test_files_resolve, test_traffic_is_a_function_of_the_seed,
             test_window_arithmetic, test_trace_reduction,
             test_module_reader_wants_one_program,
             test_references_agree_with_the_program]
    if "--rehearse" in sys.argv:
        tests.append(test_rehearsals)
    for t in tests:
        t()
        print("ok", t.__name__)
