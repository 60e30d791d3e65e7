"""The Mistral-4 configuration, traffic, arithmetic, reader and metric files
the benchmark gained in PR 48, under every PR's tests: the cases live beside
the code they pin."""

from benchmarks.tests.test_bench_mistral4 import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_cell_lists_itself_where_its_metrics_are_true(monkeypatch):
    """The benchmark's case of this name (star-imported above, shadowed
    here) holds PR 48's five metrics to list its cell ALONE, which no later
    cell that reports ``prefill_mfu.serve`` can keep, and the cell's list to
    be the MiMo cell's but for its own, which no later metric of the long
    prompts' cells can keep, and the benchmark's files are add-only, its
    tests among them.  The same case over the cells and metrics as far as
    PR 48 wrote them (``tests/test_bench_mimo_v2.py``'s way), and what was
    appended since by name."""
    import benchmarks.tests.test_bench_mistral4 as theirs

    appended, cells = [], []

    def load_as_pr48_left_it(*path):
        bench = theirs_load(*path)
        if path[-1] == "BENCHMARK.json":
            names = [m["name"] for m in bench["per_layer"]]
            cut = max(map(names.index, theirs.NEW_METRICS)) + 1
            appended[:] = names[cut:]
            bench["per_layer"] = bench["per_layer"][:cut]
            names = [w["name"] for w in bench["workloads"]]
            cut = names.index(theirs.CELL) + 1
            cells[:] = names[cut:]
            bench["workloads"] = bench["workloads"][:cut]
            for metric in bench["end_to_end"] + bench["per_layer"]:
                if "workloads" in metric:  # a later cell's name, appended
                    metric["workloads"] = [
                        w for w in metric["workloads"] if w not in cells]
        return bench

    theirs_load = theirs.load
    monkeypatch.setattr(theirs, "load", load_as_pr48_left_it)
    theirs.test_the_cell_lists_itself_where_its_metrics_are_true()
    assert cells == ["laguna_ep16_code_closed32",  # PR 52
                     "olmohybrid_l12_reason_closed64",  # PR 56
                     "granite4h_micro_chat_closed64",  # PR 60
                     "sala_l12_longctx_closed8",  # PR 62
                     "mistral16_longprompt_closed16",  # PR 66
                     "kimilinear_ep16_rollout_closed64",
                     "mistral16_decode_closed16"]  # PR 67
    assert appended == [
        "relayout_ms.train",  # PR 50
        "ring_long_decode_roofline.serve", "top10_expert_tokens.serve",
        "top10_experts_touched_pct.serve",  # PR 52
        "prefill_chunk_fill_pct.serve",  # PR 53
        "held_loop_turns.serve",  # PR 54
        "delta_decode_roofline.serve", "delta_chunk_fill_pct.serve",  # PR 56
        "attn_ms.serve", "experts_ms.serve", "state_ms.serve", "mlp_ms.serve",
        "head_ms.serve", "unscoped_pct.serve", "prefill_experts_ms.serve_rate",
        "prefill_state_ms.serve_rate", "attn_ms.train", "mlp_ms.train",
        "head_ms.train", "unscoped_pct.train",  # PR 58
        "ssm_decode_roofline.serve", "prefill_ssm_ms.serve_rate",
        "ssm_chunk_fill_pct.serve",  # PR 60
        "lightning_ms.serve", "select_ms.serve",
        "prefill_lightning_ms.serve_rate", "prefill_sparse_ms.serve_rate",
        "sparse_read_pct.serve", "lightning_chunk_fill_pct.serve",
        "sala_decode_roofline.serve", "sala_unscoped_pct.serve",  # PR 62
        "kimi_decode_roofline.serve", "kda_update_roofline.serve"]  # PR 67


def run(*command):
    out = subprocess.run(
        [sys.executable, *command], capture_output=True, text=True,
        timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_new_cell_rehearses_on_the_cpu_with_its_trace():
    """``benchmarks/selftest.py --rehearse`` names its cells and may not be
    edited by the PR that adds one (the benchmark's files are add-only), so
    the new cell's rehearsal lives here, ``selftest.py --rehearse``'s way:
    serve -> proxy -> ``LLMServer`` -> ``JaxLLMEngine`` at tiny widths on CPU
    workers, traced, with the harness's two-layer reference check (whose 64
    + 3 positions cross the tiny trained length of 16 four times), ending in
    a line that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "mistral4_ep8_longdoc_closed32", "--seed", "4800000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/mistral4_all_layers.py``: all the layers through the
    engine's own programs with rows beyond and below the trained length, the
    reference's two switched-off controls and the coarse matrices, walked at
    tiny widths (where the scales leave the limit without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "mistral4_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 20 and last["layers"] == 2
    long_rows, short_rows = last["lengths"][:2], last["lengths"][2:]
    assert min(long_rows) > 4 * 16 > max(short_rows) > 16
    for name in ("program", "control_no_yarn",
                 "control_no_query_scale_long_rows",
                 "control_coarse_matrices"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    assert last["attention_layer0"]["positions"] == long_rows[0]
