"""The MiMo-V2 configuration, traffic, arithmetic and metric files the
benchmark gained in PR 45, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_mimo_v2 import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_cell_lists_itself_where_its_metrics_are_true(monkeypatch):
    """The benchmark's case of this name (star-imported above, shadowed
    here) holds PR 45's four metrics to be the LAST four of ``per_layer`` and
    its cell the LAST of ``workloads``, which no later PR that adds a metric
    or a cell can keep, and the benchmark's files are add-only, its tests
    among them.  The same case over ``per_layer`` and ``workloads`` as far as
    PR 45 wrote them, and what was appended since by name."""
    import benchmarks.tests.test_bench_mimo_v2 as theirs

    appended, cells = [], []

    def load_as_pr45_left_it(*path):
        bench = theirs_load(*path)
        if path[-1] == "BENCHMARK.json":
            names = [m["name"] for m in bench["per_layer"]]
            cut = names.index(theirs.NEW_METRICS[-1]) + 1
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
    monkeypatch.setattr(theirs, "load", load_as_pr45_left_it)
    theirs.test_the_cell_lists_itself_where_its_metrics_are_true()
    assert appended == [
        "cache_read_pct.serve",  # PR 46
        "latent_long_decode_roofline.serve", "prefill_mfu.serve",  # PR 48
        "prefill_useful_pct.serve_rate", "ep8_expert_tokens.serve",
        "ep8_experts_touched_pct.serve",
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
    assert cells == ["mistral4_ep8_longdoc_closed32",  # PR 48
                     "laguna_ep16_code_closed32",  # PR 52
                     "olmohybrid_l12_reason_closed64",  # PR 56
                     "granite4h_micro_chat_closed64",  # PR 60
                     "sala_l12_longctx_closed8",  # PR 62
                     "mistral16_longprompt_closed16",  # PR 66
                     "kimilinear_ep16_rollout_closed64",
                     "mistral16_decode_closed16"]  # PR 67


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
    + 3 positions wrap the tiny ring of 8 eight times), ending in a line
    that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "mimo25_ep16_mixed_closed64", "--seed", "4500000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


@pytest.mark.parametrize("mode", [[], ["--harness-cut", "2"]],
                         ids=["all_layers", "harness_cut"])
def test_the_builders_comparison_rehearses_on_the_cpu(mode):
    """``benchmarks/mimo_v2_all_layers.py``: all the layers through the
    engine's own programs across a wrapped ring with its two controls (no
    sink; the matrices at three bits of mantissa), and the harness's
    two-layer cut with the harness's own functions, walked at tiny
    widths."""
    last = run(os.path.join(REPO, "benchmarks", "mimo_v2_all_layers.py"),
               "--rehearse-cpu", *mode)
    assert last["rehearsal_ok"] is True and "ok" not in last
    program, control = last["program"], last["control_coarse_matrices"]
    if mode:
        assert len(program) == len(control) == 2
    else:
        assert last["steps"] == 40 and last["positions"] == 20
        # the sink is a fifth of the stream even at these widths
        assert program["worst_rms"] < last["control_no_sink"]["median_rms"]
        assert program["median_rms"] < control["median_rms"]
