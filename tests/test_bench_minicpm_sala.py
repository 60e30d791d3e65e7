"""The MiniCPM-SALA configuration, traffic, arithmetic and metric files the
benchmark gained in PR 62, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_minicpm_sala import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    workers, traced, with prompts past the tiny ``dense_len`` (every prefill
    and decode step selects) and the harness's two-layer reference check
    (one sparse and one lightning layer; at the tiny ``dense_len`` of 64 its
    64 + 3 positions DO select, as the published 8192 never lets them),
    ending in a line that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "sala_l12_longctx_closed8", "--seed", "6200000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/minicpm_sala_all_layers.py``: all the layers through the
    engine's own programs from prompts past ``dense_len`` and one under it,
    padded with anything, against the reference by the rule and by recency,
    the state rounded to bfloat16 where the cache holds it and the coarse
    matrices, walked at tiny widths (where the scales leave the limits
    without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "minicpm_sala_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 3 * 13 and last["layers"] == "SLLSSL"
    *selecting, dense = last["lengths"]
    assert dense + 12 < 64 <= min(selecting)
    for name in ("program", "control_selection_by_recency",
                 "control_state_in_bfloat16", "control_coarse_matrices"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    # (at d 64 the embedding is most of the logits: the controls show, by
    # less than at the published widths)
    assert (last["control_coarse_matrices"]["median_rms"]
            > 2 * last["program"]["median_rms"])
    assert (last["control_selection_by_recency"]["median_rms"]
            > 2 * last["program"]["median_rms"])


def test_the_harness_cut_rehearses_on_the_cpu():
    cut = run(os.path.join(REPO, "benchmarks", "minicpm_sala_all_layers.py"),
              "--rehearse-cpu", "--harness-cut", "2")
    assert cut["rehearsal_ok"] is True and cut["layers"] == "SL"
    assert len(cut["program"]) == len(cut["control_coarse_matrices"]) == 2
    assert min(cut["control_coarse_matrices"]) > 2 * max(cut["program"])
