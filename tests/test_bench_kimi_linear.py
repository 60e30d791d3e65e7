"""The Kimi-Linear configuration, the two traffic files, arithmetic and
metric files the benchmark gained in PR 67, under every PR's tests: the
cases live beside the code they pin."""

from benchmarks.tests.test_bench_kimi_linear import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(*command):
    out = subprocess.run(
        [sys.executable, *command], capture_output=True, text=True,
        timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [
    "kimilinear_ep16_rollout_closed64", "mistral16_decode_closed16"])
def test_the_new_cells_rehearse_on_the_cpu_with_their_trace(cell):
    """``benchmarks/selftest.py --rehearse`` names its cells and may not be
    edited by the PR that adds one (the benchmark's files are add-only), so
    the new cells' rehearsals live here, ``selftest.py --rehearse``'s way:
    serve -> proxy -> ``LLMServer`` -> ``JaxLLMEngine`` at tiny widths on CPU
    workers, traced, with the harness's two-layer reference check (for
    Kimi-Linear: KDA + the dense MLP, latent attention + the experts),
    ending in a line that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               cell, "--seed", "6700000019", "--seconds", "3", "--trace",
               "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/kimi_linear_all_layers.py``: all the layers through the
    engine's own programs from prompts of 3 and 5 tokens and of two longer
    lengths, padded with anything, against the reference as the model is,
    with a scalar gate and reading one latent too few, and with the state
    rounded to bfloat16 where the cache holds it, walked at tiny widths
    (where the scales leave the limits without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "kimi_linear_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 4 * 13 and last["layers"] == "kMKKKM"
    assert last["lengths"][:2] == [3, 5]
    for name in ("program", "control_state_in_bfloat16",
                 "control_scalar_gate", "control_latent_one_position_short"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    assert (last["control_scalar_gate"]["median_rms"]
            > 5 * last["program"]["median_rms"])
    assert (last["control_latent_one_position_short"]["tiny_rows_worst_rms"]
            > 5 * last["program"]["worst_rms"])


def test_the_harness_cut_and_the_timings_rehearse_on_the_cpu():
    script = os.path.join(REPO, "benchmarks", "kimi_linear_all_layers.py")
    cut = run(script, "--rehearse-cpu", "--harness-cut", "2")
    assert cut["rehearsal_ok"] is True and cut["layers"] == "kM"
    assert len(cut["program"]) == len(cut["control_scalar_gate"]) == 2
    assert min(cut["control_scalar_gate"]) > 5 * max(cut["program"])
    timed = run(script, "--rehearse-cpu", "--time-delta")
    assert timed["rehearsal_ok"] is True
    assert set(timed["vector_gate_rule_ms"]) == set(
        timed["scalar_gate_rule_ms"]) == {"rows32_chunk8"}
    assert timed["one_token_update"]["slots"] == 4
