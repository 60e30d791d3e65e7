"""The Nemotron-H configuration, traffic, arithmetic and readers the
benchmark gained in PR 39, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_nemotron_h import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_new_cell_rehearses_on_the_cpu_with_its_trace():
    """``benchmarks/selftest.py --rehearse`` names its cells and may not be
    edited by the PR that adds one (the benchmark's files are add-only), so
    the new cell's rehearsal lives here: serve -> proxy -> ``LLMServer`` ->
    ``JaxLLMEngine`` at tiny widths on CPU workers, traced, with the
    harness's two-layer reference check, ending in a line that cannot be
    mistaken for a run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "nemotron3s_ep4_agent_closed64", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


import pytest  # noqa: E402


@pytest.mark.parametrize("mode", [[], ["--harness-cut", "2"]],
                         ids=["all_layers", "harness_cut"])
def test_the_builders_comparison_rehearses_on_the_cpu(mode):
    """``benchmarks/nemotron_h_all_layers.py``: all the layers through the
    engine's own programs, and the harness's two-layer cut with the
    harness's own functions, each with its control (the mixers' matrices at
    three bits of mantissa), walked at tiny widths."""
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "nemotron_h_all_layers.py"),
         "--rehearse-cpu", *mode],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_ok"] is True and "ok" not in last
    program, control = last["program"], last["control_coarse_mixers"]
    if mode:
        assert len(program) == len(control) == 2
        assert max(program) < min(control)
    else:
        assert program["median_rms"] < control["median_rms"]
