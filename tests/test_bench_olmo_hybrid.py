"""The Olmo-Hybrid configuration, traffic, arithmetic and metric files the
benchmark gained in PR 56, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_olmo_hybrid import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_cell_lists_itself_where_its_metrics_are_true(monkeypatch):
    """The benchmark's case of this name (star-imported above, shadowed
    here) holds PR 56's two metrics to list this cell ALONE, which no later
    cell with a delta rule of its own can keep (``delta_chunk_fill_pct.serve``
    reads Kimi-Linear's scan too: PR 67), and the benchmark's files are
    add-only, its tests among them.  The same case over the cells as far as
    PR 56 wrote them, and which later cells joined its two metrics by
    name."""
    import benchmarks.tests.test_bench_olmo_hybrid as theirs

    joined = {}

    def load_as_pr56_left_it(*path):
        bench = theirs_load(*path)
        if path[-1] == "BENCHMARK.json":
            names = [w["name"] for w in bench["workloads"]]
            later = names[names.index(theirs.CELL) + 1:]
            for metric in bench["end_to_end"] + bench["per_layer"]:
                if "workloads" in metric:  # a later cell's name, appended
                    if metric["name"] in theirs.NEW_METRICS:
                        joined[metric["name"]] = [
                            w for w in metric["workloads"] if w in later]
                    metric["workloads"] = [
                        w for w in metric["workloads"] if w not in later]
        return bench

    theirs_load = theirs.load
    monkeypatch.setattr(theirs, "load", load_as_pr56_left_it)
    theirs.test_the_cell_lists_itself_where_its_metrics_are_true()
    assert joined == {
        "delta_decode_roofline.serve": [],
        "delta_chunk_fill_pct.serve": [
            "kimilinear_ep16_rollout_closed64"]}  # PR 67


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
    workers, traced, with the harness's two-layer reference check (one full
    and one linear layer, whose 64 + 3 positions are eight of the tiny
    chunks), ending in a line that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "olmohybrid_l12_reason_closed64", "--seed", "5600000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/olmo_hybrid_all_layers.py``: all the layers through the
    engine's own programs with prompts of a few and of many chunks, padded
    with anything, the state rounded to bfloat16 where the cache holds it
    and the coarse matrices, walked at tiny widths (where the scales leave
    the limits without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "olmo_hybrid_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 4 * 13 and last["layers"] == "FLLFLL"
    short_rows, long_rows = last["lengths"][::2], last["lengths"][1::2]
    assert max(short_rows) < 32 < 64 < min(long_rows)  # 3-4 and 10-13 chunks
    for name in ("program", "control_state_in_bfloat16",
                 "control_coarse_matrices"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    assert (last["control_coarse_matrices"]["median_rms"]
            > 3 * last["program"]["median_rms"])


def test_the_builders_timings_and_the_harness_cut_rehearse_on_the_cpu():
    script = os.path.join(REPO, "benchmarks", "olmo_hybrid_all_layers.py")
    cut = run(script, "--rehearse-cpu", "--harness-cut", "2")
    assert cut["rehearsal_ok"] is True and cut["layers"] == "FL"
    assert len(cut["program"]) == len(cut["control_coarse_matrices"]) == len(
        cut["reported_position_off_by_one"]) == 2
    assert min(cut["control_coarse_matrices"]) > 3 * max(cut["program"])
    timed = run(script, "--rehearse-cpu", "--time-delta")
    assert timed["rehearsal_ok"] is True
    assert set(timed["sequence_ms"]) == set(timed["chunked_rule_ms"]) == {
        "rows32_chunk8"}
    assert timed["one_token_update"]["slots"] == 4
    assert timed["one_token_update"]["ms"] > 0
