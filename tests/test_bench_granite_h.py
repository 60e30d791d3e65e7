"""The Granite-4.0-H configuration, traffic, arithmetic and metric files the
benchmark gained in PR 60, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_granite_h import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def as_pr60_left_it(monkeypatch):
    """The benchmark's cases of PR 60 (star-imported above, two of them
    shadowed below) hold its configuration to be the LAST of ``configs`` and
    its cell the LAST of ``workloads``, which no later PR that adds either
    can keep, and the benchmark's files are add-only, its tests among them.
    ``load`` gives ``BENCHMARK.json`` as far as PR 60 wrote it; what was
    appended since comes back by name."""
    import benchmarks.tests.test_bench_granite_h as theirs

    later = {"configs": [], "workloads": []}
    theirs_load = theirs.load

    def load(*path):
        bench = theirs_load(*path)
        if path[-1] == "BENCHMARK.json":
            for key, last in (("configs", "granite4h_micro"),
                              ("workloads", theirs.CELL)):
                names = [entry["name"] for entry in bench[key]]
                cut = names.index(last) + 1
                later[key][:] = names[cut:]
                bench[key] = bench[key][:cut]
            for metric in bench["end_to_end"] + bench["per_layer"]:
                if "workloads" in metric:  # a later cell's name, appended
                    metric["workloads"] = [w for w in metric["workloads"]
                                           if w not in later["workloads"]]
        return bench

    monkeypatch.setattr(theirs, "load", load)
    return theirs, later


def test_the_configuration_is_the_source_with_nothing_cut(  # noqa: F811
        as_pr60_left_it):
    theirs, later = as_pr60_left_it
    theirs.test_the_configuration_is_the_source_with_nothing_cut(
        theirs.load(theirs.HERE, "configs", "granite4h_micro.json"))
    assert later["configs"] == ["minicpm_sala_l12",  # PR 62
                                "kimi_linear_l21_ep16"]  # PR 67


def test_the_cell_lists_itself_where_its_metrics_are_true(  # noqa: F811
        as_pr60_left_it):
    theirs, later = as_pr60_left_it
    theirs.test_the_cell_lists_itself_where_its_metrics_are_true()
    assert later["workloads"] == ["sala_l12_longctx_closed8",  # PR 62
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
    workers, traced, with the harness's two-layer reference check (two
    Mamba-2 layers and their MLPs, whose 64 + 3 positions are eight of the
    tiny chunks), ending in a line that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "granite4h_micro_chat_closed64", "--seed", "6000000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/granite_h_all_layers.py``: all the layers through the
    engine's own programs with prompts of a few and of many chunks, padded
    with anything, the state rounded to bfloat16 where the cache holds it
    and the coarse matrices, walked at tiny widths (where the scales leave
    the limits without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "granite_h_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 4 * 13 and last["layers"] == "MM*MMM*M"
    short_rows, long_rows = last["lengths"][::2], last["lengths"][1::2]
    assert max(short_rows) < 32 < 64 < min(long_rows)  # 3-4 and 10-13 chunks
    for name in ("program", "control_state_in_bfloat16",
                 "control_coarse_matrices"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    assert (last["control_coarse_matrices"]["median_rms"]
            > 3 * last["program"]["median_rms"])


def test_the_harness_cut_rehearses_on_the_cpu():
    cut = run(os.path.join(REPO, "benchmarks", "granite_h_all_layers.py"),
              "--rehearse-cpu", "--harness-cut", "2")
    assert cut["rehearsal_ok"] is True and cut["layers"] == "MM"
    assert len(cut["program"]) == len(cut["control_coarse_matrices"]) == 2
    assert min(cut["control_coarse_matrices"]) > 3 * max(cut["program"])
