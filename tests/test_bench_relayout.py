"""``relayout_ms.train`` (PR 50): the data file the benchmark gained for the
device time of the programs that only move or turn an array, read off the
recorded slice of a chip trace that ``benchmarks/selftest.py`` pins."""

import json
import os
import re

import pytest

from benchmarks.lib import trace_reduce
from benchmarks.run import ReadContext, read_layer_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "relayout_ms.train"


def load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return load("benchmarks", "layer_metrics", NAME + ".json")


@pytest.mark.parametrize("op,counted", [
    ("copy.139", True), ("copy", True), ("slice_bitcast_fusion.8", True),
    ("copy_bitcast_fusion.2", True),
    # a prefetch's two halves, the split of another array, the in-place
    # writes of the stacked gradients, and what computes
    ("copy-start.3", False), ("copy-done.3", False), ("slice-done.9", False),
    ("dynamic-slice_bitcast_fusion.14", False),
    ("bitcast_dynamic-update-slice_fusion.12", False), ("fusion.348", False),
    ("convolution_add_fusion.7", False),
    ("checkpoint.10 tpu_custom_call", False),
])
def test_the_pattern_takes_the_three_groups_and_nothing_else(spec, op,
                                                             counted):
    assert spec["reader"] == "op_ms_per_run"
    assert spec["args"]["per_module"] == "^jit_step"
    assert bool(re.search(spec["args"]["pattern"], op)) is counted


def test_the_sample_trace_reads_its_copies_a_step():
    """Two steps in the slice; by hand: every ``copy.N`` 2,991,692 ns and
    ``slice_bitcast_fusion.8`` 1,219,759 ns (no ``copy_bitcast_fusion`` ran
    inside it), over two runs of ``jit_step``."""
    bench = load("BENCHMARK.json")
    trace = trace_reduce.Trace.from_planes(
        load("benchmarks", "lib", "trace_sample.json"))
    only = {**bench, "per_layer": [m for m in bench["per_layer"]
                                   if m["name"] == NAME]}
    ctx = ReadContext(trace=trace, stats={}, config={}, mix={}, peaks={},
                      chips=1)
    for cell in ("gpt2m_dp_1chip", "gpt2m_dp_4chip"):
        assert read_layer_metrics(only, cell, ctx) == {NAME: {
            "value": pytest.approx((2991692 + 1219759) / 2 / 1e6),
            "unit": "ms"}}
    assert read_layer_metrics(only, "mistral16_chat_closed16", ctx) == {}
    ctx.trace = None  # an untraced run: left out of the line
    assert read_layer_metrics(only, "gpt2m_dp_1chip", ctx) == {}


def test_the_entry_is_appended_and_as_the_issue_names_it():
    per_layer = load("BENCHMARK.json")["per_layer"]
    names = [m["name"] for m in per_layer]
    at = names.index(NAME)
    assert at > names.index("ep8_experts_touched_pct.serve")  # PR 48's last
    assert per_layer[at] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s",
        "workloads": ["gpt2m_dp_1chip", "gpt2m_dp_4chip"]}
