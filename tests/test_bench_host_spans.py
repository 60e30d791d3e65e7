"""The benchmark's readers of the program's host spans
(``benchmarks/lib/host_spans.py`` and the three readers built on it): pinned
numbers on hand-built intervals and on a small recorded sample of a chip
trace (``benchmarks/lib/host_spans_sample.json``).  CPU only; no timing.
"""

import json
import os
import types

import pytest

from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.readers import idle_in_span_pct, span_ms, span_stat

HERE = os.path.dirname(os.path.abspath(hs.__file__))


def ctx_of(*files, trace=object()):
    """What a reader gets, with the spans handed over instead of found."""
    return types.SimpleNamespace(host_spans=list(files), trace=trace,
                                 config={}, mix={}, stats={})


# By hand, in ns.  The device is busy 0-100, 150-300, 600-1000: idle 50 + 300
# of a 1000 window.  Thread 0 steps twice (the first step admits), thread 1
# waits for the lock meanwhile.
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 100], ["fusion.2", 150, 150],
                  ["copy.3", 600, 400]],
    tr.MODULES_LINE: [["jit_prefill_one(1)", 0, 100],
                      ["jit__lambda(2)", 150, 150],
                      ["jit_sample_logits(3)", 700, 20],
                      ["jit__lambda(4)", 640, 360]]}}
HOST = [
    [["engine.lock_wait", 0, 5, {"request_id": 7}],
     ["engine.step", 10, 390, {"seq": 0}],
     ["engine.admit", 20, 100, {"request_id": 7, "slot": 0, "prompt_len": 30,
                                "padded_len": 128, "queue_wait_ms": 4.0}],
     ["engine.prefill.dispatch", 25, 10, {}],
     ["engine.sample", 40, 80, {"slots": 1}],
     ["engine.decode.dispatch", 140, 20, {"active": 1}],
     ["engine.sample", 160, 230, {"slots": 1}],
     ["engine.counts", 395, 0, {"occupied": 1, "waiting": 1, "admitted": 1,
                                "retired": 0}],
     ["engine.step", 500, 450, {"seq": 1}],
     ["engine.admit", 505, 60, {"request_id": 8, "slot": 1, "prompt_len": 98,
                                "padded_len": 128, "queue_wait_ms": 10.0}],
     ["engine.decode.dispatch", 630, 15, {"active": 2}],
     ["engine.sample", 650, 290, {"slots": 2}],
     ["engine.counts", 945, 0, {"occupied": 2, "waiting": 0, "admitted": 1,
                                "retired": 0}]],
    [["engine.lock_wait", 12, 480, {"request_id": 8}]],
]


@pytest.fixture
def by_hand():
    return hs.from_planes(HOST, DEVICE)


def test_spans_nest_by_containment(by_hand):
    first, second = by_hand.threads
    assert [r.name for r in first] == ["engine.lock_wait", "engine.step",
                                      "engine.step"]
    step = first[1]
    assert [c.name for c in step.children] == [
        "engine.admit", "engine.decode.dispatch", "engine.sample",
        "engine.counts"]
    assert [c.name for c in step.children[0].children] == [
        "engine.prefill.dispatch", "engine.sample"]
    assert {d.name for d in step.descendants()} >= {"engine.prefill.dispatch"}
    assert [r.name for r in second] == ["engine.lock_wait"]
    assert len(by_hand.spans("engine.sample")) == 3
    assert len(by_hand.spans()) == 14


def test_idle_inside_and_outside_spans_by_hand(by_hand):
    [chip] = by_hand.chips
    assert hs.idle_gaps(chip) == [(100, 150), (300, 600)]
    assert hs.intersect_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    # Samples cover 40-120, 160-390, 650-940: idle inside them 100-120 and
    # 300-390 = 110 of the 1000 ns window.
    assert hs.idle_pct([by_hand], "engine.sample", inside=True) == 11.0
    assert hs.idle_pct([by_hand], "engine.sample", inside=False) == 24.0
    # Steps cover 10-400 and 500-950: idle outside them 400-500 = 100.
    assert hs.idle_pct([by_hand], "engine.step", inside=False) == 10.0
    ctx = ctx_of(by_hand)
    assert idle_in_span_pct.read(ctx, "engine.sample", "inside") == 11.0
    assert idle_in_span_pct.read(ctx, "engine.step", "outside") == 10.0
    # inside + outside of one name is device_idle_pct's idle: 35 %.
    assert (idle_in_span_pct.read(ctx, "engine.step", "inside")
            + idle_in_span_pct.read(ctx, "engine.step", "outside")) == 35.0
    with pytest.raises(ValueError):
        idle_in_span_pct.read(ctx, "engine.step", "around")
    split = hs.idle_by_span(by_hand, chip, "engine.step")
    assert split == {
        "engine.admit": 80e-9, "engine.decode.dispatch": 10e-9,
        "engine.sample": 90e-9, "engine.counts": 0.0,
        # 120-140, 390-400 of the first step; 500-505, 565-600 of the second.
        "(in a step, in no child)": pytest.approx(70e-9),
        "(outside every step)": 100e-9, "(all idle)": 350e-9,
        "(window)": 1000e-9}


def test_span_readers_by_hand(by_hand):
    ctx = ctx_of(by_hand)
    assert span_ms.read(ctx, "engine.step") == (390 + 450) / 2 / 1e6
    assert span_ms.read(ctx, "engine.lock_wait") == (5 + 480) / 2 / 1e6
    # Both steps admitted: none is a plain decode step.
    assert span_ms.read(ctx, "engine.step", without="engine.admit") is None
    assert span_ms.read(ctx, "engine.step", without="engine.nothing") == (
        span_ms.read(ctx, "engine.step"))
    assert span_stat.read(ctx, "engine.admit", "median", "queue_wait_ms") == 7.0
    assert span_stat.read(ctx, "engine.counts", "mean", "occupied") == 1.5
    assert span_stat.read(ctx, "engine.admit", "ratio_pct", "prompt_len",
                          over="padded_len") == 50.0
    assert span_stat.read(ctx, "engine.admit", "mean", "no_such") is None
    with pytest.raises(ValueError):
        span_stat.read(ctx, "engine.admit", "mode", "slot")


def test_clock_checks_by_hand(by_hand):
    [chip] = by_hand.chips
    # dispatch spans start at 140 and 630; decode programs at 150 and 640.
    assert hs.dispatch_lags_ms(by_hand, chip, "engine.decode.dispatch",
                               "^jit__lambda") == [10 / 1e6, 10 / 1e6]
    # The one sample program (700-720) runs inside the last sample span
    # (650-940); no decode program starts inside a sample span.
    assert hs.tail_margins_ms(by_hand, chip, "engine.sample",
                              "^jit_sample_logits") == [(940 - 720) / 1e6]
    assert hs.tail_margins_ms(by_hand, chip, "engine.sample",
                              "^jit__lambda") == []


def test_a_trace_without_spans_reads_none():
    """The parent commit's program has no span: every reader leaves its
    metric out of the line, and raises nothing."""
    bare = hs.from_planes([], DEVICE)
    for ctx in (ctx_of(bare), ctx_of(), ctx_of(trace=None)):
        assert span_ms.read(ctx, "engine.step") is None
        assert span_ms.read(ctx, "engine.step", without="engine.admit") is None
        assert span_stat.read(ctx, "engine.counts", "mean", "occupied") is None
        assert idle_in_span_pct.read(ctx, "engine.sample", "inside") is None
        assert idle_in_span_pct.read(ctx, "engine.step", "outside") is None
    # No trace at all (--trace 0): nothing is looked for on disk.
    plain = types.SimpleNamespace(trace=None, config={"name": "x"},
                                  mix={"name": "y"})
    assert hs.for_ctx(plain) == []


@pytest.mark.parametrize("cell, config, traffic", [
    ("gpt2m_dp_1chip", "gpt2_medium", "dp_32k"),
    ("mistral16_chat_closed16", "mistral7b_l16", "chat_closed16"),
])
def test_the_cell_is_found_from_config_and_traffic(cell, config, traffic):
    """``ReadContext`` carries the configuration file and the traffic file,
    not the cell: their ``name`` keys find it, and with it the trace."""
    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    assert hs.cell_name(cfg, mix) == cell
    ctx = types.SimpleNamespace(config=cfg, mix=mix, trace=object())
    assert hs.trace_dir(ctx) == os.path.join(
        hs.ROOT, ".bench_out", cell, "trace")
    with pytest.raises(LookupError):
        hs.cell_name({"name": config}, {"name": "no_such_traffic"})


def test_every_new_metric_resolves_to_a_reader():
    with open(os.path.join(hs.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"] if m["name"] in {
        "decode_step_host_ms.serve", "idle_in_sample_pct.serve",
        "idle_outside_step_pct.serve", "lock_wait_ms.serve",
        "queue_wait_ms.serve", "prefill_useful_pct.serve",
        "occupied_slots_mean.serve", "report_span_ms.train"}]
    assert len(new) == 8
    readers = {"span_ms": span_ms, "span_stat": span_stat,
               "idle_in_span_pct": idle_in_span_pct}
    ctx = ctx_of(hs.from_planes(HOST, DEVICE))
    for m in new:
        with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert m["source"] in ("host_clock", "program_counter", "device_trace")
        value = readers[spec["reader"]].read(ctx, **spec["args"])
        assert value is None or value >= 0  # the arguments fit the reader


# ------------------------------------------------ the recorded sample
# 347.6 ms of a traced run of mistral16_chat_closed16 on a v5e (PR 25): three
# engine steps, the middle one admitting a request.  Host spans as recorded;
# the device's operations merged into their busy intervals.  (No lock wait
# lies wholly inside so short a slice: they last 0.7-1.2 s.)
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "host_spans_sample.json")) as f:
        sample = json.load(f)
    return hs.from_planes(sample["host"], sample["device"])


def test_the_recorded_sample_nests_as_the_engine_wrote_it(recorded):
    [chip] = recorded.chips
    assert (chip.window_ns, chip.busy_ns) == (344386380, 231112947)
    assert len(recorded.threads) == 3  # three replica threads stepped
    steps = sorted(recorded.spans("engine.step"), key=lambda s: s.start)
    assert [s.stats["seq"] for s in steps] == [134, 135, 136]
    assert [[c.name for c in s.children] for s in steps] == [
        ["engine.retire", "engine.decode.dispatch", "engine.sample",
         "engine.retire", "engine.counts"],
        ["engine.admit", "engine.retire", "engine.decode.dispatch",
         "engine.sample", "engine.retire", "engine.counts"],
        ["engine.retire", "engine.decode.dispatch", "engine.sample",
         "engine.retire", "engine.counts"]]
    [admit] = recorded.spans("engine.admit")
    assert [c.name for c in admit.children] == [
        "engine.prefill.dispatch", "engine.sample"]
    assert {k: admit.stats[k] for k in ("request_id", "slot", "prompt_len",
                                        "padded_len")} == {
        "request_id": 34, "slot": 11, "prompt_len": 150, "padded_len": 2048}
    assert len(admit.stats["trace_id"]) == 32  # the proxy's cluster trace
    # Steps of different threads never overlap: the engine lock.
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))


def test_readers_on_the_recorded_sample(recorded):
    ctx = ctx_of(recorded)
    exact = pytest.approx
    assert span_ms.read(ctx, "engine.step") == 66.740657
    assert span_ms.read(ctx, "engine.step",
                        without="engine.admit") == 66.0036215
    assert span_ms.read(ctx, "engine.lock_wait") is None
    assert span_stat.read(ctx, "engine.counts", "mean",
                          "occupied") == exact(41 / 3, rel=1e-12)
    assert span_stat.read(ctx, "engine.admit", "median",
                          "queue_wait_ms") == exact(55.333208, rel=1e-9)
    assert span_stat.read(ctx, "engine.admit", "ratio_pct", "prompt_len",
                          over="padded_len") == 100 * 150 / 2048
    inside = idle_in_span_pct.read(ctx, "engine.sample", "inside")
    outside = idle_in_span_pct.read(ctx, "engine.step", "outside")
    assert inside == exact(32.17031550434718, rel=1e-12)
    assert outside == exact(0.22126891313181432, rel=1e-12)
    [chip] = recorded.chips
    idle_pct = 100.0 * (1 - chip.busy_ns / chip.window_ns)  # device_idle_pct
    assert inside + outside <= idle_pct
    assert (idle_in_span_pct.read(ctx, "engine.step", "inside")
            + outside) == exact(idle_pct, rel=1e-12)


def test_idle_by_span_and_the_clock_on_the_recorded_sample(recorded):
    [chip] = recorded.chips
    split = {k: round(v * 1e9) for k, v in
             hs.idle_by_span(recorded, chip, "engine.step").items()}
    assert split == {
        "engine.admit": 3240176, "engine.retire": 25829,
        "engine.decode.dispatch": 691746, "engine.sample": 108469451,
        "engine.counts": 1760, "(in a step, in no child)": 82451,
        "(outside every step)": 762020, "(all idle)": 113273433,
        "(window)": 344386380}
    parts = [v for k, v in split.items()
             if k not in ("(all idle)", "(window)")]
    assert sum(parts) == split["(all idle)"]
    # One clock: each decode program starts after its dispatch span opens,
    # and each sample span ends after its last sampling program.
    assert hs.dispatch_lags_ms(recorded, chip, "engine.decode.dispatch",
                               "^jit__lambda") == [0.041477, 0.649362,
                                                   0.683488]
    assert hs.tail_margins_ms(recorded, chip, "engine.sample",
                              "^jit_sample_logits") == [
        2.312178, 1.72525, 1.849416, 1.856229]
