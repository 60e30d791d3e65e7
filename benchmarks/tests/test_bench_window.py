"""The serving window's arithmetic (``jobs/serve_stream.py``: ``lead_in_s``,
``reduce_window``, ``end_to_end``, ``percentile``, ``longest_stall_s``), the
generator's promise that every seed offers the same sizes in the same cyclic
order (``lib/traffic.py``), the training run's start in two numbers
(``jobs/train_dp.py`` ``split_setup``), which metrics a cell's line carries
(``run.py`` ``end_to_end_metrics``), what ``BENCHMARK.json`` promises of
``moves``, and the no-op test's arithmetic (``sets.py``): pure functions on
hand-built request logs.  CPU only; no sleeping, no cluster.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks import sets  # noqa: E402
from benchmarks.jobs import serve_stream as ss  # noqa: E402
from benchmarks.jobs import train_dp  # noqa: E402
from benchmarks.lib import traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# The clients' first send at 100.0, a lead-in of 5 s, a window of 10 s.
T_FIRST, T0, T_END, SECONDS = 100.0, 105.0, 115.0, 10.0


def request(t_send, token_times, error=None):
    return {"t_send": t_send, "token_times": list(token_times),
            "error": error, "asked": len(token_times),
            "text": "x" * len(token_times),
            "t_end": max([t_send, *token_times])}


def window(*requests, t0=T0, t_end=T_END):
    load = {"t_first": T_FIRST, "t0": t0, "t_end": t_end,
            "requests": list(requests), "lateness": []}
    return ss.reduce_window(load, t_end - t0)


@pytest.mark.parametrize("seconds, lead", [
    (45.0, 5.0), (90.0, 5.0), (15.0, 5.0), (3.0, 1.0), (1.5, 0.5)])
def test_lead_in_is_five_seconds_from_fifteen_on(seconds, lead):
    assert ss.lead_in_s(seconds) == lead
    assert ss.LEAD_IN_S == 5.0


@pytest.mark.parametrize("t_send, times, samples, tokens", [
    # Sent in the lead-in, answered in it: nothing of it is in the window.
    (100.0, [100.9, 101.0, 101.1], [], 0),
    # Sent in the lead-in, still streaming when the window opens: no TTFT
    # sample, and only the tokens received from t0 on.
    (104.0, [104.5, 104.9, 105.0, 105.5], [], 2),
    # The window's first instant belongs to it.
    (105.0, [105.25], [250.0], 1),
    # Sent in the window, answered in it.
    (110.0, [110.5, 111.0], [500.0], 2),
    # Sent before the end, answered after it: a TTFT sample, no token.
    (114.5, [115.0, 115.5], [500.0], 0),
    # Sent before the end, tokens on both sides of it: the sample, and the
    # tokens received before the end alone.
    (114.0, [114.75, 115.0, 116.0], [750.0], 1),
])
def test_ttft_by_send_time_tokens_by_arrival(t_send, times, samples, tokens):
    win = window(request(t_send, times))
    assert win["ttft_ms"] == pytest.approx(samples)
    assert win["tokens"] == tokens
    assert win["tokens_per_s"] == tokens / SECONDS
    assert win["attempted"] == len(samples) and win["failed"] == 0


@pytest.mark.parametrize("times, gaps", [
    ([104.0, 104.5, 105.0], [500.0]),          # a gap that ends at t0 counts
    ([104.5, 105.5], [1000.0]),                # taken by its end, whole
    ([110.0, 110.0, 110.0, 110.25], [0.0, 0.0, 250.0]),  # one chunk: gaps 0
    ([114.5, 115.0], []),                      # ends at t_end: outside
    ([114.0, 114.5, 115.5, 116.0], [500.0]),
    ([103.0, 104.0], []),                      # all of it in the lead-in
])
def test_gaps_are_taken_by_their_end(times, gaps):
    win = window(request(104.0, times))
    assert win["itl_ms"] == pytest.approx(gaps)


@pytest.mark.parametrize("in_window", [True, False])
def test_a_failed_request_counts_and_gives_no_sample(in_window):
    bad = request(110.0 if in_window else 101.0, [], error="ServerError: x")
    good = request(106.0, [106.5])
    win = window(bad, good)
    assert win["ttft_ms"] == pytest.approx([500.0])
    assert win["attempted"] == (2 if in_window else 1)
    assert win["failed"] == (1 if in_window else 0)
    assert win["errors"] == (["ServerError: x"] if in_window else [])


def test_a_failed_request_that_streamed_first_still_failed():
    win = window(request(110.0, [110.5], error="stream ended without [DONE]"))
    assert win["failed"] == 1 and win["attempted"] == 1
    assert win["tokens"] == 1  # the client did receive it


def test_a_reply_with_no_token_is_short_not_failed():
    win = window(request(110.0, []), request(111.0, [111.5]))
    assert win["attempted"] == 2 and win["failed"] == 0
    assert win["ttft_ms"] == pytest.approx([500.0])


def test_the_old_window_reads_the_same_log_from_the_first_send():
    """What ``run`` reports under ``from_first_send``: ``seconds`` from the
    first send, so the opening burst is among the samples."""
    burst = request(100.0, [101.0, 101.5])
    later = request(106.0, [106.25, 112.0])
    assert window(burst, later)["ttft_ms"] == pytest.approx([250.0])
    old = window(burst, later, t0=T_FIRST, t_end=T_FIRST + SECONDS)
    assert old["ttft_ms"] == pytest.approx([1000.0, 250.0])
    assert old["tokens"] == 3  # 112.0 is past 110.0


@pytest.mark.parametrize("n, pct, index", [
    (1, 90, 0), (1, 50, 0), (10, 90, 9), (10, 50, 5), (10, 95, 9),
    (200, 90, 180), (200, 95, 190), (200, 50, 100), (215, 90, 193)])
def test_percentile_picks_the_element_it_says(n, pct, index):
    values = [float(v) for v in range(n)]
    shuffled = values[1::2] + values[0::2]
    assert ss.percentile(shuffled, pct) == values[index]
    assert ss.percentile([], pct) is None
    # Beyond the 90th of 200 lie 19 samples; of 100, nine: the guide's ten
    # want a window of over 100 requests.
    assert n - 1 - index == len([v for v in values if v > values[index]])


def test_end_to_end_of_a_window_and_of_an_empty_one():
    win = window(*[request(106.0 + i * 0.01, [106.5 + i * 0.02, 107.0 + i])
                   for i in range(8)])
    e2e = ss.end_to_end(win)
    assert e2e["serve_tokens_per_s"] == win["tokens"] / SECONDS
    assert e2e["ttft_p50_ms"] == ss.percentile(win["ttft_ms"], 50)
    assert e2e["itl_p95_ms"] == ss.percentile(win["itl_ms"], 95)
    assert ss.end_to_end(window()) == {
        "serve_tokens_per_s": 0.0, "ttft_p50_ms": None, "itl_p95_ms": None}


@pytest.mark.parametrize("n, index, beyond", [
    # The Mistral chat cell: 351-377 requests a window (PR 37's tree).
    (351, 175, 175), (372, 186, 185), (377, 188, 188),
    # Were the LongCat agent cell judged on it: 160-183.
    (160, 80, 79), (171, 85, 85), (183, 91, 91)])
def test_the_ttft_median_is_the_element_its_name_says(n, index, beyond):
    values = [float(v) for v in range(n)]
    win = window(*[request(106.0, [106.0 + v / 1e3]) for v in reversed(values)])
    assert ss.end_to_end(win)["ttft_p50_ms"] == pytest.approx(values[index])
    assert n - 1 - index == beyond


@pytest.mark.parametrize("gaps, index", [(100, 95), (73_000, 69_350), (21, 19)])
def test_the_gap_tail_is_the_element_its_name_says(gaps, index):
    # One request whose tokens come 1, 2, 3 ... ms apart: gap i is i + 1 ms.
    times, t = [106.0], 106.0
    for i in range(gaps):
        t += (i + 1) / 1e3
        times.append(t)
    win = ss.reduce_window(
        {"t_first": T_FIRST, "t0": T0, "t_end": t + 1.0, "lateness": [],
         "requests": [dict(request(105.5, times), t_end=t)]}, t + 1.0 - T0)
    assert len(win["itl_ms"]) == gaps
    assert ss.end_to_end(win)["itl_p95_ms"] == pytest.approx(index + 1)


@pytest.mark.parametrize("requests, stall", [
    # Tokens every half second from 105 to 115: the longest silence is 0.5.
    ([(104.0, [105.0 + i / 2 for i in range(20)], 115.0)], 0.5),
    # Nothing between 107 and 110.5 while a request is out: 3.5 s.
    ([(104.0, [105.0, 106.0, 107.0, 110.5, 111.0], 115.0),
      (106.0, [106.5, 107.0], 107.0)], 4.0),
    # The same silence, but nobody was waiting from 108 to 110: the stall is
    # the longer piece with a request in flight, 107 -> 108.
    ([(104.0, [105.0, 106.0, 107.0], 108.0),
      (110.0, [110.5, 111.0, 112.0, 113.0, 114.0, 114.5], 114.5)], 1.0),
    # Silence outside the window does not count; inside it is cut at the edge.
    ([(100.0, [101.0, 104.0, 112.0], 120.0)], 7.0),
    # No request at all: nothing was outstanding.
    ([], 0.0),
])
def test_the_longest_stall_wants_a_request_in_flight(requests, stall):
    load = {"t_first": T_FIRST, "t0": T0, "t_end": T_END, "lateness": [],
            "requests": [dict(request(t_send, times), t_end=t_done)
                         for t_send, times, t_done in requests]}
    assert ss.longest_stall_s(load) == pytest.approx(stall)


@pytest.mark.parametrize("t_start, t_fit, t_enter, t_window", [
    (1000.0, 1002.9, 1006.5, 1026.0),    # one chip: ~3.6 s of gang wait
    (1000.0, 1003.1, 1027.6, 1040.7),    # four chips, a quick start
    (1000.0, 1003.1, 1041.9, 1055.1),    # four chips, a slow one: same set-up
    (5.0, 5.0, 5.0, 5.0)])
def test_setup_and_the_gang_wait_add_up_to_the_old_setup(
        t_start, t_fit, t_enter, t_window):
    start = train_dp.split_setup(t_start, t_fit, t_enter, t_window)
    assert set(start) == {"setup_s", "gang_ready_s"}
    assert start["gang_ready_s"] == pytest.approx(t_enter - t_fit)
    assert start["setup_s"] + start["gang_ready_s"] == pytest.approx(
        t_window - t_start)  # what setup_s was until PR 38


def test_the_gang_wait_moves_the_gang_wait_alone():
    quick = train_dp.split_setup(0.0, 3.0, 27.5, 43.8)
    slow = train_dp.split_setup(0.0, 3.0, 41.8, 58.1)
    assert quick["setup_s"] == pytest.approx(slow["setup_s"])
    assert slow["gang_ready_s"] - quick["gang_ready_s"] == pytest.approx(14.3)


def cells():
    return [pytest.param(w["name"], id=w["name"]) for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", cells())
def test_a_cells_line_carries_the_metrics_that_list_it(cell):
    values = {m["name"]: 1.5 for m in BENCH["end_to_end"]}
    got = bench_run.end_to_end_metrics(BENCH, cell, values)
    want = {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(got) == want and "setup_s" in got and len(got) >= 2
    assert got["setup_s"] == {"value": 1.5, "unit": "s"}


def test_a_cell_in_no_metrics_list_prints_setup_s_alone():
    values = {m["name"]: 2.0 for m in BENCH["end_to_end"]}
    got = bench_run.end_to_end_metrics(BENCH, "a_cell_nobody_lists", values)
    assert got == {"setup_s": {"value": 2.0, "unit": "s"}}


@pytest.mark.parametrize("metric", [
    pytest.param(m, id=m["name"]) for m in BENCH["per_layer"]])
def test_a_layer_metric_moves_a_number_its_cells_report(metric):
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert "workloads" not in moved or cell in moved["workloads"], cell
    spec = os.path.join(ROOT, "benchmarks", "layer_metrics",
                        metric["name"] + ".json")
    with open(spec) as f:
        assert json.load(f)["name"] == metric["name"]


def test_no_layer_metric_file_is_left_without_its_entry():
    folder = os.path.join(ROOT, "benchmarks", "layer_metrics")
    assert sorted(n[:-5] for n in os.listdir(folder)) == sorted(
        m["name"] for m in BENCH["per_layer"])


@pytest.mark.parametrize("values, want", [
    ([100.0, 101.0, 102.0, 103.0, 104.0, 105.0], 4.0 / 102.5),
    # One far-off run a set is carried: it is the one left out.
    ([100.0, 101.0, 102.0, 103.0, 104.0, 81.0], 4.0 / 101.5),
    ([100.0, 101.0, 102.0, 103.0, 104.0, 130.0], 4.0 / 102.5),
    # Two are not.
    ([100.0, 101.0, 102.0, 103.0, 82.0, 81.0], 21.0 / 100.5),
    ([50.0, 50.0], 0.0)])
def test_range5_leaves_out_the_run_farthest_from_the_median(values, want):
    assert sets.range5(values) == pytest.approx(want)


def test_the_rule_reads_what_the_drivers_check_reads():
    one = [100.0, 100.5, 101.0, 101.5, 102.0, 102.5]
    two = [100.2, 100.7, 101.2, 101.7, 102.2, 102.7]
    got = sets.rule(one, two)
    assert got["medians"] == [101.25, 101.45]
    assert got["medians_differ"] == pytest.approx(0.2 / 101.25)
    assert got["range5"] == pytest.approx([2.0 / 101.25, 2.0 / 101.45])
    assert got["iqr_sets"] == pytest.approx([1.75 / 101.25, 1.75 / 101.45])
    assert got["tight"] == pytest.approx(
        sets.iqr(one[:-1]) + sets.iqr(two[:-1]))  # each without its farthest
    assert got["loose"] == pytest.approx(8 * sets.iqr(one + two))
    assert got["bound"] == pytest.approx(5 * 1.75 / 101.25)
    assert got["tight"] <= got["bound"] <= got["loose"]
    # A number that does not move asks for the floor, never less.
    flat = sets.rule([7.0] * 6, [7.0] * 6)
    assert flat["bound"] == 0.01 and flat["medians_differ"] == 0.0


@pytest.mark.parametrize("one, two, bound, judged", [
    # Quartile spreads of ~1 %: five times that, inside the window.
    ([100.0, 100.5, 101.0, 101.5, 102.0, 102.5],
     [100.2, 100.7, 101.2, 101.7, 102.2, 102.7], 0.05, True),
    # One far-off run a set is carried by the "too tight" clause ...
    ([100.0, 100.5, 101.0, 101.5, 102.0, 80.0],
     [100.2, 100.7, 101.2, 101.7, 102.2, 130.0], 0.05, True),
    # ... and a spread of 30 % by no bound the contract allows.
    ([100.0, 110.0, 120.0, 130.0, 140.0, 150.0],
     [101.0, 111.0, 121.0, 131.0, 141.0, 151.0], 0.10, False),
    # Medians further apart than the bound: not let through.
    ([100.0, 100.5, 101.0, 101.5, 102.0, 102.5],
     [108.0, 108.5, 109.0, 109.5, 110.0, 110.5], 0.05, False),
    # Steady to a hair: 1 % is never too loose, 5 % is.
    ([100.0, 100.01, 100.02, 100.03, 100.04, 100.05],
     [100.0, 100.01, 100.02, 100.03, 100.04, 100.05], 0.01, True),
    ([100.0, 100.01, 100.02, 100.03, 100.04, 100.05],
     [100.0, 100.01, 100.02, 100.03, 100.04, 100.05], 0.05, False)])
def test_the_no_op_test_at_a_bound(one, two, bound, judged):
    readings = sets.rule(one, two)
    assert sets.passes(readings, bound) is judged
    if readings["bound"] is None:
        assert not judged and readings["tight"] > sets.CAP
    else:
        assert sets.passes(readings, readings["bound"]) or (
            readings["medians_differ"] >= readings["bound"])


@pytest.mark.parametrize("metric", [
    pytest.param(m, id=m["name"]) for m in BENCH["end_to_end"]])
def test_no_bound_is_over_the_contracts_cap(metric):
    assert 0.01 <= metric["bound"] <= sets.CAP


def test_the_sets_interleave_over_the_same_seeds():
    import argparse

    args = argparse.Namespace(
        seeds="1,2,3,4,5,6", cold_seed=9, seconds=45.0, trace_seeds="7,8",
        parent="", parent_seeds="")
    runs = sets.plan(args)
    labels = [r[0] for r in runs]
    assert labels == ["cold"] + ["set1", "set2"] * 6 + ["traced"] * 2
    one = [r[2] for r in runs if r[0] == "set1"]
    two = [r[2] for r in runs if r[0] == "set2"]
    assert sorted(one) == sorted(two) == [1, 2, 3, 4, 5, 6]
    assert all(a != b for a, b in zip(one, two))  # never twice in a row
    assert [r[4] for r in runs if r[0] == "traced"] == [1, 1]


def mixes():
    folder = os.path.join(ROOT, "benchmarks", "traffic")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            mix = json.load(f)
        if mix["kind"] == "serve_stream":
            yield pytest.param(mix, id=name)
            yield pytest.param(dict(mix, **mix["tiny"]), id=name + ":tiny")


@pytest.mark.parametrize("mix", mixes())
def test_every_seed_offers_the_same_sizes_in_the_same_round(mix):
    a = traffic.requests(mix, 3000000019)
    b = traffic.requests(mix, 7)
    size = lambda reqs: [  # noqa: E731
        (r["prompt_tokens"], r["max_tokens"]) for r in reqs]
    assert a == traffic.requests(mix, 3000000019)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # The seed says where in the population's fixed round the clients start.
    rounds = [traffic.sizes(mix)[i:] + traffic.sizes(mix)[:i]
              for i in range(len(a))]
    assert size(a) in rounds and size(b) in rounds and size(a) != size(b)


def read_context(stats, config=None, mix=None):
    return bench_run.ReadContext(
        trace=None, stats=stats, config=config or {}, mix=mix or {},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)


class StepTrace:
    """As much of ``lib.trace_reduce.Trace`` as ``module_ms`` reads."""

    def __init__(self, runs_ns):
        self.runs_ns = runs_ns

    def module_names(self, pattern):
        return {"jit_step"} if self.runs_ns else set()

    def module_runs(self, pattern):
        return self.runs_ns


@pytest.mark.parametrize("runs_ns, want", [
    # 32 x 1024 tokens of 1e9 operations each in a second: 32.768 TFLOP/s.
    ([1_000_000_000], 100 * 32.768e12 / 197e12),
    # The median of the runs on the device, all chips, not the host's clock.
    ([400_000_000, 500_000_000, 9_000_000_000], 100 * 32.768e12 / 0.5 / 197e12),
    ([], None)])
def test_the_training_steps_share_of_the_peak(runs_ns, want):
    from benchmarks.readers import mfu_train

    ctx = read_context({"flops_per_token": 1e9, "rows_per_chip": 32,
                        "seq": 1024, "step_ms": [123.0]})
    ctx.trace = StepTrace(runs_ns)
    got = mfu_train.read(ctx, "^jit_step")
    assert got == (pytest.approx(want) if want is not None else None)
    ctx.trace = None  # no trace, nothing to read: never the host's clock
    assert mfu_train.read(ctx, "^jit_step") is None


def test_a_share_of_the_peak_with_nothing_to_read_is_left_out():
    from benchmarks.readers import mfu_decode

    ctx = read_context({"model": {}}, config={"family": "llama"})
    assert mfu_decode.read(ctx, "^jit__lambda", "engine.counts") is None


@pytest.mark.parametrize("sizes, want", [
    ([(100, 10)], 105.0), ([(100, 10), (300, 30)], (10 * 105 + 30 * 315) / 40)])
def test_the_mean_context_weighs_a_request_by_its_decode_steps(sizes, want):
    from benchmarks.lib import flops

    assert flops.mean_decode_context(sizes) == pytest.approx(want)


def test_a_decoded_token_needs_two_operations_a_parameter_and_its_context():
    from benchmarks.lib import flops, flops_longcat

    m = {"d_model": 64, "d_ff": 128, "n_layer": 2, "n_head": 4,
         "n_kv_head": 2, "vocab_size": 512}
    base = flops.llama_decode_flops_per_token(m, 0)
    assert base == 2.0 * flops.llama_matmul_params(m)
    assert flops.llama_decode_flops_per_token(m, 100) - base == 4 * 2 * 100 * 64
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "longcat_flash_l4_ep32.json")) as f:
        lm = json.load(f)["model"]
    near, far = (flops_longcat.decode_flops_per_token(lm, c) for c in (0, 1000))
    assert near > 2.0 * lm["n_layer"] * flops_longcat.nonexpert_layer_params(lm)
    assert far - near == 2 * lm["n_layer"] * 2.0 * 1000 * lm["n_head"] * (
        2 * lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
