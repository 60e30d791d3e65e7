"""The serving window's arithmetic (``jobs/serve_stream.py``: ``lead_in_s``,
``reduce_window``, ``end_to_end``, ``percentile``) and the generator's
promise that every seed offers the same sizes in the same cyclic order
(``lib/traffic.py``): pure
functions on hand-built request logs.  CPU only; no sleeping, no cluster.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.jobs import serve_stream as ss  # noqa: E402
from benchmarks.lib import traffic  # noqa: E402

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
    assert e2e["ttft_p90_ms"] == ss.percentile(win["ttft_ms"], 90)
    assert e2e["itl_p95_ms"] == ss.percentile(win["itl_ms"], 95)
    empty = ss.end_to_end(window())
    assert empty == {"serve_tokens_per_s": 0.0, "ttft_p90_ms": None,
                     "itl_p95_ms": None}


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
