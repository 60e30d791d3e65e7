"""The readers of the run's cluster trace (``lib/cluster_spans.py``,
``readers/cluster_span_stat.py``, ``readers/request_leg_ms.py``) on a
hand-built ``spans.jsonl`` and hand-built host spans: exact legs, the union of
overlapping intervals, the start tree's self times, how the run's file is
found, and ``None`` for a missing file, a file with holes and two clocks that
disagree.  CPU only; no cluster, no sleeping.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import cluster_spans as cs  # noqa: E402
from benchmarks.lib import host_spans as hs  # noqa: E402
from benchmarks.lib import trace_reduce as tr  # noqa: E402
from benchmarks.readers import cluster_span_stat, request_leg_ms  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# The wall clock is the trace's clock + OFFSET.  Three requests are admitted
# in the traced window (A, B, C), one before it (Z: in the file, not in the
# window).  Wall-clock seconds from T: every leg is a round number of ms.
T = 1_700_000_000.0
OFFSET_NS = 1_699_999_990_000_000_000  # the trace's zero is 10 s before T
PROXY, REPLICA, CONTROLLER, RANK0, RANK1 = "w-proxy", "w-rep", "w-ctl", "w0", "w1"


def ns(seconds_from_t: float) -> int:
    return int(round((T + seconds_from_t) * 1e9))


def row(name, start, end, trace_id, span_id, parent_id=None, worker=PROXY,
        **attributes):
    return {"name": name, "start": T + start, "end": T + end,
            "trace_id": trace_id, "span_id": span_id, "parent_id": parent_id,
            "worker_id": worker, "node_id": "n0", "attributes": attributes}


def request_rows(tid, t_in, ingress, queue, prefill, egress, chunks, writes,
                 tokens, deltas, replica_after=0.002):
    """One streamed request whose legs are given in seconds."""
    engine_in = t_in + ingress
    admitted = engine_in + queue
    first_token = admitted + prefill
    first_write = first_token + egress
    return [
        row("serve.http.stream", t_in, first_write + 1.0, tid, tid + "-h",
            route_ms=1.0, chunks=chunks, writes=writes,
            first_write_unix_ns=ns(first_write),
            last_write_unix_ns=ns(first_write + 0.9)),
        row("task:handle_request_streaming", t_in + 0.001, first_write + 0.95,
            tid, tid + "-t", tid + "-h", worker=REPLICA),
        row("serve.request.stream", t_in + replica_after, first_write + 0.95,
            tid, tid + "-r", tid + "-t", worker=REPLICA, sem_wait_ms=0.5,
            ttft_s=first_token - t_in - replica_after, chunks=chunks),
        row("engine.stream", engine_in, first_write + 0.9, tid, tid + "-e",
            tid + "-t", worker=REPLICA, request_id=1,
            admitted_unix_ns=ns(admitted),
            first_token_unix_ns=ns(first_token), deltas=deltas,
            tokens=tokens),
    ]


START = [
    # serve.run returns at 0.5 s; its replica is up at 24 s.
    row("serve.run", 0.0, 0.5, "S", "run"),
    row("task:deploy", 0.1, 0.4, "S", "dep", "run", worker=CONTROLLER),
    row("serve.replica.spawn", 0.2, 24.0, "S", "spawn", "dep",
        worker=CONTROLLER),
    row("serve.replica.init", 3.2, 23.0, "S", "init", "spawn",
        worker=REPLICA),
    row("llm.engine.build", 4.0, 22.0, "S", "build", "init", worker=REPLICA),
    row("llm.engine.weights", 4.0, 6.0, "S", "wts", "build", worker=REPLICA),
    row("llm.engine.compile", 6.0, 16.0, "S", "c0", "build", worker=REPLICA,
        program="prefill_one", rung=256),
    row("llm.engine.compile", 6.0, 20.0, "S", "c1", "build", worker=REPLICA,
        program="decode_step"),
    # Compilations of the replica's process: two overlap (pool threads), one
    # later under the process's own trace; one in another process.
    row("xla.compile", 7.0, 15.0, "S", "x0", "c0", worker=REPLICA,
        event="/jax/core/compile/backend_compile_duration"),
    row("xla.compile", 9.0, 19.0, "S", "x1", "c1", worker=REPLICA,
        event="/jax/core/compile/backend_compile_duration"),
    row("xla.compile", 9.5, 10.0, "S", "x2", "c1", worker=REPLICA,
        event="/jax/compilation_cache/cache_retrieval_time_sec"),
    row("xla.compile", 30.0, 30.5, "proc:w-rep", "x3", None, worker=REPLICA,
        event="/jax/core/compile/backend_compile_duration",
        fun_name="jit(sample_logits)"),
    # After the traced window (A is admitted at 50.021): the benchmark's
    # reference check, which is no set-up.
    row("xla.compile", 60.0, 64.0, "proc:w-rep", "x5", None, worker=REPLICA,
        event="/jax/core/compile/backend_compile_duration",
        fun_name="jit(reference)"),
    row("xla.compile", 1.0, 2.0, "proc:w-ctl", "x4", None, worker=CONTROLLER,
        event="/jax/core/compile/backend_compile_duration"),
]
GANG = [
    row("train.fit", 100.0, 190.0, "G", "fit", attempt=0),
    row("train.placement", 100.1, 100.6, "G", "pg", "fit", bundles=2),
    row("train.backend", 103.5, 125.5, "G", "be", "fit"),
    row("train.worker.jax_init", 103.7, 114.0, "G", "j0", "be", worker=RANK0,
        rank=0),
    row("train.worker.jax_init", 104.0, 125.2, "G", "j1", "be", worker=RANK1,
        rank=1, import_s=2.5, initialize_s=0.2, runtime_s=18.5),
    row("train.worker.loop", 125.6, 125.6, "G", "l0", "fit", worker=RANK0,
        rank=0),
    row("train.worker.loop", 125.6, 125.6, "G", "l1", "fit", worker=RANK1,
        rank=1),
    row("xla.compile", 130.0, 140.0, "proc:w0", "y0", None, worker=RANK0,
        event="/jax/core/compile/backend_compile_duration"),
    row("xla.compile", 138.0, 141.0, "proc:w0", "y1", None, worker=RANK0,
        event="/jax/core/compile/backend_compile_duration"),
    row("xla.compile", 130.0, 170.0, "proc:w1", "y2", None, worker=RANK1,
        event="/jax/core/compile/backend_compile_duration"),
]
REQUESTS = (
    # tid, in, ingress, queue, prefill, egress, chunks, writes, tokens, deltas
    request_rows("Z", 40.000, 0.020, 0.010, 0.015, 0.004, 9, 9, 9, 9)
    + request_rows("A", 50.000, 0.010, 0.011, 0.013, 0.002, 10, 10, 10, 10)
    + request_rows("B", 50.500, 0.012, 0.009, 0.014, 0.003, 12, 8, 12, 12)
    + request_rows("C", 51.000, 0.030, 0.002, 0.016, 0.007, 8, 6, 8, 4))


def spans_file(dropped=0, rows=START + GANG + REQUESTS):
    return cs.from_rows([{"session": "s1", "dropped_spans": dropped,
                          "spans": len(rows)}, *rows])


def admit(tid, admitted_from_t, skew_ns=0, request_id=1):
    """An ``engine.admit`` host span on the trace's clock, with its anchor."""
    unix = ns(admitted_from_t)
    return ["engine.admit", unix - OFFSET_NS - skew_ns, 12_000_000,
            {"request_id": request_id, "slot": 0, "prompt_len": 30,
             "padded_len": 256, "queue_wait_ms": 1.0, "unix_ns": unix,
             **({"trace_id": tid} if tid else {})}]


def host(skews=(0, 300_000, -200_000)):
    """The traced window's host spans: A, B and C are admitted in it."""
    device = {"/device:TPU:0": {
        tr.OPS_LINE: [["fusion.1", ns(49.0) - OFFSET_NS, 1_000_000]],
        tr.MODULES_LINE: []}}
    events = [admit("A", 50.021, skews[0]), admit("B", 50.521, skews[1]),
              admit("C", 51.032, skews[2])]
    return hs.from_planes([events], device)


def ctx_of(trace, *files):
    return types.SimpleNamespace(cluster_spans=trace, host_spans=list(files),
                                 trace=object(), config={}, mix={}, stats={})


def spec(metric):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def read_metric(metric, ctx):
    s = spec(metric)
    reader = {"cluster_span_stat": cluster_span_stat,
              "request_leg_ms": request_leg_ms}[s["reader"]]
    return reader.read(ctx, **s["args"])


# --------------------------------------------------------------- the clocks
def test_the_offset_is_the_median_reading_and_the_spread_its_quartiles():
    relation = cs.clock(cs.admissions(ctx_of(spans_file(), host())))
    assert relation.readings == 3
    assert relation.offset_ns == OFFSET_NS  # the median skew is 0
    assert relation.range_ms == pytest.approx(0.5)
    # statistics.quantiles(n=4) of (-0.3, 0, 0.2) ms: Q1 = -0.3, Q3 = 0.2.
    assert relation.spread_ms == pytest.approx(0.5)
    assert relation.good
    assert relation.on_trace_ns(T + 50.0) == pytest.approx(
        ns(50.0) - OFFSET_NS, abs=500)


def test_one_admission_is_a_relation_with_no_spread():
    one = hs.from_planes([[admit("A", 50.021)]], {})
    relation = cs.clock(cs.admissions(ctx_of(spans_file(), one)))
    assert (relation.readings, relation.spread_ms) == (1, 0.0)


def test_an_admission_without_its_anchor_is_no_reading():
    old = admit("A", 50.021)
    del old[3]["unix_ns"]  # the parent commit's program
    ctx = ctx_of(spans_file(), hs.from_planes([[old]], {}))
    assert cs.clock(cs.admissions(ctx)) is None
    assert cs.window_trace_ids(ctx) is None
    assert read_metric("ingress_ms.serve_median", ctx) is None
    assert read_metric("chunks_per_write.serve", ctx) is None


# ---------------------------------------------------------------- the legs
@pytest.mark.parametrize("metric, want", [
    # Medians over A, B, C; Z was admitted before the window.
    ("ingress_ms.serve_median", 12.0),        # 10, 12, 30
    ("egress_first_ms.serve_median", 3.0),    # 2, 3, 7
    ("chunks_per_write.serve", 30 / 24),      # (10 + 12 + 8) / (10 + 8 + 6)
    ("tokens_per_delta.serve", 30 / 26),      # (10 + 12 + 8) / (10 + 12 + 4)
])
def test_a_request_path_metric_reads_the_windows_requests(metric, want):
    assert read_metric(metric, ctx_of(spans_file(), host())) == (
        pytest.approx(want, abs=1e-3))


def test_every_leg_of_a_request_by_hand():
    [a, b, c] = cs.window_requests(ctx_of(spans_file(), host()))
    assert [r.trace_id for r in (a, b, c)] == ["A", "B", "C"]
    legs = c.legs_ms()
    assert legs == pytest.approx({
        "ingress": 30.0, "queue": 2.0, "prefill": 16.0, "egress_first": 7.0,
        "proxy_ttft": 55.0, "proxy_to_replica": 2.0,
        "replica_to_engine": 28.0, "first_to_last_write": 900.0,
        "egress_last": 0.0}, abs=1e-3)
    # The legs add up to the proxy's own time to its first write.
    assert sum(legs[k] for k in ("ingress", "queue", "prefill",
                                 "egress_first")) == pytest.approx(
        legs["proxy_ttft"], abs=1e-3)
    for leg, want in (("queue", 9.0), ("prefill", 14.0),
                      ("proxy_to_replica", 2.0), ("replica_to_engine", 10.0)):
        assert request_leg_ms.read(
            ctx_of(spans_file(), host()), leg) == pytest.approx(want, abs=1e-3)


def test_a_request_with_one_end_missing_is_left_out():
    rows = [r for r in START + REQUESTS
            if not (r["trace_id"] == "B" and r["name"] == "engine.stream")]
    ctx = ctx_of(spans_file(rows=rows), host())
    assert [r.trace_id for r in cs.window_requests(ctx)] == ["A", "C"]
    assert read_metric("ingress_ms.serve_median", ctx) == pytest.approx(20.0)
    # chunks / writes needs the proxy's span alone: B still counts.
    assert read_metric("chunks_per_write.serve", ctx) == pytest.approx(30 / 24)


@pytest.mark.parametrize("ctx", [
    pytest.param(lambda: ctx_of(spans_file(dropped=3), host()),
                 id="the_file_has_holes"),
    pytest.param(lambda: ctx_of(spans_file(), host(
        skews=(0, 2_500_000, -2_500_000))), id="the_clocks_disagree"),
    pytest.param(lambda: ctx_of(spans_file(), hs.from_planes([], {})),
                 id="no_admission_in_the_window"),
    pytest.param(lambda: ctx_of(spans_file(rows=START), host()),
                 id="no_request_in_the_file"),
])
def test_nothing_to_read_is_none_for_every_request_path_metric(ctx):
    for metric in ("ingress_ms.serve_median", "egress_first_ms.serve_median",
                   "chunks_per_write.serve", "tokens_per_delta.serve"):
        assert read_metric(metric, ctx()) is None, metric


def test_a_clock_spread_over_the_limit_is_reported_and_refused():
    ctx = ctx_of(spans_file(), host(skews=(0, 2_500_000, -2_500_000)))
    relation = cs.clock(cs.admissions(ctx))
    assert relation.spread_ms == pytest.approx(5.0)
    assert relation.spread_ms > cs.CLOCK_LIMIT_MS
    assert not relation.good and cs.window_trace_ids(ctx) is None
    # The starts need no clock relation: they are read all the same.
    assert read_metric("engine_build_s.serve", ctx) == pytest.approx(18.0)


# -------------------------------------------------------------- the starts
@pytest.mark.parametrize("metric, want", [
    ("replica_spawn_s.serve", 3.2),   # serve.run 0.0 -> replica.init 3.2
    ("engine_build_s.serve", 18.0),
    ("gang_spawn_s.train", 25.6),     # train.fit 100.0 -> rank 0's loop 125.6
    ("gang_spawn_s.train_x4", 3.7),   # train.fit 100.0 -> first jax_init 103.7
    ("gang_jax_init_s.train_x4", 21.5),  # 103.7 -> 125.2
    ("xla_compile_s.train", 11.0),    # rank 0: [130, 140] u [138, 141]
])
def test_a_start_metric_reads_its_spans(metric, want):
    no_trace = ctx_of(spans_file())  # the starts need no traced window
    assert read_metric(metric, no_trace) == pytest.approx(want)
    assert read_metric(metric, ctx_of(spans_file(dropped=1))) is None


def test_a_replicas_compilations_are_set_up_until_the_window_opens():
    # The replica's process before A's admission at 50.021: [7, 15] u [9, 19]
    # u [9.5, 10] u [30, 30.5].  The controller's compilation is another
    # process's; the reference check's [60, 64] came after the window.
    ctx = ctx_of(spans_file(), host())
    assert cs.first_admission(ctx) == pytest.approx(T + 50.021)
    assert read_metric("xla_compile_s.serve", ctx) == pytest.approx(12.5)
    whole = cluster_span_stat.read(
        ctx, "xla.compile", "union", process_of={"name": "llm.engine.build"})
    assert whole == pytest.approx(16.5)
    # No anchored admission (no traced window, the parent's engine): the
    # window's opening is unknown, and so is what was set-up.
    assert read_metric("xla_compile_s.serve", ctx_of(spans_file())) is None
    assert read_metric("xla_compile_s.serve", ctx_of(
        spans_file(dropped=1), host())) is None


@pytest.mark.parametrize("intervals, want", [
    ([], 0.0),
    ([(1.0, 2.0)], 1.0),
    ([(1.0, 3.0), (2.0, 5.0)], 4.0),                 # overlapping
    ([(1.0, 9.0), (2.0, 3.0), (4.0, 5.0)], 8.0),     # nested
    ([(4.0, 5.0), (1.0, 2.0), (2.0, 3.0)], 3.0),     # touching, unsorted
    ([(1.0, 2.0), (5.0, 7.0), (6.0, 6.5)], 3.0),     # apart
])
def test_the_union_of_intervals(intervals, want):
    assert cs.union_s(intervals) == pytest.approx(want)


def test_self_time_is_a_span_less_what_its_children_cover():
    trace = spans_file()
    by_id = {r.span_id: r for r in trace.rows}
    # build 4..22: weights 4..6, compiles 6..16 and 6..20 -> 16 of 18 covered.
    # (Seconds near 1.7e9 hold a float to a quarter of a microsecond.)
    assert cs.self_s(trace, by_id["build"]) == pytest.approx(2.0, abs=1e-5)
    # serve.run 0..0.5 holds task:deploy 0.1..0.4.
    assert cs.self_s(trace, by_id["run"]) == pytest.approx(0.2, abs=1e-5)
    # A child that outlasts its parent covers it to its end, no further.
    assert cs.self_s(trace, by_id["dep"]) == pytest.approx(0.1, abs=1e-5)
    assert cs.self_s(trace, by_id["fit"]) == pytest.approx(
        90.0 - (0.5 + 22.0), abs=1e-5)  # zero-length marks cover nothing


def test_a_process_is_picked_by_what_it_did():
    trace = spans_file()
    assert cs.process_of(trace, "llm.engine.build") == REPLICA
    assert cs.process_of(trace, "train.worker.loop", {"rank": 1}) == RANK1
    assert cs.process_of(trace, "train.worker.loop", {"rank": 7}) is None
    assert cluster_span_stat.read(
        ctx_of(trace), "xla.compile", "union",
        process_of={"name": "train.worker.loop", "where": {"rank": 1}}) == (
        pytest.approx(40.0))


def test_the_other_arguments_of_the_reader():
    ctx = ctx_of(spans_file(), host())
    read = cluster_span_stat.read
    assert read(ctx, "llm.engine.compile", "max") == pytest.approx(14.0)
    assert read(ctx, "serve.http.stream", "ratio", attr="chunks",
                over="writes") == pytest.approx(39 / 33)
    assert read(ctx, "serve.http.stream", "ratio", attr="chunks",
                over="writes", window=True) == pytest.approx(30 / 24)
    assert read(ctx, "train.fit", "until", until="train.placement",
                edge="end") == pytest.approx(0.6)
    assert read(ctx, "no.such.span", "max") is None
    assert read(ctx, "serve.run", "until", until="no.such.span") is None
    with pytest.raises(ValueError):
        read(ctx, "serve.run", "mean")


# ------------------------------------------------------- finding the file
def write_file(folder, trace_rows, dropped=0):
    os.makedirs(folder)
    path = os.path.join(folder, cs.FILE_NAME)
    with open(path, "w") as f:
        for r in ({"session": os.path.basename(folder)[len("session_"):],
                   "dropped_spans": dropped, "spans": len(trace_rows)},
                  *trace_rows):
            f.write(json.dumps(r) + "\n")
    return path


def test_the_runs_file_is_the_one_written_after_its_outputs_were_made(
        tmp_path, monkeypatch):
    monkeypatch.setattr(cs, "ROOT", str(tmp_path))
    monkeypatch.setattr(cs.tempfile, "gettempdir", lambda: str(tmp_path / "t"))
    sessions = tmp_path / "t" / "ray_tpu"
    assert cs.find("cell") is None  # no outputs, no run
    out_dir = tmp_path / ".bench_out" / "cell"
    (out_dir / "trace").mkdir(parents=True)
    (out_dir / "trace" / "a.xplane.pb").write_bytes(b"")
    assert cs.find("cell") is None  # the parent's program: nothing written

    def at(path, when):
        os.utime(path, (when, when))

    before = write_file(str(sessions / "session_old"), START)
    mine = write_file(str(sessions / "session_mine"), START + REQUESTS)
    # The outputs: made at 1000 (the trace inside at 1030, its summary and so
    # the directory itself at 1050); sessions written at 900 and 1040.
    at(out_dir / "trace" / "a.xplane.pb", 1030)
    at(out_dir / "trace", 1030)
    at(out_dir, 1050)
    for path, when in ((before, 900), (mine, 1040)):
        at(path, when)
    assert cs.made_at(str(out_dir)) == 1030
    assert cs.find("cell") == mine
    assert cs.parse(cs.find("cell")).session == "mine"
    # Another driver shut down in the same temporary directory meanwhile:
    # nothing says whose is whose, so it is nobody's.
    other = write_file(str(sessions / "session_other"), GANG)
    at(other, 1045)
    assert cs.find("cell") is None
    at(mine, 1000)  # written before anything of this run: not this run's
    assert cs.find("cell") == other
    # A session that started nothing (no ``serve.run``, no ``train.fit``)
    # is not a run of a cell.
    at(other, 900)
    idle = write_file(str(sessions / "session_idle"), REQUESTS)
    at(idle, 1040)
    assert cs.find("cell") is None


def test_a_context_finds_its_cells_file_and_a_given_one_is_taken_at_its_word(
        tmp_path, monkeypatch):
    cell = BENCH["workloads"][1]
    monkeypatch.setattr(cs, "ROOT", str(tmp_path))
    monkeypatch.setattr(cs.tempfile, "gettempdir", lambda: str(tmp_path / "t"))
    (tmp_path / ".bench_out" / cell["name"]).mkdir(parents=True)
    ctx = types.SimpleNamespace(
        config={"name": cell["config"]}, mix={"name": cell["traffic"]},
        trace=None, stats={})
    assert cs.for_ctx(ctx) is None  # no file: the parent commit
    assert read_metric("engine_build_s.serve", ctx) is None
    path = write_file(str(tmp_path / "t" / "ray_tpu" / "session_x"), START)
    future = os.path.getmtime(tmp_path / ".bench_out" / cell["name"]) + 5
    os.utime(path, (future, future))
    assert cs.for_ctx(ctx).session == "x"
    assert read_metric("engine_build_s.serve", ctx) == pytest.approx(18.0)
    # No traced window (ctx.trace is None): no request-path metric.
    assert read_metric("chunks_per_write.serve", ctx) is None
    write_file(str(tmp_path / "t" / "ray_tpu" / "session_y"), START, dropped=2)
    os.utime(os.path.join(str(tmp_path / "t" / "ray_tpu" / "session_y"),
                          cs.FILE_NAME), (future - 1, future - 1))
    assert cs.for_ctx(ctx) is None  # the run's file has holes


# -------------------------------------------------------------- the report
def test_the_report_prints_the_trees_the_ranks_and_the_legs(tmp_path):
    path = write_file(str(tmp_path / "session_r"), START + GANG + REQUESTS)
    text = cs.report(path)
    assert "session r, " in text and "dropped 0" in text
    assert "serve.run +0.000s 0.500s self 0.200s" in text
    assert "      llm.engine.build +4.000s 18.000s self 2.000s" in text
    assert '"program": "prefill_one", "rung": 256' in text
    assert "train.fit +0.000s 90.000s" in text
    assert "rank 0: +0.000s .. +10.300s (10.300s)\n" in text
    assert ("rank 1: +0.300s .. +21.500s (21.200s) import_s 2.500s "
            "initialize_s 0.200s runtime_s 18.500s") in text
    assert "they overlap" in text
    assert "replica w-rep: union 16.500s; " in text  # no traced window here
    assert "    4.000s +53.000s backend_compile_duration jit(reference)" in text
    assert "cache_retrieval_time_sec x1 sum 0.500s" in text
    legs = "\n".join(cs.request_lines(spans_file(), [host()]))
    assert "spread 0.500 ms" in legs and "good" in legs
    assert "3 of 3 admitted requests have both ends" in legs
    assert "leg ingress (ms): n=3 min=10.000 median=12.000 max=30.000" in legs
    assert "leg egress_first (ms): n=3 min=2.000 median=3.000" in legs
    assert " | 8/6 8/4" in legs and "\n  1: " in legs  # its request_id
    assert "leg egress_last (ms): n=3 min=0.000" in legs
    assert "leg first_to_last_write (ms): n=3 min=900.000" in legs
    compiled = "\n".join(cs.compile_lines(
        spans_file(), cs.first_admission(ctx_of(spans_file(), host()))))
    assert ("replica w-rep: union 16.500s (4.000s of it after the traced "
            "window opened); ") in compiled
    assert "route_ms (proxy: entry -> handle.remote returned): n=3" in legs
    assert "sem_wait_ms (replica: the user semaphore): n=3 min=0.500" in legs


def test_the_prefill_leg_against_the_devices_record():
    """Admitted -> first token, split by the device's own runs of the prefill
    program: waiting behind what was queued, running, handing over."""
    ctx = ctx_of(spans_file(), host())
    requests = cs.window_requests(ctx)
    relation = cs.clock(cs.admissions(ctx))

    def run(start_from_t, ms):
        return ["jit_prefill_one(7)", ns(start_from_t) - OFFSET_NS,
                int(ms * 1e6)]

    device = {"/device:TPU:0": {
        tr.OPS_LINE: [["fusion.1", ns(49.0) - OFFSET_NS, 1_000_000]],
        tr.MODULES_LINE: [
            run(49.9, 5),  # before the window's first admission: nobody's
            run(50.026, 6),  # A: admitted 50.021, first token 50.034
            run(50.523, 10),  # B: admitted 50.521, first token 50.535
            ["jit__lambda(3)", ns(50.9) - OFFSET_NS, 15_000_000],
            run(51.040, 7)]}}  # C: admitted 51.032, first token 51.048
    [chip] = hs.from_planes([], device).chips
    split = cs.device_split_ms(requests, relation, [chip])
    want = {"behind the device's queue": [5.0, 2.0, 8.0],
            "the program's run": [6.0, 10.0, 7.0],
            "its end -> handed over": [2.0, 2.0, 1.0]}
    assert set(split) == set(want)
    for name, values in want.items():
        assert split[name] == pytest.approx(values, abs=1e-3), name
    assert cs.device_split_ms(requests, relation, []) == {}


def test_a_span_whose_parent_is_not_in_the_file_is_shown_under_its_root(
        tmp_path):
    rows = [dict(r, parent_id="run-gone") if r["span_id"] == "l0" else r
            for r in GANG]
    text = cs.report(write_file(str(tmp_path / "session_o"), rows))
    assert ('  train.worker.loop +25.600s 0.000s self 0.000s {"rank": 0} '
            "(its parent is not in the file)") in text
    assert text.count("(its parent is not in the file)") == 1


def test_the_new_entries_name_layers_sources_and_cells_as_the_issue_does():
    new = {m["name"]: m for m in BENCH["per_layer"]
           if os.path.exists(os.path.join(
               ROOT, "benchmarks", "layer_metrics", m["name"] + ".json"))
           and spec(m["name"])["reader"] in ("cluster_span_stat",
                                             "request_leg_ms")}
    assert len(new) == 11
    assert {m["layer"] for m in new.values()} == {
        "proxy and handle", "replica start", "gang start", "compile cache"}
    assert {m["source"] for m in new.values()} <= {
        "program_span", "program_counter"}
    serving = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1
               and w["name"] not in ("gpt2m_dp_1chip",)]
    assert new["chunks_per_write.serve"]["workloads"] == serving
    assert new["ingress_ms.serve_median"]["moves"] == "ttft_p50_ms"
    assert new["gang_spawn_s.train"]["moves"] == "gang_ready_s"
    for name, metric in new.items():
        assert spec(name)["what"], name  # says which span it reads
