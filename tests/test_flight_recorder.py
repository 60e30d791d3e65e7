"""Runtime flight recorder: task-phase, collective, backpressure, and
object-store telemetry (built-in ``ray_tpu_*`` metrics + timeline phase
rows), plus the Prometheus exposition round trip.

Reference analogs: Podracer-style accelerator/utilization accounting
(arxiv 2104.06272) needs per-phase task timings; EQuARX-style collective
optimization (arxiv 2506.17615) needs per-op bytes/bandwidth capture.
"""

from __future__ import annotations

import asyncio
import re
import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import flight_recorder, metrics


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


# ------------------------------------------------------------------ helpers
def _timeline_events(min_phase_rows: int = 1, timeout: float = 30.0):
    """Chrome-trace events (what /api/timeline serves), polled until the
    executor-side flushes land."""
    from ray_tpu.util.state.api import StateApiClient, chrome_trace_events

    client = StateApiClient()
    deadline = time.time() + timeout
    events = []
    while time.time() < deadline:
        events = chrome_trace_events(client.list_task_events(limit=100000))
        rows = [
            e for e in events
            if e["cat"] == "profile" and (e["args"] or {}).get("phase")
        ]
        phases = {e["args"]["phase"] for e in rows}
        if len(rows) >= min_phase_rows and set(
            flight_recorder.TASK_PHASES
        ) <= phases:
            return events
        time.sleep(0.3)
    return events


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})? (?P<value>[^ ]+)$"
)
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')


def _parse_prometheus(text: str):
    """Strict-ish exposition parser: every line must be a valid TYPE
    comment or sample; returns (types, samples) where samples maps
    (name, labels_frozenset) -> float."""
    types = {}
    samples = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            assert parts[:2] == ["#", "TYPE"], f"bad comment line: {line!r}"
            assert len(parts) == 4, f"bad TYPE line: {line!r}"
            name, kind = parts[2], parts[3]
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        labels = m.group("labels") or ""
        label_items = []
        if labels:
            for pair in labels.split(","):
                assert _LABEL_RE.match(pair), f"bad label {pair!r} in {line!r}"
                k, v = pair.split("=", 1)
                label_items.append((k, v[1:-1]))
        value = float(m.group("value"))
        key = (m.group("name"), frozenset(label_items))
        assert key not in samples, f"duplicate sample {key}"
        samples[key] = value
    return types, samples


# ------------------------------------------------------------- task phases
class TestTaskPhases:
    def test_phase_rows_in_timeline(self, cluster):
        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(
            [f.remote(i) for i in range(5)], timeout=60
        ) == [1, 2, 3, 4, 5]
        events = _timeline_events(min_phase_rows=5 * 4)
        rows = [
            e for e in events
            if e["cat"] == "profile" and (e["args"] or {}).get("phase")
        ]
        phases = {e["args"]["phase"] for e in rows}
        assert set(flight_recorder.TASK_PHASES) <= phases, phases
        # Every phase row is a well-formed Chrome-trace 'X' slice tied to
        # a task.
        for e in rows:
            assert e["ph"] == "X"
            assert e["dur"] >= 0
            if e["args"]["phase"] in flight_recorder.TASK_PHASES:
                assert e["args"].get("task_id")
        # All 5 tasks produced an execute row.
        exec_tasks = {
            e["args"]["task_id"] for e in rows
            if e["args"]["phase"] == "execute" and e["args"].get("task")== "f"
        }
        assert len(exec_tasks) == 5

    def test_summarize_task_phases(self, cluster):
        from ray_tpu.util.state import summarize_task_phases

        @ray_tpu.remote
        def g():
            return 1

        assert ray_tpu.get([g.remote() for _ in range(3)], timeout=60)
        _timeline_events(min_phase_rows=3 * 4)
        summary = summarize_task_phases()
        for phase in flight_recorder.TASK_PHASES:
            assert phase in summary, summary.keys()
            row = summary[phase]
            assert row["count"] >= 3
            assert 0 <= row["p50_s"] <= row["p99_s"] <= row["max_s"]

    def test_phase_histogram_in_metrics(self, cluster):
        @ray_tpu.remote
        def h():
            return 1

        assert ray_tpu.get(h.remote(), timeout=60) == 1
        # The executing worker's registry flushes on its own period; the
        # driver-side merge must eventually show the phase histogram.
        deadline = time.time() + 30
        while time.time() < deadline:
            by_name = {
                v["name"]: v for v in metrics.snapshot().values()
            }
            ent = by_name.get(flight_recorder.TASK_PHASE_HIST)
            if ent is not None and ent["count"] >= 1:
                return
            time.sleep(0.5)
        pytest.fail("ray_tpu_task_phase_s never appeared in the merged view")


# -------------------------------------------------------------- prometheus
class TestPrometheusExposition:
    def test_histogram_buckets_roundtrip(self, cluster):
        h = metrics.Histogram("fr_test_lat_s", boundaries=[0.01, 0.1, 1.0])
        for v in (0.005, 0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        c = metrics.Counter("fr_test_total", tag_keys=("route",))
        c.inc(2.0, tags={"route": "/a"})
        c.inc(1.0, tags={"route": "/b"})
        metrics.Gauge("fr_test_inflight").set(7.0)
        text = metrics.prometheus_text()
        types, samples = _parse_prometheus(text)
        assert types["fr_test_lat_s"] == "histogram"
        assert types["fr_test_total"] == "counter"
        assert types["fr_test_inflight"] == "gauge"

        def bucket(le):
            return samples[("fr_test_lat_s_bucket", frozenset({("le", le)}))]

        # Cumulative and monotone, with the exact per-boundary counts.
        assert bucket("0.01") == 1
        assert bucket("0.1") == 3
        assert bucket("1.0") == 4
        assert bucket("+Inf") == 5
        assert samples[("fr_test_lat_s_count", frozenset())] == 5
        assert samples[("fr_test_lat_s_sum", frozenset())] == pytest.approx(
            5.605
        )

    def test_all_builtin_metrics_parse(self, cluster):
        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote(), timeout=60) == 1
        time.sleep(0.5)
        types, samples = _parse_prometheus(metrics.prometheus_text())
        # Bucket monotonicity for every histogram present.
        for name, kind in types.items():
            if kind != "histogram":
                continue
            by_tags = {}
            for (sname, labels), value in samples.items():
                if sname != name + "_bucket":
                    continue
                tags = dict(labels)
                le = tags.pop("le")
                by_tags.setdefault(frozenset(tags.items()), []).append(
                    (float("inf") if le == "+Inf" else float(le), value)
                )
            assert by_tags, f"histogram {name} emitted no buckets"
            for series in by_tags.values():
                series.sort()
                values = [v for _, v in series]
                assert values == sorted(values), f"{name} not cumulative"
                assert series[-1][0] == float("inf")


# ------------------------------------------------ collectives + scaling
class TestCollectiveTelemetry:
    def test_instrumented_group_records(self):
        import numpy as np

        class FakeGroup:
            world_size = 4

            def allreduce(self, tensors, op=None):
                return tensors

            def broadcast(self, tensors, src_rank=0):
                return tensors

        g = flight_recorder.instrument_group(FakeGroup(), "test")
        payload = [np.ones((256,), np.float32)] * 4
        g.allreduce(payload)
        g.broadcast(payload)
        with metrics._lock:
            local = dict(metrics._local)
        ops = {
            dict(tags)["op"]: ent["value"]
            for (name, tags), ent in local.items()
            if name == flight_recorder.COLLECTIVE_OPS_TOTAL
            and dict(tags).get("backend") == "test"
        }
        assert ops.get("allreduce", 0) >= 1
        assert ops.get("broadcast", 0) >= 1
        nbytes = {
            dict(tags)["op"]: ent["value"]
            for (name, tags), ent in local.items()
            if name == flight_recorder.COLLECTIVE_BYTES_TOTAL
            and dict(tags).get("backend") == "test"
        }
        assert nbytes["allreduce"] >= 4 * 256 * 4
        # Bandwidth histogram captured with world-size tagging.
        bw = [
            ent for (name, tags), ent in local.items()
            if name == flight_recorder.COLLECTIVE_BANDWIDTH_HIST
            and dict(tags).get("world_size") == "4"
        ]
        assert bw and all(e["count"] >= 1 for e in bw)

    def test_local_group_collectives_recorded(self):
        """End-to-end over the real LOCAL backend (8 virtual CPU devices)."""
        import numpy as np

        from ray_tpu.collective import collective_stats
        from ray_tpu.collective.local_group import LocalXlaGroup

        before = collective_stats().get("reducescatter", {}).get("ops", 0)
        g = LocalXlaGroup("fr-test")
        n = g.world_size
        out = g.reducescatter(
            [np.ones((n,), np.float32) for _ in range(n)]
        )
        assert float(np.asarray(out[0])[0]) == pytest.approx(n)
        stats = collective_stats()
        assert stats["reducescatter"]["ops"] == before + 1
        assert stats["reducescatter"]["bytes"] >= n * n * 4

    def test_scaling_efficiency_gauge(self):
        flight_recorder.record_scaling_efficiency(8, 0.93)
        with metrics._lock:
            ent = metrics._local.get(
                (flight_recorder.ICI_SCALING_EFFICIENCY,
                 (("devices", "8"),))
            )
        assert ent is not None and ent["value"] == pytest.approx(0.93)


# -------------------------------------------- backpressure + drop counting
class TestBackpressureTelemetry:
    def test_blocked_submission_records_wait(self):
        from ray_tpu.core.config import GlobalConfig
        from ray_tpu.core.core_worker import _SubmitBudget

        with metrics._lock:
            prev = metrics._local.get(
                (flight_recorder.BACKPRESSURE_WAIT_HIST, ())
            )
            prev_count = prev["count"] if prev else 0
        old = GlobalConfig.task_queue_memory_cap_bytes
        GlobalConfig.override(task_queue_memory_cap_bytes=1000)
        try:
            budget = _SubmitBudget()
            budget.charge(900, may_block=False)
            t = threading.Timer(0.15, budget.release, args=(900,))
            t.start()
            budget.charge(900, may_block=True)  # blocks until the release
            t.join()
        finally:
            GlobalConfig.override(task_queue_memory_cap_bytes=old)
        with metrics._lock:
            ent = metrics._local.get(
                (flight_recorder.BACKPRESSURE_WAIT_HIST, ())
            )
        assert ent is not None and ent["count"] == prev_count + 1
        # The recorded wait is roughly the 0.15 s the releaser imposed.
        assert ent["sum"] >= 0.1


class TestTaskEventDrops:
    def test_unreachable_control_plane_counts_drops(self):
        from ray_tpu.core.task_events import TaskEventBuffer

        class DeadCP:
            async def call(self, *a, **kw):
                raise ConnectionError("control plane unreachable")

        with metrics._lock:
            prev = metrics._local.get(
                (flight_recorder.TASK_EVENTS_DROPPED_TOTAL, ())
            )
            prev_total = prev["value"] if prev else 0
        buf = TaskEventBuffer(DeadCP(), "node", "worker")
        buf.record("t1", "f", "RUNNING")
        buf.record("t1", "f", "FINISHED")
        asyncio.run(buf.flush())
        assert buf.num_dropped == 2
        with metrics._lock:
            ent = metrics._local.get(
                (flight_recorder.TASK_EVENTS_DROPPED_TOTAL, ())
            )
        assert ent is not None and ent["value"] == prev_total + 2


# ----------------------------------------------------- flush on disconnect
class TestFinalFlush:
    def test_shutdown_flush_pushes_unflushed_window(self, cluster):
        """A fresh (not-yet-due) metrics window must survive worker exit:
        _flush_observability pushes it to the cluster KV immediately."""
        from ray_tpu.api import global_worker

        w = global_worker()
        # Make the periodic flush think it just ran, then record: the
        # sample now sits ONLY in the local registry (the lost-final-window
        # scenario for a short-lived worker).
        metrics.payload_snapshot()  # drain whatever came before
        metrics._last_flush = time.monotonic()
        metrics.Counter("fr_final_window_total").inc(3.0)
        key = f"worker:{w.worker_id.hex()}"
        stored = w.kv_get("metrics", key) or {}
        assert not any("fr_final_window_total" in k for k in stored)
        w._run_sync(w._flush_observability(), timeout=10)
        stored = w.kv_get("metrics", key) or {}
        assert any("fr_final_window_total" in k for k in stored)


# ------------------------------------------------------- overhead envelope
def measure_obs_overhead(n_calls=300, trials=3, n_warmup=30):
    """Task round-trip cost with the flight recorder ON vs OFF.

    Two fresh clusters (same shape) so the OFF run carries zero residue of
    the ON run's instrumentation; best-of-``trials`` per config because
    single-shot throughput on a shared 1-core box swings with scheduler
    noise.  Returns per-call seconds for each config and the overhead
    fraction.  The <5% guard is the acceptance bar for all flight-recorder
    instrumentation on the hot path."""

    def per_call_s(flight_recorder_on: bool) -> float:
        ray_tpu.init(
            num_cpus=1,
            _system_config={
                "enable_flight_recorder": flight_recorder_on,
                "prestart_workers": 2,
            },
        )
        try:
            @ray_tpu.remote
            def f():
                return b"ok"

            for _ in range(n_warmup):
                ray_tpu.get(f.remote(), timeout=60)
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    ray_tpu.get(f.remote(), timeout=60)
                best = min(best, (time.perf_counter() - t0) / n_calls)
            return best
        finally:
            ray_tpu.shutdown()

    t_on = per_call_s(True)
    t_off = per_call_s(False)
    return {
        "per_call_on_s": t_on,
        "per_call_off_s": t_off,
        "overhead_fraction": max(0.0, t_on / t_off - 1.0),
    }


@pytest.mark.slow
class TestObsOverheadEnvelope:
    def test_overhead_under_five_percent(self):
        best = float("inf")
        for _ in range(3):  # shared-box noise: keep the best measurement
            res = measure_obs_overhead(n_calls=200, trials=3)
            best = min(best, res["overhead_fraction"])
            if best < 0.05:
                break
        assert best < 0.05, f"flight recorder costs {best:.1%} on the hot path"
