"""Streaming data-plane scheduler tests (`ray_tpu/data/streaming.py`):
out-of-order streaming, operator autoscaling, dynamic block shaping,
early-exit cancellation, plan-rule stability, and raylint cleanliness.
Reference test model: ray ``python/ray/data/tests/test_streaming_executor*``.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata
from ray_tpu.core.config import GlobalConfig


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=8)
    yield ctx
    ray_tpu.shutdown()


def _wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _data_straggler_walls(rd, n_blocks=10, straggler_s=1.8, per_block_s=0.18):
    """Ordered-vs-unordered wall time on a straggler-skewed pipeline.

    One slow map task at the head of the stream feeds a consumer that
    does fixed work per block (a simulated train step — ingest on the
    step's critical path, the JaxTrainer scenario).  Ordered emission
    parks the consumer until the straggler lands (wall ~= straggler +
    n*per_block); unordered keeps it fed (wall ~= max(straggler,
    n*per_block) + per_block).  Returns both walls and checks the result
    SETS are identical — the out-of-order win must never change the
    answer.
    """

    def skew_map(x):
        time.sleep(straggler_s if x == 0 else 0.01)
        return x

    def run(preserve_order):
        ds = (
            rd.from_items(list(range(n_blocks)), parallelism=n_blocks)
            .map(skew_map)
            .execution_options(preserve_order=preserve_order)
        )
        got = []
        t0 = time.perf_counter()
        for block in ds.iter_blocks():
            time.sleep(per_block_s)  # simulated per-batch train step
            got.extend(block)
        return time.perf_counter() - t0, sorted(got)

    walls = {}
    for label, preserve in (("unordered", False), ("ordered", True)):
        samples = []
        for _ in range(2):
            dt, got = run(preserve)
            assert got == list(range(n_blocks)), got
            samples.append(dt)
        walls[label] = min(samples)
    return walls


class TestOutOfOrder:
    def test_unordered_set_completeness_under_skew(self, cluster):
        """Injected per-task latency skew: unordered emission must still
        deliver exactly the full result set."""
        ds = (
            rdata.from_items(list(range(8)), parallelism=8)
            .map(lambda x: (time.sleep(0.3 if x == 0 else 0.01), x * 2)[1])
            .execution_options(preserve_order=False)
        )
        out = ds.take_all()
        assert sorted(out) == [x * 2 for x in range(8)]

    def test_ordered_mode_default_and_deterministic(self, cluster):
        """preserve_order defaults ON: same skew, emission order is the
        plan order, twice in a row."""
        ds = rdata.from_items(list(range(8)), parallelism=8).map(
            lambda x: (time.sleep(0.3 if x == 0 else 0.01), x * 2)[1]
        )
        assert ds.take_all() == [x * 2 for x in range(8)]
        assert ds.take_all() == [x * 2 for x in range(8)]

    def test_unordered_streams_ahead_of_straggler(self, cluster):
        """The blocks behind fast tasks must arrive BEFORE the straggler
        completes (out-of-order delivery, not just eventual totality)."""
        def skew(x):
            time.sleep(1.0 if x == 0 else 0.01)
            return x

        ds = (
            rdata.from_items(list(range(6)), parallelism=6)
            .map(skew)
            .execution_options(preserve_order=False)
        )
        t0 = time.perf_counter()
        first = next(iter(ds.iter_blocks()))
        dt = time.perf_counter() - t0
        assert first != [0]  # a fast block came first...
        assert dt < 0.9  # ...and before the straggler's 1s sleep

    @pytest.mark.slow
    def test_unordered_beats_ordered_on_straggler_skew(self, cluster):
        """Unordered >= 1.5x faster wall time than ordered on the
        straggler-skew pipeline, identical result sets (set equality is
        asserted inside the helper)."""
        walls = _data_straggler_walls(rdata)
        speedup = walls["ordered"] / walls["unordered"]
        assert speedup >= 1.5, walls


class TestAutoscale:
    def test_pool_scales_up_then_down(self, cluster):
        """Bursty input: a burst of fast-arriving blocks drives the pool
        to max_size; the trailing trickle starves it back to min_size.
        Both transitions asserted from the recorded timeline and visible
        as flight-recorder metrics."""
        GlobalConfig.override(
            data_autoscale_interval_s=0.05,
            data_autoscale_idle_s=0.25,
            data_max_tasks_per_op=2,
        )
        try:
            def paced(x):
                # Blocks 0-15 arrive as a burst; 16-23 trickle in slowly;
                # the final block holds the stream open for a 2.5 s quiet
                # window.  On a loaded machine the pool can still be
                # draining the burst through the whole trickle phase, so
                # only the quiet tail GUARANTEES a starvation window
                # (pool idle, input empty) long past data_autoscale_idle_s
                # in which downscaling must engage.
                if x < 16:
                    time.sleep(0.01)
                elif x < 24:
                    time.sleep(0.8)
                else:
                    time.sleep(2.5)
                return x

            def pool_fn(b):
                time.sleep(0.2)
                return b

            ds = (
                rdata.from_items(list(range(25)), parallelism=25)
                .map(paced)
                .map_batches(
                    pool_fn,
                    compute=rdata.ActorPoolStrategy(min_size=1, max_size=3),
                )
            )
            out = ds.take_all()
            assert sorted(out) == list(range(25))
            st = ds._last_stats[-1]
            assert st.name == "MapBatches"
            timeline = st.pool_size_timeline
            assert st.pool_size_peak == 3, timeline
            assert st.autoscale_up_events >= 2
            assert st.autoscale_down_events >= 1
            # Returned to min_size (1) after the peak, BEFORE teardown's 0.
            after_peak = timeline[timeline.index(3):]
            assert 1 in after_peak, timeline
            assert timeline[-1] == 0  # pool torn down at operator finish
            # Flight-recorder visibility.
            from ray_tpu.util import metrics

            snap = metrics.snapshot()
            assert any(
                k.startswith("ray_tpu_data_autoscale_events_total") for k in snap
            )
            assert any(
                k.startswith("ray_tpu_data_pool_size") for k in snap
            )
        finally:
            GlobalConfig.override(
                data_autoscale_interval_s=0.1,
                data_autoscale_idle_s=0.5,
                data_max_tasks_per_op=8,
            )

    def test_fixed_pool_unchanged(self, cluster):
        """Plain size= pins both bounds: no autoscale events ever."""
        ds = rdata.range_dataset(12, parallelism=6).map_batches(
            lambda b: [x + 1 for x in b],
            compute=rdata.ActorPoolStrategy(size=2),
        )
        assert sorted(ds.take_all()) == list(range(1, 13))
        st = ds._last_stats[-1]
        assert st.autoscale_up_events == 0
        assert st.autoscale_down_events == 0
        assert st.pool_size_peak == 2


class TestBlockShaping:
    def test_coalesce_row_exact_across_exchange(self, cluster):
        """Many undersized blocks coalesce before the exchange; every
        row survives."""
        ds = rdata.read_numpy({"x": np.arange(4000)}, parallelism=8)
        shaped = ds.execution_options(
            target_block_size_bytes=512 * 1024
        ).repartition(3)
        got = sorted(r["x"] for r in shaped.take_all())
        assert got == list(range(4000))
        shape_st = [s for s in shaped._last_stats if s.name == "ShapeBlocks"]
        assert shape_st and shape_st[0].blocks_coalesced >= 2

    def test_split_row_exact_across_exchange(self, cluster):
        """Oversized blocks split before the exchange; row-exact."""
        ds = rdata.read_numpy({"x": np.arange(60_000)}, parallelism=2)
        shaped = ds.execution_options(
            target_block_size_bytes=64 * 1024
        ).repartition(4)
        got = sorted(r["x"] for r in shaped.take_all())
        assert got == list(range(60_000))
        shape_st = [s for s in shaped._last_stats if s.name == "ShapeBlocks"]
        assert shape_st and shape_st[0].blocks_split >= 1

    def test_shaping_off_by_default(self, cluster):
        ds = rdata.range_dataset(100, parallelism=4).repartition(2)
        m = ds.materialize()
        assert m.num_blocks() == 2
        assert not any(
            s.name == "ShapeBlocks" for s in ds._last_stats
        )


class TestPlanRulesUnchanged:
    """The optimizer rewrites are untouched by the scheduler swap."""

    def test_fusion_single_stage(self, cluster):
        ds = (
            rdata.range_dataset(20, parallelism=2)
            .map(lambda x: x + 1)
            .filter(lambda x: x % 2 == 0)
            .map(lambda x: x * 10)
        )
        assert sorted(ds.take_all()) == [
            x * 10 for x in range(1, 21) if x % 2 == 0
        ]
        # Read + three narrow ops fused into ONE executed operator.
        assert len(ds._last_stats) == 1
        assert ds._last_stats[0].num_tasks == 2

    def test_repartition_elision(self, cluster):
        ds = rdata.range_dataset(60, parallelism=3).repartition(5).repartition(2)
        m = ds.materialize()
        assert m.num_blocks() == 2
        assert sorted(m.take_all()) == list(range(60))
        # Only ONE exchange executed (the later repartition wins).
        assert sum(
            1 for s in ds._last_stats if s.name == "Repartition"
        ) == 1

    def test_parquet_pushdown(self, cluster, tmp_path):
        rows = [{"a": i, "b": float(i)} for i in range(50)]
        rdata.from_items(rows, parallelism=2).write_parquet(
            str(tmp_path / "pq")
        )
        ds = rdata.read_parquet(str(tmp_path / "pq")).filter(
            predicate=("a", "<", 10)
        ).select_columns(["a"])
        out = sorted(r["a"] for r in ds.take_all())
        assert out == list(range(10))

    def test_map_fuses_into_shuffle_map_phase(self, cluster):
        ds = rdata.range_dataset(8, parallelism=2).map(
            lambda x: x + 1
        ).random_shuffle(seed=7)
        assert sorted(ds.take_all()) == list(range(1, 9))
        assert sorted(ds.take_all()) == list(range(1, 9))  # no re-mutation


class TestEarlyExitCancellation:
    def test_limit_cancels_inflight_upstream(self, cluster):
        """limit(n) satisfied -> the still-in-flight upstream refs are
        cancelled, observable in op stats, the cancel counter, and in
        far fewer tasks run than blocks exist."""
        from ray_tpu.core.core_worker import global_worker

        w = global_worker()
        before = w._tasks_cancelled

        def slow(x):
            time.sleep(0.2)
            return x

        ds = (
            rdata.from_items(list(range(80)), parallelism=40)
            .map(slow)
            .limit(2)
        )
        assert ds.take_all() == [0, 1]
        map_st = ds._last_stats[0]
        assert map_st.tasks_cancel_requested > 0
        assert map_st.num_tasks < 40  # launches stopped early too
        # Owner-side acceptance is a posted loop callback; poll for it.
        assert _wait_until(lambda: w._tasks_cancelled > before)

    def test_limit_remote_count_trim_on_big_blocks(self, cluster):
        """Blocks above _LIMIT_DRIVER_FETCH_MAX_BYTES take the remote
        count/trim path (no full driver fetch per block); the limit is
        still row-exact, including the mid-block trim."""
        from ray_tpu.data import streaming

        # ~6 MiB per block (int64), well over the 4 MiB driver-get cap.
        n_per_block = 750_000
        ds = rdata.read_numpy(
            {"x": np.arange(2 * n_per_block)}, parallelism=2
        ).limit(n_per_block + 5_000)
        rows = ds.take_all()
        assert len(rows) == n_per_block + 5_000
        assert [r["x"] for r in rows[:3]] == [0, 1, 2]
        assert rows[-1]["x"] == n_per_block + 4_999
        limit_st = [
            s for s in ds._last_stats if s.name.startswith("Limit")
        ]
        assert limit_st and limit_st[0].num_tasks == 2
        # Guard the threshold constant itself so a future bump doesn't
        # silently turn this back into a driver-fetch test.
        assert 6_000_000 > streaming._LIMIT_DRIVER_FETCH_MAX_BYTES

    def test_abandoned_iterator_cancels(self, cluster):
        """A consumer that simply stops pulling (take) also triggers
        cancellation via generator close, not just LimitStage."""
        from ray_tpu.core.core_worker import global_worker

        w = global_worker()
        before = w._tasks_cancelled

        def slow(x):
            time.sleep(0.2)
            return x

        ds = rdata.from_items(list(range(60)), parallelism=60).map(slow)
        out = ds.take(3)
        assert out == [0, 1, 2]
        assert _wait_until(lambda: w._tasks_cancelled > before)

    def test_cancel_api_semantics(self, cluster):
        """ray_tpu.cancel core contract: queued tasks die with
        TaskCancelledError; finished tasks are untouched."""

        @ray_tpu.remote
        def slow(i):
            time.sleep(0.4)
            return i

        done_ref = slow.remote(-1)
        assert ray_tpu.get(done_ref, timeout=60) == -1
        ray_tpu.cancel(done_ref)  # no-op on a finished task
        assert ray_tpu.get(done_ref, timeout=60) == -1

        refs = [slow.remote(i) for i in range(24)]
        time.sleep(0.1)
        ray_tpu.cancel(refs)
        outcomes = []
        for r in refs:
            try:
                outcomes.append(("ok", ray_tpu.get(r, timeout=60)))
            except ray_tpu.TaskCancelledError:
                outcomes.append(("cancelled", None))
        cancelled = sum(1 for kind, _ in outcomes if kind == "cancelled")
        assert cancelled > 0  # queued tasks were skipped
        # Whatever completed, completed correctly.
        for (kind, val), i in zip(outcomes, range(24)):
            if kind == "ok":
                assert val == i

    def test_raced_cancel_not_recorded_after_reply(self, cluster):
        """Executor side: a cancel notify that loses the race with task
        completion is dropped, not recorded — a stale _cancelled_tasks
        entry would fail a later re-execution of the same task id
        (retry / lineage reconstruction) with TaskCancelledError."""
        from ray_tpu.core.core_worker import global_worker

        w = global_worker()
        tid = b"\xde\xad\xbe\xef-not-pending"
        w.handle_cancel_task({"task_ids": [tid]}, None)
        assert tid not in w._cancelled_tasks  # task not pending: dropped
        w._pending_exec_tasks.add(tid)
        try:
            w.handle_cancel_task({"task_ids": [tid]}, None)
            assert tid in w._cancelled_tasks  # pending: recorded
        finally:
            w._pending_exec_tasks.discard(tid)
            w._cancelled_tasks.discard(tid)
            if tid in w._cancelled_order:
                w._cancelled_order.remove(tid)


class TestStatsAndSmoke:
    def test_stats_formatted_summary(self, cluster):
        ds = rdata.range_dataset(100, parallelism=4).map(lambda x: x)
        ds.take_all()
        text = ds.stats()
        assert "tasks" in text
        assert "queue wait p50/p95" in text
        assert "blocks out" in text

    def test_wall_excludes_consume_time(self, cluster):
        """OpStats.wall_s measures operator work: a slow CONSUMER must
        not inflate the (fast) operator's wall."""
        ds = rdata.range_dataset(40, parallelism=4).map(lambda x: x)
        t0 = time.perf_counter()
        for _block in ds.iter_blocks():
            time.sleep(0.25)  # slow consumer
        consume_wall = time.perf_counter() - t0
        st = ds._last_stats[0]
        # Operator wall closes at last output PRODUCED (next scheduler
        # pass), not at last output consumed — it must sit well under
        # the ~1s consume wall instead of tracking it.
        assert consume_wall > 0.9
        # The old generator chain folded every consumer sleep into the
        # op's wall (wall ~= consume_wall); the scheduler must not.
        assert st.wall_s < consume_wall * 0.8, (st.wall_s, consume_wall)

    def test_streaming_rows_smoke(self, cluster):
        """Tier-1 smoke of a map / filter / take_all stream at small
        scale."""
        n = 20_000
        t0 = time.perf_counter()
        out = (
            rdata.range_dataset(n, parallelism=8)
            .map(lambda x: x + 1)
            .filter(lambda x: x % 2 == 0)
            .take_all()
        )
        dt = time.perf_counter() - t0
        assert len(out) == n // 2
        assert dt < 60

    def test_straggler_wait_metric_recorded(self, cluster):
        from ray_tpu.util import metrics

        ds = rdata.from_items(list(range(4)), parallelism=4).map(
            lambda x: (time.sleep(0.1), x)[1]
        )
        ds.take_all()
        snap = metrics.snapshot()
        assert any(
            k.startswith("ray_tpu_data_straggler_wait_s") for k in snap
        )


class TestExecutionOptions:
    def test_chained_calls_merge(self):
        """Keyword fields compose across chained calls instead of
        silently resetting earlier choices."""
        ds = rdata.range_dataset(8, parallelism=2).execution_options(
            preserve_order=False
        )
        ds2 = ds.execution_options(target_block_size_bytes=1024)
        assert ds2._options.preserve_order is False
        assert ds2._options.target_block_size_bytes == 1024

    def test_object_plus_kwargs_rejected(self):
        ds = rdata.range_dataset(8, parallelism=2)
        with pytest.raises(ValueError):
            ds.execution_options(
                rdata.ExecutionOptions(), preserve_order=False
            )


class TestRaylintClean:
    def test_streaming_module_lints_clean(self):
        """The new subsystem carries zero new waivers."""
        from ray_tpu.devtools import lint

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        target = os.path.join(root, "ray_tpu", "data", "streaming.py")
        violations, _ = lint.run(
            [target], lint.default_waiver_file(), check_docs=False
        )
        assert [v for v in violations if not v.waived] == []
        assert [v for v in violations if v.waived] == []  # zero waivers
