"""Single-node scalability-envelope regressions (reference:
``release/benchmarks/single_node/test_single_node.py``).

The full envelopes (10k args, 3k returns, 10k-ref get, 100k queued,
arena-oversized spill) are run by nothing in this repository; the tests
here pin the MACHINERY those envelopes lean on at smoke scale so tier-1
stays fast, plus heavier (still box-sane) versions under
``@pytest.mark.slow``:

  - wide-args / wide-returns / wide-get correctness at scale,
  - submission backpressure: queued-task memory is CAPPED — a producer
    flood blocks at the cap instead of growing driver RSS without bound,
    and everything still completes,
  - an arena-oversized put round-trips end-to-end through the disk spill
    tier,
  - spill exhaustion raises ObjectStoreFullError promptly — a clear
    error, never a hang,
  - LanePool.stop() fail-fast semantics (queued items fail, busy lanes
    are never stranded on their own queue).
"""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.exceptions import ObjectStoreFullError


class TestWideSmoke:
    """Wide args / returns / get at smoke scale — one shared cluster
    (class-scoped: tier-1 pays one init, not three)."""

    @pytest.fixture(scope="class")
    def cluster(self):
        ctx = ray_tpu.init(
            num_cpus=4,
            _system_config={
                "prestart_workers": 2,
                "worker_startup_timeout_s": 120.0,
            },
        )
        yield ctx
        ray_tpu.shutdown()

    def test_wide_args_smoke(self, cluster):
        """One task with hundreds of object args: every arg resolves,
        holds release afterwards (args_holds bookkeeping at width)."""

        @ray_tpu.remote
        def concat(*args):
            return b"".join(args)

        n = 300
        refs = [ray_tpu.put(bytes([i % 256])) for i in range(n)]
        out = ray_tpu.get(concat.remote(*refs), timeout=120)
        assert out == bytes(i % 256 for i in range(n))
        # A ref passed twice resolves to the same value twice (dedup'd
        # fetch).
        out2 = ray_tpu.get(
            concat.remote(refs[0], refs[0], refs[1]), timeout=120
        )
        assert out2 == bytes([0, 0, 1])
        w = ray_tpu.api.global_worker()
        time.sleep(0.5)  # let arg-holds release land on the loop
        held = [o for o in w.owned.values() if o.args_holds > 0]
        assert not held, f"{len(held)} objects still arg-held"

    def test_wide_returns_smoke(self, cluster):
        @ray_tpu.remote(num_returns=100)
        def hundred():
            return [i.to_bytes(2, "little") for i in range(100)]

        refs = hundred.remote()
        assert len(refs) == 100
        vals = ray_tpu.get(refs, timeout=120)
        assert [int.from_bytes(v, "little") for v in vals] == list(
            range(100)
        )

    def test_wide_get_smoke(self, cluster):
        """One get over hundreds of shm-tier objects after evicting the
        owner's memory-store cache: every value re-reads from the
        arena."""
        n = 300
        blob = np.zeros(130_000, np.uint8)  # above inline cap: shm tier
        refs = [ray_tpu.put(blob) for _ in range(n)]
        w = ray_tpu.api.global_worker()
        for r in refs:
            w.memory_store.free(r.id)
        out = ray_tpu.get(refs, timeout=300)
        assert len(out) == n
        assert all(o.nbytes == blob.nbytes for o in out)


def test_submission_backpressure_caps_queue_memory():
    """A producer flood larger than the cap must (a) block at the cap —
    queued bytes never exceed cap + one charge — and (b) still complete
    every task."""
    cap = 150_000
    ray_tpu.init(
        num_cpus=4,
        _system_config={
            "task_queue_memory_cap_bytes": cap,
            "prestart_workers": 2,
        },
    )
    try:

        @ray_tpu.remote
        def slow_len(blob):
            time.sleep(0.02)
            return len(blob)

        payload = b"z" * 5000
        refs = [slow_len.remote(payload) for _ in range(120)]
        assert ray_tpu.get(refs, timeout=300) == [5000] * 120
        w = ray_tpu.api.global_worker()
        stats = w.submit_budget.stats()
        assert stats["blocked_total"] > 0, "flood never hit the cap"
        # One in-flight charge may legitimately sit above the cap (a lone
        # submission is always admitted); anything more is unbounded
        # growth — the regression this test pins.
        slack = len(payload) + 1024
        assert stats["peak_bytes"] <= cap + slack, stats
        assert stats["queued_bytes"] == 0, "charges leaked"
    finally:
        ray_tpu.shutdown()


def test_backpressure_timeout_is_clear_error():
    """A cluster that cannot drain (zero workers) must surface the cap as
    PendingTaskBackpressureTimeout, not hang the producer forever."""
    from ray_tpu.core.exceptions import PendingTaskBackpressureTimeout

    ray_tpu.init(
        num_cpus=1,
        _system_config={
            "task_queue_memory_cap_bytes": 10_000,
            "task_queue_block_timeout_s": 1.5,
            "prestart_workers": 0,
        },
    )
    try:

        @ray_tpu.remote
        def hold(blob):
            time.sleep(60)

        payload = b"q" * 8000
        # First submission admitted (cap admits a lone charge); the second
        # crosses the cap while the first can never complete in time.
        hold.remote(payload)
        t0 = time.monotonic()
        with pytest.raises(PendingTaskBackpressureTimeout):
            for _ in range(4):
                hold.remote(payload)
        assert time.monotonic() - t0 < 30
    finally:
        ray_tpu.shutdown()


class TestSpillTier:
    """Arena-oversized objects through the disk spill tier — one shared
    small-arena cluster for the put and task-return routes."""

    ARENA = 32 * 1024**2

    @pytest.fixture(scope="class")
    def cluster(self):
        ctx = ray_tpu.init(
            num_cpus=2,
            _system_config={
                "object_store_memory_bytes": self.ARENA,
                "prestart_workers": 0,
                "worker_startup_timeout_s": 120.0,
            },
        )
        yield ctx
        ray_tpu.shutdown()

    def test_oversized_put_round_trips_spill_tier(self, cluster):
        """An object >= 2x the arena size must travel put -> disk spill
        -> get, with the agent's directory accounting it as spilled."""
        big = np.arange(self.ARENA // 4, dtype=np.int64)  # 2x arena
        ref = ray_tpu.put(big)
        w = ray_tpu.api.global_worker()
        # The spilled value must NOT be pinned in the owner's heap cache
        # — the whole point of spilling is bounded RSS.
        assert not w.memory_store.contains(ref.id)
        back = ray_tpu.get(ref, timeout=120)
        assert back.nbytes == big.nbytes
        assert (back[:100] == big[:100]).all()
        assert back[-1] == big[-1]
        st = w._run_sync(w.agent.call("debug_state"))
        assert st["spilled_objects"] >= 1
        assert st["spilled_bytes"] >= big.nbytes

    def test_oversized_task_return_travels_spill_tier(self, cluster):
        """Task RETURNS above the arena size take the same spill route
        as puts (worker-side packaging, owner-side read-back)."""

        @ray_tpu.remote
        def produce(n):
            return np.ones(n, np.int64)

        n = self.ARENA // 4  # 2x arena once serialized
        ref = produce.remote(n)  # HELD: a dropped ref frees the spill
        out = ray_tpu.get(ref, timeout=180)
        assert out.nbytes == n * 8
        assert out[0] == 1 and out[-1] == 1
        w = ray_tpu.api.global_worker()
        st = w._run_sync(w.agent.call("debug_state"))
        assert st["spilled_objects"] >= 1, st
        # Dropping the ref must reclaim the spill file (refcounting
        # reaches the disk tier too).
        import ray_tpu.core.object_store as ost

        path = ost.spill_path(w.session_id, ref.id)
        assert os.path.exists(path)
        del ref, out
        deadline = time.monotonic() + 20
        while os.path.exists(path):
            if time.monotonic() > deadline:
                raise AssertionError("spill file leaked after ref drop")
            time.sleep(0.2)


def test_spill_exhaustion_raises_clear_error():
    """When the spill tier is capped below the object size, the put must
    raise ObjectStoreFullError promptly — not hang, not SIGBUS."""
    ray_tpu.init(
        num_cpus=2,
        _system_config={
            "object_store_memory_bytes": 16 * 1024**2,
            "object_spill_max_bytes": 8 * 1024**2,
            "prestart_workers": 0,
        },
    )
    try:
        t0 = time.monotonic()
        with pytest.raises(ObjectStoreFullError, match="spill"):
            ray_tpu.put(np.zeros(4 * 1024**2, np.int64))  # 32 MB
        assert time.monotonic() - t0 < 10, "exhaustion must fail fast"
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------- LanePool


def _make_loop():
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    return loop, t


def test_lane_pool_stop_fails_queued_items_and_frees_lanes():
    """Regression (ADVICE r5 #1): stop() must fail still-queued items —
    never silently drop them or eat its own sentinels — and every lane
    must exit instead of blocking forever in q.get()."""
    from ray_tpu.core.core_worker import LanePool

    loop, _t = _make_loop()
    try:
        pool = LanePool(loop, size=2)
        gate = threading.Event()
        started = threading.Event()

        def blocker():
            started.set()
            gate.wait(10)
            return "done"

        # Occupy both lanes, then queue two more items no lane can reach.
        futs = [
            asyncio.run_coroutine_threadsafe(pool.run(blocker), loop)
            for _ in range(2)
        ]
        started.wait(5)
        queued = [
            asyncio.run_coroutine_threadsafe(pool.run(lambda: "never"), loop)
            for _ in range(2)
        ]
        time.sleep(0.2)  # let the queued items land in the SimpleQueue
        pool.stop()
        # Queued (unclaimed) items fail fast with a clear error...
        for f in queued:
            with pytest.raises(RuntimeError, match="lane pool stopped"):
                f.result(timeout=10)
        # ...while claimed items run to completion.
        gate.set()
        assert [f.result(timeout=10) for f in futs] == ["done", "done"]
        # And every lane thread exits (no lane stranded on q.get()).
        deadline = time.monotonic() + 10
        while any(t.is_alive() for t in pool._threads):
            if time.monotonic() > deadline:
                raise AssertionError("lane thread stranded after stop()")
            time.sleep(0.05)
        # New work after stop is refused loudly, not queued into the void.
        with pytest.raises(RuntimeError, match="stopped"):
            asyncio.run_coroutine_threadsafe(
                pool.run(lambda: 1), loop
            ).result(timeout=10)
    finally:
        loop.call_soon_threadsafe(loop.stop)


# ------------------------------------------------------------- slow tier


@pytest.mark.slow
def test_wide_args_envelope():
    """Heavier wide-args run (2k args) — catches quadratic behavior in
    arg pinning/resolution that smoke scale hides."""
    ray_tpu.init(num_cpus=4, _system_config={"prestart_workers": 2})
    try:

        @ray_tpu.remote
        def count(*args):
            return len(args)

        n = 2000
        refs = [ray_tpu.put(b"x") for _ in range(n)]
        t0 = time.monotonic()
        assert ray_tpu.get(count.remote(*refs), timeout=600) == n
        assert time.monotonic() - t0 < 120
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_wide_returns_envelope():
    ray_tpu.init(num_cpus=4, _system_config={"prestart_workers": 2})
    try:
        n = 1000

        @ray_tpu.remote(num_returns=n)
        def many():
            return [b"y"] * n

        vals = ray_tpu.get(many.remote(), timeout=600)
        assert len(vals) == n
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_queued_flood_envelope():
    """20k queued no-ops against a small submission cap: backpressure
    engages, queued bytes stay bounded, every task completes."""
    cap = 2 * 1024**2
    ray_tpu.init(
        num_cpus=4,
        _system_config={
            "task_queue_memory_cap_bytes": cap,
            "prestart_workers": 4,
            "worker_startup_timeout_s": 240.0,
        },
    )
    try:

        @ray_tpu.remote
        def noop():
            return None

        n = 20_000
        refs = [noop.remote() for _ in range(n)]
        for i in range(0, n, 2000):
            ray_tpu.get(refs[i : i + 2000], timeout=1200)
        w = ray_tpu.api.global_worker()
        stats = w.submit_budget.stats()
        assert stats["blocked_total"] > 0
        assert stats["peak_bytes"] <= cap + 4096
        assert stats["queued_bytes"] == 0
    finally:
        ray_tpu.shutdown()
