"""End-to-end tests of the public task/actor/object/placement-group API on a
single-node cluster.  One module-scoped cluster amortizes process startup
(this machine has a single CPU core)."""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=8)
    yield ctx
    ray_tpu.shutdown()


def test_task_roundtrip(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3


def test_task_chain_ref_args(cluster):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(4):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref, timeout=60) == 5


def test_many_small_tasks(cluster):
    @ray_tpu.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(100)]
    assert ray_tpu.get(refs, timeout=60) == [i * i for i in range(100)]


def test_multiple_returns(cluster):
    @ray_tpu.remote(num_returns=2)
    def divmod_(a, b):
        return a // b, a % b

    q, r = divmod_.remote(7, 3)
    assert ray_tpu.get([q, r], timeout=60) == [2, 1]


def test_put_get_small_and_large(cluster):
    small = ray_tpu.put({"k": [1, 2, 3]})
    assert ray_tpu.get(small, timeout=30) == {"k": [1, 2, 3]}
    big = np.arange(1_000_000, dtype=np.float32)  # 4 MB → shm path
    ref = ray_tpu.put(big)
    out = ray_tpu.get(ref, timeout=30)
    np.testing.assert_array_equal(big, out)


def test_large_arg_and_return(cluster):
    @ray_tpu.remote
    def double(a):
        return a * 2

    big = np.ones(1_000_000, dtype=np.float32)
    out = ray_tpu.get(double.remote(ray_tpu.put(big)), timeout=60)
    assert out.dtype == np.float32 and float(out.sum()) == 2_000_000.0


def test_nested_ref_stays_ref(cluster):
    @ray_tpu.remote
    def probe(container):
        inner = container["ref"]
        assert isinstance(inner, ray_tpu.ObjectRef)
        return ray_tpu.get(inner, timeout=30)

    inner = ray_tpu.put(99)
    assert ray_tpu.get(probe.remote({"ref": inner}), timeout=60) == 99


def test_error_propagation(cluster):
    @ray_tpu.remote
    def boom():
        raise KeyError("missing")

    with pytest.raises(ray_tpu.TaskError) as ei:
        ray_tpu.get(boom.remote(), timeout=60)
    assert isinstance(ei.value.cause, KeyError)
    assert "boom" in ei.value.remote_traceback


def test_error_through_dependency(cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("x")

    @ray_tpu.remote
    def use(v):
        return v

    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(use.remote(boom.remote()), timeout=60)


def test_wait(cluster):
    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 2

    f, s = fast.remote(), slow.remote()
    ready, pending = ray_tpu.wait([f, s], num_returns=1, timeout=30)
    assert ready == [f] and pending == [s]


def test_get_timeout(cluster):
    @ray_tpu.remote
    def sleepy():
        time.sleep(30)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(sleepy.remote(), timeout=0.5)


def test_nested_task_submission(cluster):
    @ray_tpu.remote
    def inner(x):
        return x * 10

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x), timeout=30) + 1

    assert ray_tpu.get(outer.remote(4), timeout=60) == 41


def test_actor_basic(cluster):
    @ray_tpu.remote
    class Acc:
        def __init__(self):
            self.total = 0

        def add(self, x):
            self.total += x
            return self.total

    a = Acc.remote()
    refs = [a.add.remote(i) for i in range(10)]
    results = ray_tpu.get(refs, timeout=60)
    # Ordered execution: running totals.
    assert results == [0, 1, 3, 6, 10, 15, 21, 28, 36, 45]


def test_actor_ordering_strict(cluster):
    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.seen = []

        def rec(self, i):
            self.seen.append(i)
            return len(self.seen)

        def dump(self):
            return self.seen

    log = Log.remote()
    for i in range(20):
        log.rec.remote(i)
    assert ray_tpu.get(log.dump.remote(), timeout=60) == list(range(20))


def test_named_actor_and_get_actor(cluster):
    @ray_tpu.remote
    class Holder:
        def __init__(self, v):
            self.v = v

        def get(self):
            return self.v

    Holder.options(name="holder-x").remote(123)
    h = ray_tpu.get_actor("holder-x")
    assert ray_tpu.get(h.get.remote(), timeout=60) == 123
    with pytest.raises(ValueError):
        ray_tpu.get_actor("does-not-exist")


def test_actor_handle_passed_to_task(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    @ray_tpu.remote
    def bump(c):
        return ray_tpu.get(c.inc.remote(), timeout=30)

    c = Counter.remote()
    assert ray_tpu.get(bump.remote(c), timeout=60) == 1
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 2


def test_kill_actor(cluster):
    @ray_tpu.remote
    class Victim:
        def ping(self):
            return "ok"

    v = Victim.remote()
    assert ray_tpu.get(v.ping.remote(), timeout=60) == "ok"
    ray_tpu.kill(v)
    time.sleep(1.0)
    with pytest.raises((ray_tpu.ActorDiedError, ray_tpu.TaskError)):
        ray_tpu.get(v.ping.remote(), timeout=30)


def test_async_actor(cluster):
    @ray_tpu.remote
    class AsyncActor:
        async def work(self, x):
            import asyncio

            await asyncio.sleep(0.01)
            return x + 1

    a = AsyncActor.remote()
    assert ray_tpu.get(a.work.remote(41), timeout=60) == 42


def test_placement_group_lifecycle(cluster):
    pg = ray_tpu.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=20)

    @ray_tpu.remote
    def where():
        return "ran"

    strat = ray_tpu.placement_group_strategy(pg, 0)
    assert (
        ray_tpu.get(where.options(scheduling_strategy=strat).remote(), timeout=60)
        == "ran"
    )
    ray_tpu.remove_placement_group(pg)


def test_placement_group_infeasible_pending(cluster):
    # More CPUs than the cluster has: stays PENDING, doesn't crash.
    pg = ray_tpu.placement_group([{"CPU": 64}], strategy="PACK")
    assert not pg.ready(timeout=0.5)
    ray_tpu.remove_placement_group(pg)


def test_cluster_resources(cluster):
    total = ray_tpu.cluster_resources()
    assert total.get("CPU") == 8.0


def test_state_summary(cluster):
    state = ray_tpu.state_summary()
    assert len(state["nodes"]) == 1
    assert isinstance(state["actors"], list)


def test_max_retries_on_worker_crash(cluster):
    import os

    marker = "/tmp/ray_tpu_crash_once_%d" % time.time_ns()

    @ray_tpu.remote(max_retries=2)
    def crash_once():
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # simulate worker crash
        return "recovered"

    assert ray_tpu.get(crash_once.remote(), timeout=90) == "recovered"
    os.unlink(marker)


def test_no_retries_surfaces_crash(cluster):
    @ray_tpu.remote(max_retries=0)
    def die():
        import os

        os._exit(1)

    with pytest.raises(ray_tpu.WorkerCrashedError):
        ray_tpu.get(die.remote(), timeout=60)


class TestStreamingGenerators:
    """Streaming-generator returns (reference: num_returns='streaming')."""

    def test_sync_generator_streams(self, cluster):
        @ray_tpu.remote
        def countdown(n):
            for i in range(n):
                yield i * 10

        gen = countdown.remote(5)
        assert isinstance(gen, ray_tpu.ObjectRefGenerator)
        values = [ray_tpu.get(ref, timeout=60) for ref in gen]
        assert values == [0, 10, 20, 30, 40]

    def test_async_generator_streams(self, cluster):
        @ray_tpu.remote
        async def apounce(n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield f"chunk{i}"

        values = [ray_tpu.get(r, timeout=60) for r in apounce.remote(3)]
        assert values == ["chunk0", "chunk1", "chunk2"]

    def test_generator_error_mid_stream(self, cluster):
        @ray_tpu.remote
        def bad():
            yield 1
            yield 2
            raise RuntimeError("stream broke")

        gen = bad.remote()
        assert ray_tpu.get(next(gen), timeout=60) == 1
        assert ray_tpu.get(next(gen), timeout=60) == 2
        with pytest.raises(Exception, match="stream broke"):
            for _ in gen:
                pass

    def test_take_returns_what_has_arrived_in_order(self, cluster):
        """``take()`` blocks for one item and hands over every item that
        has arrived by then: a consumer that waited catches up in one call;
        every item comes exactly once, in order; ``[]`` at the end."""
        import time

        @ray_tpu.remote
        def burst(n):
            for i in range(n):
                yield i

        gen = burst.remote(20)
        time.sleep(2.0)  # all twenty have been pushed by now
        first = gen.take()
        assert len(first) > 1  # more than the one it blocked for
        values = ray_tpu.get(first, timeout=60)
        while True:
            refs = gen.take()
            if not refs:
                break
            values += ray_tpu.get(refs, timeout=60)
        assert values == list(range(20))
        assert gen.take() == []

    def test_take_raises_after_the_items_before_the_error(self, cluster):
        import time

        @ray_tpu.remote
        def bad():
            yield 1
            yield 2
            raise RuntimeError("stream broke")

        gen = bad.remote()
        time.sleep(2.0)
        values = []
        with pytest.raises(Exception, match="stream broke"):
            while True:
                refs = gen.take()
                assert refs  # the error comes before any empty batch
                values += ray_tpu.get(refs, timeout=60)
        assert values == [1, 2]

    def test_large_items_via_shm(self, cluster):
        import numpy as np

        @ray_tpu.remote
        def big_chunks():
            for i in range(3):
                yield np.full(50_000, float(i))  # 400KB > inline cap

        arrays = [ray_tpu.get(r, timeout=60) for r in big_chunks.remote()]
        assert [float(a[0]) for a in arrays] == [0.0, 1.0, 2.0]
        assert all(a.shape == (50_000,) for a in arrays)

    def test_streaming_interleaves_with_consumption(self, cluster):
        """Items arrive as produced — the consumer sees the first item long
        before the generator finishes."""
        import time as _time

        @ray_tpu.remote
        def slow_gen():
            for i in range(3):
                yield i
                _time.sleep(0.5)

        gen = slow_gen.remote()
        t0 = _time.monotonic()
        first = ray_tpu.get(next(gen), timeout=60)
        first_latency = _time.monotonic() - t0
        assert first == 0
        assert first_latency < 1.0  # did not wait for the full 1.5s run
        assert [ray_tpu.get(r, timeout=60) for r in gen] == [1, 2]

    def test_actor_method_streaming_opt_in(self, cluster):
        @ray_tpu.remote(max_concurrency=2)
        class Gen:
            def stream(self, n):
                for i in range(n):
                    yield i + 100

            def plain(self):
                return "ok"

        g = Gen.remote()
        gen = g.stream.options(num_returns="streaming").remote(3)
        assert [ray_tpu.get(r, timeout=60) for r in gen] == [100, 101, 102]
        # Plain methods on the same actor unaffected.
        assert ray_tpu.get(g.plain.remote(), timeout=60) == "ok"
        ray_tpu.kill(g)

    def test_generator_without_streaming_flag_errors(self, cluster):
        @ray_tpu.remote(max_concurrency=2)
        class Gen:
            def stream(self):
                yield 1

        g = Gen.remote()
        # No opt-in: the method returns a raw generator, which cannot
        # serialize — surfaces as a task error, never a hang.
        with pytest.raises(Exception):
            ray_tpu.get(g.stream.remote(), timeout=60)
        ray_tpu.kill(g)

    def test_explicit_num_returns_on_generator_fn(self, cluster):
        @ray_tpu.remote(num_returns=2)
        def two():
            yield "a"
            yield "b"

        r1, r2 = two.remote()
        assert ray_tpu.get(r1, timeout=60) == "a"
        assert ray_tpu.get(r2, timeout=60) == "b"

    def test_streaming_retry_on_worker_death(self, cluster):
        @ray_tpu.remote(max_retries=2)
        def flaky_gen(marker_dir):
            import os

            yield 1
            yield 2
            marker = os.path.join(marker_dir, "died")
            if not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)  # die mid-stream on the first attempt
            yield 3

        import tempfile

        d = tempfile.mkdtemp()
        values = [
            ray_tpu.get(r, timeout=120) for r in flaky_gen.remote(d)
        ]
        # The retry replays from scratch: earlier yields repeat, then the
        # stream completes.
        assert values[-1] == 3
        assert values.count(1) >= 1 and values.count(2) >= 1

    def test_streaming_flag_on_non_generator_errors(self, cluster):
        @ray_tpu.remote(max_concurrency=2)
        class A:
            def plain(self):
                return []

        a = A.remote()
        gen = a.plain.options(num_returns="streaming").remote()
        with pytest.raises(Exception, match="not a generator"):
            next(gen)
        ray_tpu.kill(a)

    def test_error_after_items_delivers_items_first(self, cluster):
        @ray_tpu.remote
        def partial():
            yield "x"
            raise ValueError("after one")

        gen = partial.remote()
        collected = []
        with pytest.raises(Exception, match="after one"):
            for ref in gen:
                collected.append(ray_tpu.get(ref, timeout=60))
        assert collected == ["x"]
