"""Tests for ray_tpu.util (ActorPool, Queue, metrics) and runtime envs.

Models the reference's test strategy for these utilities
(``python/ray/tests/test_actor_pool.py``, ``test_queue.py``,
``test_metrics_agent.py``, ``test_runtime_env*.py`` — SURVEY.md §4).
"""

import os

import pytest

import ray_tpu
from ray_tpu.util import ActorPool, Empty, Full, Queue
from ray_tpu.util import metrics


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote(num_cpus=0)
class _Doubler:
    def double(self, x):
        return 2 * x


@pytest.fixture
def doublers(cluster):
    actors = []

    def make(n):
        actors.extend(_Doubler.remote() for _ in range(n))
        return list(actors)

    yield make
    for a in actors:
        ray_tpu.kill(a)


def test_actor_pool_ordered_map(doublers):
    pool = ActorPool(doublers(3))
    out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
    assert out == [2 * i for i in range(8)]


def test_actor_pool_unordered_map(doublers):
    pool = ActorPool(doublers(3))
    out = list(pool.map_unordered(lambda a, v: a.double.remote(v), range(8)))
    assert sorted(out) == [2 * i for i in range(8)]


def test_actor_pool_submit_get_next(doublers):
    pool = ActorPool(doublers(2))
    for v in range(5):
        pool.submit(lambda a, v: a.double.remote(v), v)
    got = [pool.get_next() for _ in range(5)]
    assert got == [0, 2, 4, 6, 8]
    assert not pool.has_next()


def test_actor_pool_push_pop(doublers):
    pool = ActorPool(doublers(1))
    extra = pool.pop_idle()
    assert extra is not None
    assert pool.pop_idle() is None
    pool.push(extra)
    assert list(pool.map(lambda a, v: a.double.remote(v), [3])) == [6]


def test_queue_fifo(cluster):
    q = Queue()
    for i in range(5):
        q.put(i)
    assert q.qsize() == 5
    assert [q.get() for _ in range(5)] == list(range(5))
    assert q.empty()
    q.shutdown()


def test_queue_maxsize_and_nowait(cluster):
    q = Queue(maxsize=2)
    q.put(1)
    q.put(2)
    assert q.full()
    with pytest.raises(Full):
        q.put_nowait(3)
    with pytest.raises(Full):
        q.put(3, timeout=0.05)
    assert q.get() == 1
    q.put(3)
    assert q.get_batch(2) == [2, 3]
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get(timeout=0.05)
    q.shutdown()


def test_queue_from_remote_tasks(cluster):
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return n

    assert ray_tpu.get(producer.remote(q, 4), timeout=30) == 4
    assert sorted(q.get_batch(4)) == [0, 1, 2, 3]
    q.shutdown()


def test_metrics_counter_gauge_histogram(cluster):
    c = metrics.Counter("req_total", tag_keys=("route",))
    c.inc(2.0, tags={"route": "/a"})
    c.inc(3.0, tags={"route": "/a"})
    g = metrics.Gauge("inflight")
    g.set(7.0)
    h = metrics.Histogram("lat_s", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    snap = metrics.snapshot()
    by_name = {v["name"]: v for v in snap.values()}
    assert by_name["req_total"]["value"] == 5.0
    assert by_name["inflight"]["value"] == 7.0
    assert by_name["lat_s"]["count"] == 2
    text = metrics.prometheus_text()
    assert "# TYPE req_total counter" in text
    assert "lat_s_count" in text


def test_metrics_undeclared_tag_raises(cluster):
    c = metrics.Counter("tagged", tag_keys=("a",))
    with pytest.raises(ValueError):
        c.inc(1.0, tags={"b": "x"})


def test_metrics_recorded_in_worker(cluster):
    @ray_tpu.remote
    def work():
        c = metrics.Counter("worker_side")
        c.inc(4.0)
        metrics.flush()
        return True

    assert ray_tpu.get(work.remote(), timeout=30)
    by_name = {v["name"]: v for v in metrics.snapshot().values()}
    assert by_name["worker_side"]["value"] == 4.0


def test_a_recording_thread_never_waits_for_the_workers_loop(monkeypatch):
    """A flush that falls due on a thread other than the worker's loop is
    handed to the loop and not waited for: the loop may itself be waiting for
    that thread (an engine's loop records every step while a unary caller
    blocks the replica's loop until its request is done).  ``flush()`` is
    the one call that waits."""
    import asyncio
    import threading

    from ray_tpu.core import core_worker

    pushed, blocking = [], []

    class ControlPlane:
        async def call(self, method, payload):
            pushed.append((method, payload["key"]))

    class Worker:
        loop = asyncio.new_event_loop()  # nobody runs it yet: a busy loop
        cp = ControlPlane()
        worker_id = b"\x01\x02"

        def kv_put(self, *args):
            blocking.append(args)

    monkeypatch.setattr(core_worker, "_global_worker", Worker())
    monkeypatch.setattr(metrics, "_flush_hook", None)
    monkeypatch.setattr(metrics, "_last_flush", 0.0)  # a flush is due
    recorder = threading.Thread(
        target=lambda: metrics.Counter("recorded_off_the_loop").inc(),
        daemon=True)
    recorder.start()
    recorder.join(timeout=30)
    assert not recorder.is_alive() and not blocking and not pushed
    Worker.loop.run_until_complete(asyncio.sleep(0.05))  # the loop's turn
    Worker.loop.close()
    assert pushed == [("kv_put", "worker:0102")]
    metrics.Counter("recorded_off_the_loop").inc()  # none due so soon
    metrics.flush()
    assert len(blocking) == 1


def test_runtime_env_env_vars(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"RT_TEST_VAR": "hello"}})
    def read_env():
        return os.environ.get("RT_TEST_VAR")

    assert ray_tpu.get(read_env.remote(), timeout=60) == "hello"


def test_runtime_env_working_dir(cluster, tmp_path):
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "cfg.txt").write_text("42")
    (proj / "helper_mod_rt.py").write_text("MAGIC = 99\n")

    @ray_tpu.remote(runtime_env={"working_dir": str(proj)})
    def use_wd():
        import helper_mod_rt

        with open("cfg.txt") as f:
            return f.read(), helper_mod_rt.MAGIC

    out = ray_tpu.get(use_wd.remote(), timeout=60)
    assert out == ("42", 99)


def test_runtime_env_py_modules(cluster, tmp_path):
    pkg = tmp_path / "mypkg_rt"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("VALUE = 'from-module'\n")

    @ray_tpu.remote(runtime_env={"py_modules": [str(pkg)]})
    def use_mod():
        import mypkg_rt

        return mypkg_rt.VALUE

    assert ray_tpu.get(use_mod.remote(), timeout=60) == "from-module"


def test_runtime_env_unknown_key_raises(cluster):
    # Every reference runtime_env mode is now supported (pip/uv r3,
    # conda r4, container/image_uri r5) — but an unrecognized key must
    # still fail fast, not be silently dropped.
    with pytest.raises(ValueError):

        @ray_tpu.remote(runtime_env={"nonsense_key": {"image": "x"}})
        def f():
            pass

        f.remote()


def test_tpu_util_helpers(cluster):
    from ray_tpu.util import tpu

    assert tpu.get_num_tpu_chips_on_node() >= 0
    assert tpu.get_current_pod_worker_count() >= 1


def test_util_package_lazy_attrs():
    """PEP 562 lazy init must preserve the public attribute surface the
    eager imports used to provide, including submodule access."""
    import ray_tpu.util as u

    assert u.Queue is not None and u.ActorPool is not None
    assert u.queue.Queue is u.Queue
    assert u.actor_pool.ActorPool is u.ActorPool
    assert hasattr(u.state, "summarize_task_phases")
    assert hasattr(u.tpu, "__name__")
    with pytest.raises(AttributeError):
        u.no_such_attr
