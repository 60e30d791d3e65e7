"""Long-poll push of serve control state (reference
``python/ray/serve/_private/long_poll.py:252``): handles and proxies
subscribe; replica-list and route-table changes are pushed, not polled."""

import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield
    serve.shutdown()


@serve.deployment
class Echo:
    def __call__(self, x):
        return x


@pytest.fixture(params=["first_cluster", "after_a_cluster_left_its_client"])
def earlier_cluster(request):
    """What this process did before the test's cluster: nothing, or a cluster
    that served and was shut down with NO ``serve.shutdown()``, so that its
    long-poll client, listening, is still the process's
    (``tests/test_chaos_injection.py`` leaves one, and under six workers
    this file ran after it in the same process: the push was 44 s late,
    PR 64)."""
    if request.param == "first_cluster":
        return
    from ray_tpu.serve.long_poll import long_poll_client

    ray_tpu.init(num_cpus=4)
    handle = serve.run(Echo.options(name="Earlier").bind())
    assert handle.remote("a").result(timeout=60) == "a"
    left = long_poll_client()
    assert left._thread.is_alive()
    ray_tpu.shutdown()
    assert not left._stopped


class TestLongPollPush:
    def test_replica_update_pushed_fast(self, earlier_cluster, serve_cluster):
        h = serve.run(Echo.options(num_replicas=1).bind())
        assert h.remote("a").result(timeout=60) == "a"
        # The handle is subscribed now (first _refresh registered the key).
        before = list(h._replicas)
        assert len(before) == 1

        # Scale 1 -> 3 and measure how long until the HANDLE's cached list
        # reflects it WITHOUT any direct controller RPC from the handle.

        serve.run(Echo.options(num_replicas=3).bind())
        deadline = time.monotonic() + 5.0
        latency = None
        t0 = time.monotonic()
        while time.monotonic() < deadline:
            from ray_tpu.serve.long_poll import long_poll_client

            pushed = long_poll_client().get(("replicas", "Echo"))
            if pushed is not None and len(pushed) == 3:
                latency = time.monotonic() - t0
                break
            time.sleep(0.005)
        assert latency is not None, "replica update never pushed"
        # one RPC latency, not a poll period (old design: 2-5s timer)
        assert latency < 1.0, f"push took {latency:.3f}s"

        # And the handle consumes the push on its next route.
        h._refresh()
        assert len(h._replicas) == 3

    def test_route_table_pushed_on_deploy_and_delete(self, serve_cluster):
        from ray_tpu.serve.long_poll import long_poll_client

        serve.run(Echo.bind())
        lp = long_poll_client()
        lp.register(("routes",))
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            routes = lp.get(("routes",))
            if routes is not None and "/Echo" in routes:
                break
            time.sleep(0.005)
        else:
            raise AssertionError("route push never arrived")

        serve.delete("Echo")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            routes = lp.get(("routes",))
            if routes is not None and "/Echo" not in routes:
                break
            time.sleep(0.005)
        else:
            raise AssertionError("route removal never pushed")

    def test_dead_replica_replacement_pushed(self, serve_cluster):
        h = serve.run(Echo.options(num_replicas=2).bind())
        assert h.remote("x").result(timeout=60) == "x"
        from ray_tpu.serve.long_poll import long_poll_client

        lp = long_poll_client()
        # Wait for the initial push so we can detect the NEXT one.
        deadline = time.monotonic() + 5.0
        while lp.get(("replicas", "Echo")) is None:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        old_ids = {r._actor_id for r in lp.get(("replicas", "Echo"))}

        victim = h._replicas[0]
        ray_tpu.kill(victim)
        # Controller reconcile notices the death and pushes the replacement.
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            pushed = lp.get(("replicas", "Echo"))
            ids = {r._actor_id for r in pushed}
            if ids != old_ids and len(ids) == 2:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("replacement replica never pushed")
        # Routing keeps working against the pushed list.
        h._refresh()
        assert h.remote("y").result(timeout=60) == "y"
