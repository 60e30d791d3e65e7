"""Serve autoscaling, composition, multiplexing, replica FT, and config
deploy (reference test model: ray ``python/ray/serve/tests/``)."""

import threading
import time

import pytest

import ray_tpu
import ray_tpu.serve as serve


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=8)
    yield ctx
    serve.shutdown()
    ray_tpu.shutdown()


def _wait_for(pred, timeout=30, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.3)
    raise AssertionError(f"timed out waiting for {msg}")


def test_composition_handle_in_handle(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Adder:
        def __init__(self, delta):
            self.delta = delta

        def __call__(self, x):
            return x + self.delta

    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Pipeline:
        def __init__(self, adder):
            self.adder = adder

        def __call__(self, x):
            partial = self.adder.remote(x).result(timeout=30)
            return partial * 10

    handle = serve.run(Pipeline.bind(Adder.bind(5)))
    assert handle.remote(2).result(timeout=60) == 70
    serve.delete("Pipeline")
    serve.delete("Adder")


def test_autoscaling_up_and_down(cluster):
    @serve.deployment(
        ray_actor_options={"num_cpus": 0},
        max_ongoing_requests=2,
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1.0,
            "upscale_delay_s": 0.2,
            "downscale_delay_s": 1.0,
        },
    )
    class Slow:
        async def __call__(self):
            import asyncio

            await asyncio.sleep(0.4)
            return "ok"

    handle = serve.run(Slow.bind())
    assert serve.status()["Slow"]["num_replicas"] == 1
    # Sustained pressure: many concurrent requests.
    responses = [handle.remote() for _ in range(40)]
    _wait_for(
        lambda: serve.status()["Slow"]["num_replicas"] >= 2,
        timeout=30,
        msg="scale up",
    )
    for r in responses:
        assert r.result(timeout=60) == "ok"
    _wait_for(
        lambda: serve.status()["Slow"]["num_replicas"] == 1,
        timeout=30,
        msg="scale down",
    )
    serve.delete("Slow")


def test_dead_replica_replaced(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Fragile:
        def __call__(self):
            return "alive"

        def crash(self):
            import os

            os._exit(1)

    handle = serve.run(Fragile.bind())
    assert handle.remote().result(timeout=60) == "alive"
    try:
        handle.crash.remote().result(timeout=10)
    except Exception:
        pass
    # Reconciler replaces the dead replica; requests succeed again.
    def works():
        try:
            fresh = serve.get_handle("Fragile")
            return fresh.remote().result(timeout=10) == "alive"
        except Exception:
            return False

    _wait_for(works, timeout=40, msg="replica replacement")
    serve.delete("Fragile")


def test_multiplexed_models(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "weights": model_id * 2}

        async def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model(model_id)
            return f"{model['id']}:{x}"

        def load_count(self):
            return len(self.loads)

    handle = serve.run(MultiModel.bind())
    h_a = handle.options(multiplexed_model_id="ma")
    h_b = handle.options(multiplexed_model_id="mb")
    assert h_a.remote(1).result(timeout=60) == "ma:1"
    assert h_b.remote(2).result(timeout=60) == "mb:2"
    assert h_a.remote(3).result(timeout=60) == "ma:3"
    # LRU: 2 distinct models → exactly 2 loads despite 3 calls.
    loads = serve.get_handle("MultiModel").load_count.remote().result(timeout=30)
    assert loads == 2
    serve.delete("MultiModel")


def test_deploy_config_and_cli_status(cluster, tmp_path, capsys):
    import json

    config = {
        "applications": [
            {
                "import_path": "tests.serve_config_app:app",
                "route_prefix": "/echo2",
                "deployment_overrides": {"num_replicas": 2},
            }
        ]
    }
    handles = serve.deploy_config(config)
    assert "ConfigEcho" in handles
    assert handles["ConfigEcho"].remote("hi").result(timeout=60) == "echo:hi"
    assert serve.status()["ConfigEcho"]["num_replicas"] == 2

    from ray_tpu.scripts.cli import main

    assert main(["serve", "status"]) == 0
    out = capsys.readouterr().out
    assert "ConfigEcho" in out
    serve.delete("ConfigEcho")


def test_handle_streaming(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield {"chunk": i}

    handle = serve.run(Streamer.bind())
    chunks = list(handle.options(stream=True).remote(4))
    assert chunks == [{"chunk": i} for i in range(4)]
    # Non-generator via stream errors loudly.
    @serve.deployment(name="NotGen", ray_actor_options={"num_cpus": 0})
    class NotGen:
        def __call__(self):
            return 42

    h2 = serve.run(NotGen.bind())
    with pytest.raises(Exception, match="generator"):
        list(h2.options(stream=True).remote())
    serve.delete("Streamer")
    serve.delete("NotGen")


def test_http_sse_streaming(cluster):
    import urllib.request

    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Ticker:
        async def __call__(self, body):
            if body.get("stream") is True:
                def gen():
                    for i in range(3):
                        yield {"tick": i}

                return gen()
            return {"all": 3}

    serve.run(Ticker.bind(), route_prefix="/tick")
    url = serve.start_http_proxy(port=8171)
    import json as _json

    req = urllib.request.Request(
        f"{url}/tick",
        data=_json.dumps({"stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    raw = urllib.request.urlopen(req, timeout=120).read().decode()
    frames = [l[len("data: "):] for l in raw.splitlines() if l.startswith("data: ")]
    assert frames[-1] == "[DONE]"
    ticks = [_json.loads(f)["tick"] for f in frames[:-1]]
    assert ticks == [0, 1, 2]
    # Non-stream body unaffected.
    req = urllib.request.Request(
        f"{url}/tick",
        data=_json.dumps({}).encode(),
        headers={"Content-Type": "application/json"},
    )
    out = _json.loads(urllib.request.urlopen(req, timeout=60).read())
    assert out["result"] == {"all": 3}
    serve.stop_http_proxy()
    serve.delete("Ticker")


def test_http_sse_sends_every_chunk_once_when_they_come_faster_than_it_writes(
        cluster):
    """The proxy takes whatever chunks have arrived in one hop
    (``DeploymentResponseGenerator.take``): each is still one ``data:``
    frame, in order, and a mid-stream error follows the chunks before it."""
    import json as _json
    import urllib.request

    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Burst:
        def __call__(self, body):
            for i in range(body["n"]):
                yield {"i": i}
            if body.get("fail"):
                raise RuntimeError("burst broke")

    handle = serve.run(Burst.bind(), route_prefix="/burst")
    gen = handle.options(stream=True).remote({"n": 40})
    got = []
    while True:
        chunks = gen.take()
        if not chunks:
            break
        got += chunks
    assert got == [{"i": i} for i in range(40)]

    url = serve.start_http_proxy(port=8172)
    for fail in (False, True):
        req = urllib.request.Request(
            f"{url}/burst",
            data=_json.dumps({"stream": True, "n": 200, "fail": fail}).encode(),
            headers={"Content-Type": "application/json"},
        )
        raw = urllib.request.urlopen(req, timeout=120).read().decode()
        frames = [_json.loads(l[6:]) for l in raw.splitlines()
                  if l.startswith("data: ") and l != "data: [DONE]"]
        assert raw.rstrip().endswith("data: [DONE]")
        assert frames[:200] == [{"i": i} for i in range(200)]
        if fail:
            assert len(frames) == 201 and "burst broke" in frames[200]["error"]
        else:
            assert len(frames) == 200
    serve.stop_http_proxy()
    serve.delete("Burst")


class TestAutoscaleDrainRetire:
    def test_up_then_drain_then_down(self, cluster):
        """Queue pressure scales replicas up; idling scales down via
        drain-then-retire — the retiring replica leaves the routable set
        but finishes its queue, so no request is dropped."""
        import ray_tpu.serve as serve

        @serve.deployment(
            name="SlowEcho",
            ray_actor_options={"num_cpus": 0},
            max_ongoing_requests=2,
            autoscaling_config={
                "min_replicas": 1,
                "max_replicas": 3,
                "target_ongoing_requests": 1.0,
                "upscale_delay_s": 0.2,
                "downscale_delay_s": 0.8,
                "drain_timeout_s": 30.0,
            },
        )
        class SlowEcho:
            def __call__(self, x):
                time.sleep(0.3)
                return x

        handle = serve.run(SlowEcho.bind())
        results = []
        errors = []
        stop = threading.Event()

        def client(i):
            j = 0
            while not stop.is_set():
                try:
                    results.append(
                        handle.remote((i, j)).result(timeout=120)
                    )
                except Exception as e:  # noqa: BLE001 — assert below
                    errors.append(e)
                j += 1

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True,
                             name=f"load-{i}")
            for i in range(8)
        ]
        for t in threads:
            t.start()
        try:
            _wait_for(
                lambda: serve.status()["SlowEcho"]["num_replicas"] >= 2,
                timeout=90, msg="scale-up under queue pressure",
            )
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=120)
        assert not errors, errors[:3]
        assert results  # load actually flowed
        n_before = len(results)
        _wait_for(
            lambda: serve.status()["SlowEcho"]["num_replicas"] == 1
            and serve.status()["SlowEcho"]["num_draining"] == 0,
            timeout=120, msg="drain-then-retire back to min",
        )
        assert len(results) == n_before  # nothing trickled in as errors
        assert not errors
        serve.delete("SlowEcho")

    def test_autoscale_events_recorded(self, cluster):
        """The scale decisions above landed on the flight recorder."""
        from ray_tpu.util import metrics
        from ray_tpu.util.metric_registry import (
            SERVE_AUTOSCALE_EVENTS_TOTAL,
        )

        def directions():
            return {
                (ent.get("tags") or {}).get("direction")
                for ent in metrics.snapshot().values()
                if ent.get("name") == SERVE_AUTOSCALE_EVENTS_TOTAL
            }

        _wait_for(
            lambda: {"up", "down", "drain_retired"} <= directions(),
            timeout=60, msg="autoscale events in the metrics registry",
        )
