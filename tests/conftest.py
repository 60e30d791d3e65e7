import os
import sys

# The suite runs on the CPU: JAX pinned to it (worker processes inherit the
# pin), with eight virtual devices for the sharding / collective tests.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RAY_TPU_log_level", "INFO")
# ray_tpu places JAX's persistent compile cache (core/compile_cache.py); the
# suite keeps compiling from scratch, as it always has — hundreds of
# processes sharing one cache directory is not what it is here to test.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def engines_shut_down(monkeypatch):
    """Every ``JaxLLMEngine`` a test builds is shut down after it, so no
    ``engine.loop`` thread outlives its test (a replica's dies with its
    process).  Nothing for a worker that has not imported the engine."""
    module = sys.modules.get("ray_tpu.llm.engine")
    if module is None:
        yield
        return
    built = []
    init = module.JaxLLMEngine.__init__

    def tracked(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append(self)

    monkeypatch.setattr(module.JaxLLMEngine, "__init__", tracked)
    yield
    for engine in built:
        engine.shutdown()


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=4)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    import ray_tpu
    from ray_tpu.core.node import Cluster

    cluster = Cluster()
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()
