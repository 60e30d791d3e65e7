"""Training backends: per-framework distributed-runtime setup.

Reference: ray ``python/ray/train/backend.py`` (Backend.on_start/on_shutdown)
and the Jax backend at ``train/v2/jax/config.py:21-101`` (rank-0 address
broadcast, then per-worker ``jax.distributed.initialize``).  Here the Jax
backend is the *default*: rank 0 picks a coordinator port, the address is
shipped through the worker-group actors, and every worker initializes the
JAX coordination service, after which the whole slice is one device mesh and
in-step collectives ride ICI.
"""

from __future__ import annotations

from typing import List

from .worker_group import JAX_INIT_TIMEOUT_S


class Backend:
    def on_start(self, worker_group) -> None:  # noqa: D401
        pass

    def on_shutdown(self, worker_group) -> None:
        pass


class JaxBackend(Backend):
    """Bootstraps ``jax.distributed`` across the worker group."""

    def __init__(self, platform: str = "", coordinator_port: int = 0):
        self.platform = platform  # "" = leave the env's platform alone
        self.coordinator_port = coordinator_port

    def on_start(self, worker_group):
        import ray_tpu

        n = len(worker_group.workers)
        if n <= 1 and not self.platform:
            return  # single worker: nothing to rendezvous
        addr = ray_tpu.get(
            worker_group.workers[0].get_coordinator_address.remote(
                self.coordinator_port
            ),
            timeout=60,
        )
        # One runtime address per worker: one-chip workers sharing a host
        # need each other's to form libtpu's process grid (see
        # tpu_detect.join_host_process_grid); others ignore them.
        peers = ray_tpu.get(
            [
                w.get_coordinator_address.remote(0)
                for w in worker_group.workers
            ],
            timeout=60,
        )
        ray_tpu.get(
            [
                w.init_jax_distributed.remote(
                    addr, n, rank, self.platform, peers
                )
                for rank, w in enumerate(worker_group.workers)
            ],
            timeout=JAX_INIT_TIMEOUT_S + 30,
        )


class TorchBackend(Backend):
    """CPU torch.distributed (gloo) process group for parity with the
    reference's TorchTrainer (ray ``train/torch/config.py:73-122``)."""

    def on_start(self, worker_group):
        import ray_tpu

        n = len(worker_group.workers)
        addr = ray_tpu.get(
            worker_group.workers[0].get_coordinator_address.remote(0),
            timeout=60,
        )
        host, port = addr.rsplit(":", 1)
        ray_tpu.get(
            [
                w.init_torch_distributed.remote(host, int(port), n, rank)
                for rank, w in enumerate(worker_group.workers)
            ],
            timeout=300,
        )


class TensorflowBackend(Backend):
    """TF_CONFIG-based MultiWorkerMirroredStrategy setup (reference:
    ray ``train/tensorflow/config.py`` ``_setup_tensorflow_environment``).
    Each worker reserves its own port; every rank gets the same cluster
    spec with itself as ``task.index``, so a
    ``tf.distribute.MultiWorkerMirroredStrategy()`` constructed inside
    ``train_loop_per_worker`` rendezvouses over gRPC without any other
    launcher."""

    def on_start(self, worker_group):
        import json

        import ray_tpu

        workers = worker_group.workers
        addrs = ray_tpu.get(
            [w.get_coordinator_address.remote(0) for w in workers],
            timeout=60,
        )
        ray_tpu.get(
            [
                w.set_env.remote({
                    "TF_CONFIG": json.dumps({
                        "cluster": {"worker": list(addrs)},
                        "task": {"type": "worker", "index": rank},
                    }),
                    # Silence TF's GPU probing on CPU/TPU-host workers.
                    "CUDA_VISIBLE_DEVICES": "-1",
                })
                for rank, w in enumerate(workers)
            ],
            timeout=60,
        )


class AccelerateBackend(TorchBackend):
    """HuggingFace Accelerate over the torch gloo group (reference:
    ray ``train/huggingface/accelerate`` integration).  The torch process
    group is bootstrapped exactly like TorchBackend; workers additionally
    get the env Accelerate reads so ``accelerate.Accelerator()`` inside
    ``train_loop_per_worker`` picks up the already-initialized group (and
    a ``transformers.Trainer`` built there trains data-parallel)."""

    def on_start(self, worker_group):
        import ray_tpu

        n = len(worker_group.workers)
        addr = ray_tpu.get(
            worker_group.workers[0].get_coordinator_address.remote(0),
            timeout=60,
        )
        host, port = addr.rsplit(":", 1)
        # Env FIRST: Accelerate's launcher checks MASTER_ADDR/RANK even
        # when torch.distributed is already initialized.
        ray_tpu.get(
            [
                w.set_env.remote(
                    {
                        "ACCELERATE_USE_CPU": "true",
                        "MASTER_ADDR": host,
                        "MASTER_PORT": port,
                        "RANK": str(rank),
                        "WORLD_SIZE": str(n),
                        "LOCAL_RANK": "0",
                    }
                )
                for rank, w in enumerate(worker_group.workers)
            ],
            timeout=60,
        )
        ray_tpu.get(
            [
                w.init_torch_distributed.remote(host, int(port), n, rank)
                for rank, w in enumerate(worker_group.workers)
            ],
            timeout=300,
        )
