"""Multi-host XLA collective group.

The TPU-native replacement for the reference's NCCL process group (ray
``util/collective/collective_group/nccl_collective_group.py:121``): instead
of exchanging a NCCL unique-id and managing per-peer streams, members
rendezvous on a JAX coordination-service address (published through the
control-plane KV — the analog of the unique-id-through-GCS-KV pattern in
``nccl_util.py``), call ``jax.distributed.initialize``, and all ops compile
to XLA collectives over the global device mesh: ICI within a slice, DCN
across slices.

Each member process calls every op with its *local* per-host tensor; results
come back as local numpy/jax values, exactly like the reference's eager NCCL
calls — but the op itself is a jitted shard_map, so repeated calls of the
same shape hit the XLA executable cache.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional

import numpy as np

from .types import Backend, GroupInfo, ReduceOp

logger = logging.getLogger(__name__)

_KV_NAMESPACE = "collective"


def _kv_rendezvous(group_name: str, rank: int, world_size: int,
                   coordinator_port: Optional[int] = None,
                   timeout: float = 60.0) -> str:
    """Rank 0 publishes the coordination-service address in the control-plane
    KV; everyone else polls for it."""
    from ray_tpu.core.core_worker import global_worker
    from ray_tpu.core.rpc import find_free_port

    worker = global_worker()
    key = f"coord:{group_name}"
    if rank == 0:
        port = coordinator_port or find_free_port()
        addr = f"127.0.0.1:{port}"
        import socket

        try:
            addr = f"{socket.gethostbyname(socket.gethostname())}:{port}"
        except Exception as e:
            # Loopback fallback is correct single-host; multi-host ranks
            # on other machines cannot reach 127.0.0.1, so say so.
            logger.info(
                "hostname resolution failed (%s); publishing loopback "
                "coordinator address %s", e, addr,
            )
        worker._run_sync(
            worker.cp.call(
                "kv_put",
                {"namespace": _KV_NAMESPACE, "key": key, "value": addr.encode()},
            )
        )
        return addr
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        val = worker._run_sync(
            worker.cp.call("kv_get", {"namespace": _KV_NAMESPACE, "key": key})
        )
        if val is not None:
            return val.decode()
        time.sleep(0.1)
    raise TimeoutError(f"rendezvous for group {group_name!r} timed out")


class XlaGroup:
    """One member (process) of a multi-host collective group."""

    def __init__(
        self,
        group_name: str,
        world_size: int,
        rank: int,
        coordinator_address: Optional[str] = None,
        local_device_count: Optional[int] = None,
        hosts_per_slice: Optional[int] = None,
    ):
        import jax

        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        if coordinator_address is None:
            coordinator_address = _kv_rendezvous(group_name, rank, world_size)
        self.coordinator_address = coordinator_address
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=world_size,
            process_id=rank,
        )
        from jax.sharding import Mesh

        from .types import Topology

        devices = jax.devices()
        self.devices_per_host = len(devices) // world_size
        self.mesh = Mesh(
            np.array(devices).reshape(world_size, self.devices_per_host),
            ("host", "device"),
        )
        # ``hosts_per_slice``: group members per TPU slice.  Default: the
        # whole group is one slice (every hop ICI).  Multi-slice groups
        # (cross-slice DCN) unlock the two-level algorithms, whose DCN
        # hop carries 1/hosts_per_slice of the payload.
        self.topology = Topology(world_size, hosts_per_slice or world_size)
        self._mesh3 = None  # (dcn, ici, device) view for two-level ops
        self._fn_cache: Dict[tuple, object] = {}
        self._last_decision = None
        # Flight recorder: per-op bytes/duration/bandwidth capture.  These
        # ops materialize results to numpy (host sync), so the recorded
        # durations reflect the real collective, ICI included.
        from ..util import flight_recorder

        flight_recorder.instrument_group(self, "xla")

    def info(self) -> GroupInfo:
        return GroupInfo(self.group_name, self.world_size, self.rank, Backend.XLA)

    # ------------------------------------------------------------- plumbing
    def _global_from_local(self, tensor):
        """Treat each host's tensor as one shard along the leading axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        local = np.asarray(tensor)
        sharding = NamedSharding(self.mesh, P(("host",)))
        global_shape = (self.world_size, *local.shape)
        return jax.make_array_from_process_local_data(
            sharding, local[None], global_shape
        )

    def _local_from_global(self, arr):
        shards = arr.addressable_shards
        return np.asarray(shards[0].data)

    def _build(self, key, body, out_replicated=False):
        import jax
        from jax.sharding import PartitionSpec as P

        fn = self._fn_cache.get(key)
        if fn is None:
            out_spec = P() if out_replicated else P(("host",))
            fn = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=(P(("host",)),),
                    out_specs=out_spec, check_vma=False,
                )
            )
            self._fn_cache[key] = fn
        return fn

    def _build2(self, key, body):
        """shard_map over the (dcn, ici, device) three-axis view — the
        host axis split into inter-slice x intra-slice for the two-level
        algorithms; the per-host device axis stays replicated exactly as
        in the flat path."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        fn = self._fn_cache.get(key)
        if fn is None:
            if self._mesh3 is None:
                topo = self.topology
                self._mesh3 = Mesh(
                    np.array(jax.devices()).reshape(
                        topo.dcn_size, topo.ici_size, self.devices_per_host
                    ),
                    ("dcn", "ici", "device"),
                )
            spec = P(("dcn", "ici"))
            fn = jax.jit(jax.shard_map(
                body, mesh=self._mesh3, in_specs=(spec,),
                out_specs=spec, check_vma=False,
            ))
            self._fn_cache[key] = fn
        return fn

    # ----------------------------------------------------- tuner plumbing
    def _tuner_sync(self, vec: np.ndarray) -> np.ndarray:
        """Allreduce-MEAN of the tuner's measurement table across group
        members, via a dedicated always-flat psum (never routed through
        the selection layer — selection must not depend on itself).
        Called at deterministic commit points, so every member reaches
        this collective at the same point in its call sequence."""
        import jax

        g = self._global_from_local(np.asarray(vec, np.float64))

        def body(x):
            return jax.lax.psum(x, "host")

        out = self._build(("tuner_sync", g.shape), body)(g)
        return self._local_from_global(out)[0] / self.world_size

    def _select(self, op: str, nbytes: int, quantized: bool) -> str:
        from .tuner import select_for_group

        return select_for_group(
            self, op, nbytes, quantized,
            sync=self._tuner_sync if self.world_size > 1 else None,
        )

    def _resolve_quantized(self, op: ReduceOp, dtype, quantized) -> bool:
        from .algorithms import resolve_quantized

        return resolve_quantized(op, dtype, quantized)

    # ------------------------------------------------------------------ ops
    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM,
                  quantized: bool = None):
        import jax
        import jax.numpy as jnp

        from . import algorithms as alg
        from ..core.config import GlobalConfig

        g = self._global_from_local(tensor)
        quantized = self._resolve_quantized(op, g.dtype, quantized)
        self._last_decision = None

        if op != ReduceOp.SUM:
            def body(x):
                red = {
                    ReduceOp.MAX: jax.lax.pmax,
                    ReduceOp.MIN: jax.lax.pmin,
                    ReduceOp.MEAN: jax.lax.pmean,
                }.get(op)
                if red is None:  # PRODUCT
                    return jnp.prod(
                        jax.lax.all_gather(x[0], "host"), axis=0
                    )[None]
                return red(x, "host")

            out = self._build(("ar", op, g.shape, str(g.dtype)), body)(g)
            return self._local_from_global(out)[0]

        nbytes = g.nbytes // max(1, self.world_size)
        algo = self._select("allreduce", nbytes, quantized)
        n = self.world_size
        topo = self.topology
        block = GlobalConfig.collective_quant_block_size

        if algo in (alg.TWO_LEVEL, alg.TWO_LEVEL_Q8):
            def body(x):
                return alg.two_level_allreduce(
                    x[0], "ici", "dcn", topo.ici_size,
                    quantized=(algo == alg.TWO_LEVEL_Q8), block_size=block,
                )[None]

            out = self._build2(
                ("ar2", algo, block, g.shape, str(g.dtype)), body
            )(g)
        else:
            def body(x):
                if algo == alg.RING:
                    return alg.ring_allreduce(x[0], "host", n)[None]
                if algo == alg.TREE:
                    return alg.tree_allreduce(x[0], "host", n)[None]
                if algo == alg.FLAT_Q8:
                    return alg.quantized_allreduce(
                        x[0], "host", block_size=block
                    )[None]
                return jax.lax.psum(x, "host")

            out = self._build(
                ("ar", op, algo, block if quantized else 0, g.shape,
                 str(g.dtype)),
                body,
            )(g)
        return self._local_from_global(out)[0]

    def allgather(self, tensor):
        import jax

        from . import algorithms as alg

        g = self._global_from_local(tensor)
        self._last_decision = None
        algo = self._select(
            "allgather", g.nbytes // max(1, self.world_size), False
        )
        n = self.world_size

        def body(x):
            if algo == alg.RING:
                return alg.ring_allgather(x[0], "host", n)[None]
            return jax.lax.all_gather(x[0], "host")[None]

        out = self._build(("ag", algo, g.shape, str(g.dtype)), body)(g)
        return list(self._local_from_global(out)[0])

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        import jax
        import jax.numpy as jnp

        from . import algorithms as alg

        g = self._global_from_local(tensor)
        n = self.world_size
        self._last_decision = None
        algo = alg.FLAT
        if op == ReduceOp.SUM:
            algo = self._select(
                "reducescatter", g.nbytes // max(1, n), False
            )

        def body(x):
            if op == ReduceOp.SUM:
                if algo == alg.RING:
                    return alg.ring_reducescatter(x[0], "host", n)[None]
                return jax.lax.psum_scatter(
                    x[0], "host", scatter_dimension=0, tiled=True
                )[None]
            gathered = jax.lax.all_gather(x[0], "host")
            reducer = {
                ReduceOp.MAX: jnp.max,
                ReduceOp.MIN: jnp.min,
                ReduceOp.MEAN: jnp.mean,
                ReduceOp.PRODUCT: jnp.prod,
            }[op]
            red = reducer(gathered, axis=0)
            rank = jax.lax.axis_index("host")
            chunk = red.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(red, rank * chunk, chunk)[None]

        out = self._build(("rs", op, algo, g.shape, str(g.dtype)), body)(g)
        return self._local_from_global(out)[0]

    def broadcast(self, tensor, src_rank: int = 0):
        import jax

        g = self._global_from_local(tensor)

        def body(x):
            return jax.lax.all_gather(x[0], "host")[src_rank][None]

        out = self._build(("bc", src_rank, g.shape, str(g.dtype)), body)(g)
        return self._local_from_global(out)[0]

    def alltoall(self, tensor):
        import jax

        g = self._global_from_local(tensor)

        def body(x):
            return jax.lax.all_to_all(
                x, "host", split_axis=1, concat_axis=0, tiled=False
            ).reshape(x.shape)

        out = self._build(("a2a", g.shape, str(g.dtype)), body)(g)
        return self._local_from_global(out)[0]

    def ppermute(self, tensor, shift: int = 1):
        import jax

        g = self._global_from_local(tensor)
        n = self.world_size
        perm = [(i, (i + shift) % n) for i in range(n)]

        def body(x):
            return jax.lax.ppermute(x, "host", perm)

        out = self._build(("pp", shift, g.shape, str(g.dtype)), body)(g)
        return self._local_from_global(out)[0]

    def barrier(self):
        self.allreduce(np.zeros((1,), np.float32))

    def shutdown(self):
        # jax.distributed can only be initialized once per process; keep the
        # runtime up but drop the cache.
        self._fn_cache.clear()
