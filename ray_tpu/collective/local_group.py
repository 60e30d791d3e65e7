"""Single-controller collective group over this process's local devices.

The TPU-native replacement for the reference's single-process multi-GPU
collectives (ray ``util/collective``'s ``*_multigpu`` variants backed by
cupy-NCCL, ``collective_group/nccl_collective_group.py:121``): here every op
is a jitted ``shard_map`` over a 1-D device mesh, so allreduce lowers to one
XLA ``psum`` riding ICI — no per-peer streams/events to manage, the compiler
schedules the ring.

Input convention: a list of per-rank arrays (rank i's tensor lives on local
device i), or a single already-sharded global ``jax.Array``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .types import Backend, GroupInfo, ReduceOp


class LocalXlaGroup:
    """Collective group whose ranks are this process's local devices."""

    def __init__(self, group_name: str, devices: Sequence = None,
                 slice_size: int = None):
        import jax

        self.group_name = group_name
        self.devices = list(devices) if devices is not None else jax.devices()
        self.world_size = len(self.devices)
        from jax.sharding import Mesh

        from .types import Topology

        # ``slice_size``: devices per ICI slice.  Default: every device in
        # one slice (pure-ICI topology).  A multi-slice local group (e.g.
        # megascale hosts, or a CPU mesh standing in for a 2-slice DCN
        # fabric in tests) unlocks the two-level algorithms.
        self.topology = Topology(self.world_size,
                                 slice_size or self.world_size)
        self.mesh = Mesh(np.array(self.devices), ("world",))
        self._mesh2 = None  # (dcn, ici) view, built on first two-level op
        self._fn_cache: Dict[tuple, object] = {}
        self._last_decision = None  # tuner decision of the most recent op
        # Flight recorder: op/bytes/world-size/duration + achieved-bandwidth
        # capture on every collective (no-op when disabled).
        from ..util import flight_recorder

        flight_recorder.instrument_group(self, "local")

    def info(self, rank: int = 0) -> GroupInfo:
        return GroupInfo(self.group_name, self.world_size, rank, Backend.LOCAL)

    # ------------------------------------------------------------- plumbing
    def _stack(self, tensors: List):
        """Place rank i's tensor on device i and form a global array sharded
        along the leading (world) axis — no host round-trip for arrays that
        are already on the right device."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        assert len(tensors) == self.world_size, (
            f"expected {self.world_size} per-rank tensors, got {len(tensors)}"
        )
        shape = tensors[0].shape
        dtype = tensors[0].dtype if hasattr(tensors[0], "dtype") else None
        shards = [
            jax.device_put(np.asarray(t)[None], d)
            for t, d in zip(tensors, self.devices)
        ]
        sharding = NamedSharding(self.mesh, P("world"))
        return jax.make_array_from_single_device_arrays(
            (self.world_size, *shape), sharding, shards
        )

    def _unstack(self, global_arr) -> List:
        return [s.data[0] for s in sorted(
            global_arr.addressable_shards, key=lambda s: s.index[0].start or 0
        )]

    def _shard_map(self, fn, out_spec_rank_axis=True):
        import jax
        from jax.sharding import PartitionSpec as P


        in_spec = P("world")
        out_spec = P("world") if out_spec_rank_axis else P()
        return jax.jit(
            jax.shard_map(
                fn, mesh=self.mesh, in_specs=(in_spec,),
                out_specs=out_spec, check_vma=False,
            )
        )

    def _cached(self, key, builder):
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = builder()
            self._fn_cache[key] = fn
        return fn

    def _shard_map2(self, fn):
        """shard_map over the (dcn, ici) two-level view of the same
        devices — row-major reshape keeps device order, so resharding
        from the 1-D mesh is layout-only."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        if self._mesh2 is None:
            topo = self.topology
            self._mesh2 = Mesh(
                np.array(self.devices).reshape(topo.dcn_size, topo.ici_size),
                ("dcn", "ici"),
            )
        spec = P(("dcn", "ici"))
        return jax.jit(jax.shard_map(
            fn, mesh=self._mesh2, in_specs=(spec,),
            out_specs=spec, check_vma=False,
        ))

    def _select(self, op: str, per_rank_nbytes: int, quantized: bool) -> str:
        """Tuner decision for one op call (single-controller group:
        every rank lives in this process, so the tuner's measurement
        table needs no cross-member sync)."""
        from .tuner import select_for_group

        return select_for_group(self, op, per_rank_nbytes, quantized)

    def _resolve_quantized(self, op: ReduceOp, dtype, quantized) -> bool:
        from .algorithms import resolve_quantized

        return resolve_quantized(op, dtype, quantized)

    @staticmethod
    def _quant_block() -> int:
        from ..core.config import GlobalConfig

        return GlobalConfig.collective_quant_block_size

    # ------------------------------------------------------------------ ops
    def allreduce(self, tensors: List, op: ReduceOp = ReduceOp.SUM,
                  quantized: bool = None) -> List:
        import jax
        import jax.numpy as jnp

        from . import algorithms as alg

        g = self._stack(tensors)
        quantized = self._resolve_quantized(op, g.dtype, quantized)
        self._last_decision = None

        if op != ReduceOp.SUM:
            # Non-SUM reductions keep the flat lowering (no algorithm
            # family implements reassociation-safe MAX/MIN/MEAN/PRODUCT).
            def build():
                def body(x):  # x: (1, *shape) per rank
                    if op == ReduceOp.PRODUCT:
                        # No pprod primitive: reduce via allgather.
                        gathered = jax.lax.all_gather(x[0], "world")
                        return jnp.prod(gathered, axis=0)[None]
                    red = {
                        ReduceOp.MAX: jax.lax.pmax,
                        ReduceOp.MIN: jax.lax.pmin,
                        ReduceOp.MEAN: jax.lax.pmean,
                    }[op]
                    return red(x, "world")

                return self._shard_map(body)

            out = self._cached(("ar", op, g.shape, str(g.dtype)), build)(g)
            return self._unstack(out)

        per_rank_nbytes = g.nbytes // max(1, self.world_size)
        algo = self._select("allreduce", per_rank_nbytes, quantized)
        n = self.world_size
        topo = self.topology
        block = self._quant_block()

        def build():
            if algo in (alg.TWO_LEVEL, alg.TWO_LEVEL_Q8):
                def body(x):
                    return alg.two_level_allreduce(
                        x[0], "ici", "dcn", topo.ici_size,
                        quantized=(algo == alg.TWO_LEVEL_Q8),
                        block_size=block,
                    )[None]

                return self._shard_map2(body)

            def body(x):
                if algo == alg.RING:
                    return alg.ring_allreduce(x[0], "world", n)[None]
                if algo == alg.TREE:
                    return alg.tree_allreduce(x[0], "world", n)[None]
                if algo == alg.FLAT_Q8:
                    return alg.quantized_allreduce(
                        x[0], "world", block_size=block
                    )[None]
                return jax.lax.psum(x, "world")

            return self._shard_map(body)

        out = self._cached(
            ("ar", op, algo, block if quantized else 0, g.shape,
             str(g.dtype)),
            build,
        )(g)
        return self._unstack(out)

    def allgather(self, tensors: List) -> List[List]:
        import jax

        from . import algorithms as alg

        g = self._stack(tensors)
        self._last_decision = None
        per_rank_nbytes = g.nbytes // max(1, self.world_size)
        algo = self._select("allgather", per_rank_nbytes, False)
        n = self.world_size

        def build():
            def body(x):
                if algo == alg.RING:
                    return alg.ring_allgather(x[0], "world", n)[None]
                return jax.lax.all_gather(x[0], "world")[None]

            return self._shard_map(body)

        out = self._cached(("ag", algo, g.shape, str(g.dtype)), build)(g)
        per_rank = self._unstack(out)
        return [[r[i] for i in range(self.world_size)] for r in per_rank]

    def reducescatter(self, tensors: List, op: ReduceOp = ReduceOp.SUM) -> List:
        """Rank i receives chunk i of the elementwise reduction (inputs must
        be divisible by world_size along axis 0)."""
        import jax
        import jax.numpy as jnp

        from . import algorithms as alg

        g = self._stack(tensors)
        n = self.world_size
        self._last_decision = None
        algo = alg.FLAT
        if op == ReduceOp.SUM:
            per_rank_nbytes = g.nbytes // max(1, n)
            algo = self._select("reducescatter", per_rank_nbytes, False)

        def build():
            def body(x):
                if op == ReduceOp.SUM:
                    if algo == alg.RING:
                        return alg.ring_reducescatter(x[0], "world", n)[None]
                    # The fast path: one XLA reduce-scatter over ICI.
                    return jax.lax.psum_scatter(
                        x[0], "world", scatter_dimension=0, tiled=True
                    )[None]
                gathered = jax.lax.all_gather(x[0], "world")  # (n, *shape)
                reducer = {
                    ReduceOp.MAX: jnp.max,
                    ReduceOp.MIN: jnp.min,
                    ReduceOp.MEAN: jnp.mean,
                    ReduceOp.PRODUCT: jnp.prod,
                }[op]
                red = reducer(gathered, axis=0)
                rank = jax.lax.axis_index("world")
                chunk = red.shape[0] // n
                return jax.lax.dynamic_slice_in_dim(red, rank * chunk, chunk)[None]

            return self._shard_map(body)

        out = self._cached(("rs", op, algo, g.shape, str(g.dtype)), build)(g)
        return self._unstack(out)

    def broadcast(self, tensors: List, src_rank: int = 0) -> List:
        import jax

        g = self._stack(tensors)

        def build():
            def body(x):
                gathered = jax.lax.all_gather(x[0], "world")
                return gathered[src_rank][None]

            return self._shard_map(body)

        out = self._cached(("bc", src_rank, g.shape, str(g.dtype)), build)(g)
        return self._unstack(out)

    def alltoall(self, tensors: List) -> List:
        """Rank i's output chunk j = rank j's input chunk i (axis 0)."""
        import jax

        g = self._stack(tensors)

        def build():
            def body(x):
                return jax.lax.all_to_all(
                    x, "world", split_axis=1, concat_axis=0, tiled=False
                ).reshape(x.shape)

            return self._shard_map(body)

        out = self._cached(("a2a", g.shape, str(g.dtype)), build)(g)
        return self._unstack(out)

    def sendrecv_ring(self, tensors: List, shift: int = 1) -> List:
        """ppermute ring shift: rank i's tensor goes to rank (i+shift)%n."""
        import jax

        g = self._stack(tensors)
        n = self.world_size

        def build():
            perm = [(i, (i + shift) % n) for i in range(n)]

            def body(x):
                return jax.lax.ppermute(x, "world", perm)

            return self._shard_map(body)

        out = self._cached(("pp", shift, g.shape, str(g.dtype)), build)(g)
        return self._unstack(out)

    def barrier(self):
        import numpy as _np

        self.allreduce([_np.zeros((1,), _np.float32)] * self.world_size)
