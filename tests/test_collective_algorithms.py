"""Collective algorithm library: parity vs the flat sum, quantized
error bounds, and selection through the public group API (8-device
virtual CPU mesh, 2 "slices" of 4 for the two-level paths)."""

import numpy as np
import pytest

import ray_tpu.collective as col
from ray_tpu.collective import algorithms as alg
from ray_tpu.collective.tuner import reset_tuner
from ray_tpu.collective.types import Topology


N = 8


def _mesh1():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("world",))


def _mesh2():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dcn", "ici"))


def _run1(body, stack):
    """shard_map ``body`` over the 1-D world mesh; returns (N, ...)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh1()
    g = jax.device_put(stack, NamedSharding(mesh, P("world")))
    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("world"),),
        out_specs=P("world"), check_vma=False,
    ))
    return np.asarray(f(g))


def _run2(body, stack):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh2()
    spec = P(("dcn", "ici"))
    g = jax.device_put(stack, NamedSharding(mesh, spec))
    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,),
        out_specs=spec, check_vma=False,
    ))
    return np.asarray(f(g))


@pytest.fixture(scope="module")
def int_stack():
    """Integer-valued fp32 payload: every reassociation sums exactly, so
    parity asserts can demand bit equality."""
    rng = np.random.default_rng(7)
    return rng.integers(-9, 10, size=(N, 37, 5)).astype(np.float32)


# ------------------------------------------------------------- parity
class TestAllreduceParity:
    def test_ring_matches_flat(self, int_stack):
        ref = int_stack.sum(axis=0)
        out = _run1(
            lambda x: alg.ring_allreduce(x[0], "world", N)[None], int_stack
        )
        for r in range(N):
            np.testing.assert_array_equal(out[r], ref)

    def test_tree_matches_flat(self, int_stack):
        ref = int_stack.sum(axis=0)
        out = _run1(
            lambda x: alg.tree_allreduce(x[0], "world", N)[None], int_stack
        )
        for r in range(N):
            np.testing.assert_array_equal(out[r], ref)

    def test_two_level_matches_flat(self, int_stack):
        ref = int_stack.sum(axis=0)
        out = _run2(
            lambda x: alg.two_level_allreduce(x[0], "ici", "dcn", 4)[None],
            int_stack,
        )
        for r in range(N):
            np.testing.assert_array_equal(out[r], ref)

    def test_ring_reducescatter_matches_psum_scatter(self):
        stack = np.stack([
            np.arange(N * 3, dtype=np.float32) + i for i in range(N)
        ])
        ref = stack.sum(axis=0)
        out = _run1(
            lambda x: alg.ring_reducescatter(x[0], "world", N)[None], stack
        )
        for r in range(N):
            np.testing.assert_array_equal(out[r], ref[r * 3:(r + 1) * 3])

    def test_ring_allgather_matches_all_gather(self, int_stack):
        small = int_stack[:, :4, :2].copy()
        out = _run1(
            lambda x: alg.ring_allgather(x[0], "world", N)[None], small
        )
        for r in range(N):
            np.testing.assert_array_equal(out[r], small)

    def test_odd_sizes_pad_correctly(self):
        # 13 elements: not divisible by 8 — padding must round-trip.
        stack = np.stack([
            np.arange(13, dtype=np.float32) * (i + 1) for i in range(N)
        ])
        ref = stack.sum(axis=0)
        for body in (
            lambda x: alg.ring_allreduce(x[0], "world", N)[None],
            lambda x: alg.tree_allreduce(x[0], "world", N)[None],
        ):
            out = _run1(body, stack)
            for r in range(N):
                np.testing.assert_array_equal(out[r], ref)


# --------------------------------------------------- quantized numerics
def _quant_bound(stack, block_size):
    """Per-block error bound: each rank's round-to-nearest error is at
    most scale/2 = amax/254 per element; contributions add."""
    n, size = stack.shape[0], stack[0].size
    pad = (-size) % block_size
    flat = np.pad(stack.reshape(n, -1), ((0, 0), (0, pad)))
    amax = np.abs(flat.reshape(n, -1, block_size)).max(axis=2)  # (n, nb)
    return amax.sum(axis=0) / 254.0  # per-block bound


class TestQuantizedAllreduce:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("block_size", [64, 256])
    def test_error_bound_random(self, dtype, block_size):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        stack32 = rng.normal(size=(N, 700)).astype(np.float32)
        stack = np.asarray(jnp.asarray(stack32, dtype=dtype))
        # Reference: exact fp32 sum of the (dtype-rounded) inputs.
        ref = np.asarray(stack, np.float32).sum(axis=0)
        out = _run1(
            lambda x: alg.quantized_allreduce(
                x[0], "world", block_size=block_size
            )[None],
            stack,
        ).astype(np.float32)
        bound = _quant_bound(np.asarray(stack, np.float32), block_size)
        # bf16 output rounding adds at most one ulp of the result.
        slack = (np.abs(ref) * 2 ** -7 + 1e-6) if dtype == "bfloat16" \
            else 1e-6
        size, pad = 700, (-700) % block_size
        err = np.abs(out[0] - ref)
        err_blocks = np.pad(err, (0, pad)).reshape(-1, block_size)
        slack_blocks = np.pad(np.atleast_1d(slack) * np.ones(size),
                              (0, pad)).reshape(-1, block_size)
        assert (
            err_blocks.max(axis=1)
            <= bound + slack_blocks.max(axis=1)
        ).all()

    def test_all_zero_block(self):
        stack = np.zeros((N, 512), np.float32)
        out = _run1(
            lambda x: alg.quantized_allreduce(x[0], "world")[None], stack
        )
        np.testing.assert_array_equal(out[0], np.zeros(512, np.float32))

    def test_single_outlier_block(self):
        # One huge value per block: the outlier must survive exactly-ish
        # (it IS the amax, so it quantizes to +/-127 exactly), while the
        # tiny neighbors absorb the scale's granularity.
        stack = np.full((N, 256), 1e-4, np.float32)
        stack[:, 17] = 1000.0
        ref = stack.sum(axis=0)
        out = _run1(
            lambda x: alg.quantized_allreduce(x[0], "world")[None], stack
        )
        assert abs(out[0][17] - ref[17]) <= N * 1000.0 / 254.0
        bound = _quant_bound(stack, 256)[0]
        assert np.abs(out[0] - ref).max() <= bound + 1e-6

    def test_two_level_quantized_bound(self):
        rng = np.random.default_rng(11)
        stack = rng.normal(size=(N, 600)).astype(np.float32)
        ref = stack.sum(axis=0)
        out = _run2(
            lambda x: alg.two_level_allreduce(
                x[0], "ici", "dcn", 4, quantized=True
            )[None],
            stack,
        )
        # Only the DCN hop quantizes, and it runs AFTER the ICI
        # reduce-scatter: each ici-rank quantizes its own 150-element
        # chunk of the slice partial (the chunk is smaller than a
        # quantization block, so each chunk is one block with its own
        # amax).  Bound accordingly, per chunk.
        partials = np.stack([stack[:4].sum(0), stack[4:].sum(0)])
        chunks = partials.reshape(2, 4, 150)  # (slice, ici chunk, elem)
        bound = np.abs(chunks).max(axis=2).sum(axis=0) / 254.0  # (4,)
        err = np.abs(out[0] - ref).reshape(4, 150).max(axis=1)
        assert (err <= bound + 1e-5).all()

    def test_exact_sum_when_quantization_off(self):
        """The satellite's contract: default allreduce is EXACT — no
        quantization unless opted in."""
        from ray_tpu.core.config import GlobalConfig

        assert GlobalConfig.collective_quantized_allreduce is False
        reset_tuner()
        g = col.init_local_group("exact-t")
        try:
            tensors = [
                np.full((64,), 2.0 ** -24 * (i + 1), np.float32)
                for i in range(g.world_size)
            ]
            n = g.world_size
            # Exploration covers every candidate algorithm: each must
            # return the bit-exact sum (values are exact in fp32).
            expected = np.asarray(tensors).sum(axis=0)
            for _ in range(8):
                out = g.allreduce(tensors)
                for o in out:
                    np.testing.assert_array_equal(np.asarray(o), expected)
        finally:
            col.destroy_collective_group("exact-t")

    def test_quantized_rejects_non_sum_and_int(self):
        from ray_tpu.collective.types import ReduceOp

        reset_tuner()
        g = col.init_local_group("qrej-t")
        try:
            x = [np.ones(8, np.float32)] * g.world_size
            with pytest.raises(ValueError, match="SUM"):
                g.allreduce(x, ReduceOp.MAX, quantized=True)
            xi = [np.ones(8, np.int32)] * g.world_size
            with pytest.raises(ValueError, match="float"):
                g.allreduce(xi, quantized=True)
        finally:
            col.destroy_collective_group("qrej-t")

    def test_np_roundtrip_preserves_dtype_and_shape(self):
        import jax.numpy as jnp

        for dtype in (np.float32, jnp.bfloat16):
            a = np.asarray(
                jnp.asarray(
                    np.random.default_rng(0).normal(size=(9, 13)), dtype
                )
            )
            q, scales, size = alg.quantize_blocks_np(a, 64)
            assert q.dtype == np.int8 and scales.dtype == np.float32
            back = alg.dequantize_blocks_np(q, scales, size, a.shape,
                                            a.dtype)
            assert back.shape == a.shape and back.dtype == a.dtype
            err = np.abs(
                np.asarray(back, np.float32) - np.asarray(a, np.float32)
            )
            amax = np.abs(np.asarray(a, np.float32)).max()
            assert err.max() <= amax / 254.0 + amax * 2 ** -7


# --------------------------------------------- selection via group API
class TestGroupSelection:
    def test_exploration_covers_candidates_and_commits(self):
        reset_tuner()
        g = col.init_local_group("sel-t", slice_size=4)
        assert g.topology == Topology(8, 4)
        assert g.topology.kind == "dcn" and g.topology.is_two_level
        try:
            x = [np.full((2048,), float(i + 1), np.float32)
                 for i in range(g.world_size)]
            expected = sum(range(1, g.world_size + 1))
            for _ in range(12):
                out = g.allreduce(x)
                assert all(
                    float(np.asarray(o)[0]) == expected for o in out
                )
            stats = col.collective_stats()["tuner"]
            row = next(
                v for k, v in stats.items()
                if v["op"] == "allreduce" and not v["quantized"]
            )
            # Every eligible algorithm explored, then a commitment.
            assert set(row["algorithms"]) == {
                "flat", "ring", "tree", "two_level"
            }
            assert all(
                d["attempts"] >= 2 for d in row["algorithms"].values()
            )
            assert row["chosen"] in row["algorithms"]
            assert row["topology"] == "dcn"
        finally:
            col.destroy_collective_group("sel-t")

    def test_quantized_call_uses_q8_bucket(self):
        reset_tuner()
        g = col.init_local_group("q8-t", slice_size=4)
        try:
            x = [np.ones((512,), np.float32)] * g.world_size
            out = g.allreduce(x, quantized=True)
            assert float(np.asarray(out[0])[0]) == pytest.approx(
                g.world_size, abs=g.world_size / 127,
            )
            stats = col.collective_stats()["tuner"]
            qrows = [k for k, v in stats.items() if v["quantized"]]
            assert qrows and all(k.endswith("|q8") for k in qrows)
        finally:
            col.destroy_collective_group("q8-t")

    def test_unselected_ops_do_not_inherit_decisions(self):
        """broadcast/alltoall run outside the selection layer: they must
        not be recorded under the previous allreduce's algorithm, feed
        the tuner a phantom bucket, or count as quantized."""
        from ray_tpu.util import metric_registry, metrics

        def _quant_ops():
            with metrics._lock:
                return sum(
                    ent["value"] for (name, _t), ent in metrics._local.items()
                    if name == metric_registry.COLLECTIVE_QUANTIZED_OPS_TOTAL
                )

        reset_tuner()
        g = col.init_local_group("leak-t")
        try:
            x = [np.ones((512,), np.float32)] * g.world_size
            g.allreduce(x, quantized=True)
            before = _quant_ops()
            g.broadcast(x, src_rank=1)
            g.alltoall([np.arange(8, dtype=np.float32)] * g.world_size)
            stats = col.collective_stats()["tuner"]
            assert not any(
                v["op"] in ("broadcast", "alltoall") for v in stats.values()
            )
            assert _quant_ops() == before
        finally:
            col.destroy_collective_group("leak-t")

    def test_quantized_request_lowered_to_flat_not_counted(self):
        """quantized=True on a world-1 group lowers to exact flat (the
        only candidate) — the quantized counters must not move."""
        import jax

        from ray_tpu.util import metric_registry, metrics

        def _quant_ops():
            with metrics._lock:
                return sum(
                    ent["value"] for (name, _t), ent in metrics._local.items()
                    if name == metric_registry.COLLECTIVE_QUANTIZED_OPS_TOTAL
                )

        reset_tuner()
        g = col.init_local_group("qflat-t", devices=jax.devices()[:1])
        try:
            before = _quant_ops()
            out = g.allreduce([np.ones((64,), np.float32)], quantized=True)
            np.testing.assert_array_equal(
                np.asarray(out[0]), np.ones(64, np.float32)
            )
            assert _quant_ops() == before
        finally:
            col.destroy_collective_group("qflat-t")

    def test_world1_quick_path(self):
        import jax

        reset_tuner()
        g = col.init_local_group("one-t", devices=jax.devices()[:1])
        try:
            out = g.allreduce([np.arange(4.0, dtype=np.float32)])
            np.testing.assert_array_equal(
                np.asarray(out[0]), np.arange(4.0, dtype=np.float32)
            )
            row = next(iter(col.collective_stats()["tuner"].values()))
            assert row["chosen"] == "flat"  # single candidate self-commits
        finally:
            col.destroy_collective_group("one-t")

    def test_topology_validation(self):
        with pytest.raises(ValueError, match="divisible"):
            Topology(8, 3)
        assert Topology(8, 8).kind == "ici"
        assert Topology(8, 1).kind == "dcn"
        assert not Topology(8, 1).is_two_level
        assert Topology(8, 4).dcn_size == 2
