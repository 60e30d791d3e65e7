"""Online collective autotuner: bucketing, explore->commit->decaying
re-probe, member sync at commit points, observability (stats / cluster
merge / metrics), and the train-layer opt-in threading."""

import numpy as np
import pytest

import ray_tpu.collective as col
from ray_tpu.collective import algorithms as alg
from ray_tpu.collective.tuner import (
    CollectiveTuner,
    get_tuner,
    heuristic_choice,
    reset_tuner,
    size_bucket,
)
from ray_tpu.collective.types import Topology


ICI8 = Topology(8, 8)
DCN8 = Topology(8, 4)
CANDS = alg.allreduce_candidates(8, DCN8)


# ------------------------------------------------------------- bucketing
class TestBuckets:
    def test_size_bucket_edges(self):
        assert size_bucket(1) == "le4KiB"
        assert size_bucket(4096) == "le4KiB"
        assert size_bucket(4097) == "le64KiB"
        assert size_bucket(64 << 10) == "le64KiB"
        assert size_bucket(1 << 20) == "le1MiB"
        assert size_bucket(16 << 20) == "le16MiB"
        assert size_bucket((16 << 20) + 1) == "gt16MiB"

    def test_candidates(self):
        assert alg.allreduce_candidates(1, Topology(1, 1)) == (alg.FLAT,)
        assert alg.TREE in alg.allreduce_candidates(8, ICI8)
        assert alg.TREE not in alg.allreduce_candidates(6, Topology(6, 6))
        assert alg.TWO_LEVEL in alg.allreduce_candidates(8, DCN8)
        assert alg.TWO_LEVEL not in alg.allreduce_candidates(8, ICI8)
        assert alg.allreduce_candidates(8, DCN8, quantized=True) == (
            alg.TWO_LEVEL_Q8, alg.FLAT_Q8,
        )

    def test_heuristic_table(self):
        c_ici = alg.allreduce_candidates(8, ICI8)
        assert heuristic_choice("allreduce", 1024, 8, ICI8, c_ici) \
            == alg.FLAT
        assert heuristic_choice("allreduce", 512 << 10, 8, ICI8, c_ici) \
            == alg.TREE
        assert heuristic_choice("allreduce", 64 << 20, 8, ICI8, c_ici) \
            == alg.RING
        c_dcn = alg.allreduce_candidates(8, DCN8)
        assert heuristic_choice("allreduce", 1 << 20, 8, DCN8, c_dcn) \
            == alg.TWO_LEVEL
        assert heuristic_choice("allreduce", 1024, 8, DCN8, c_dcn) \
            == alg.FLAT


# ----------------------------------------------------- selection machine
def _drive(tuner, bw_by_algo, calls, nbytes=1 << 20, sync=None):
    """Run the select->observe loop with synthetic bandwidths."""
    decisions = []
    for _ in range(calls):
        dec = tuner.select("allreduce", nbytes, 8, DCN8, CANDS, sync=sync)
        tuner.observe("allreduce", nbytes, 8, DCN8, dec["algo"],
                      bw_by_algo[dec["algo"]])
        decisions.append(dec)
    return decisions


class TestSelection:
    def test_explores_all_then_commits_to_measured_best(self):
        t = CollectiveTuner(enabled=True)
        bw = {"flat": 1e9, "ring": 5e9, "tree": 2e9, "two_level": 3e9}
        decs = _drive(t, bw, 12)
        row = next(iter(t.stats().values()))
        assert row["chosen"] == "ring"
        # Steady state rides the winner.
        assert decs[-1]["algo"] == "ring" and not decs[-1]["explored"]
        assert {d["algo"] for d in decs[:8]} == set(CANDS)

    def test_decaying_reprobe_and_recommit_flip(self):
        t = CollectiveTuner(enabled=True)
        bw = {"flat": 1e9, "ring": 5e9, "tree": 2e9, "two_level": 3e9}
        _drive(t, bw, 10)
        assert next(iter(t.stats().values()))["chosen"] == "ring"
        # The fabric changes: ring degrades, two_level now wins.  The
        # decaying re-probe must eventually flip the commitment.
        bw2 = {"flat": 1e9, "ring": 0.5e9, "tree": 2e9, "two_level": 9e9}
        _drive(t, bw2, 400)
        row = next(iter(t.stats().values()))
        assert row["chosen"] == "two_level"
        assert row["commits"] >= 2
        # Re-probes decay: far fewer explorations than calls.
        assert row["explorations"] < row["calls"] / 4

    def test_reprobe_intervals_decay_geometrically(self):
        t = CollectiveTuner(enabled=True)
        bw = {c: 1e9 for c in CANDS}
        decs = _drive(t, bw, 300)
        explore_idx = [i for i, d in enumerate(decs) if d["explored"]]
        post_commit = [i for i in explore_idx if i > 8]
        gaps = np.diff(post_commit)
        assert (gaps[1:] >= gaps[:-1]).all()  # non-shrinking gaps

    def test_disabled_rides_heuristic(self):
        t = CollectiveTuner(enabled=False)
        decs = _drive(t, {c: 1e9 for c in CANDS}, 6)
        assert all(d["algo"] == alg.TWO_LEVEL for d in decs)  # heuristic
        assert not any(d["explored"] for d in decs)

    def test_no_observations_commits_to_heuristic(self):
        t = CollectiveTuner(enabled=True)
        for _ in range(12):
            t.select("allreduce", 1 << 20, 8, DCN8, CANDS)  # no observe
        row = next(iter(t.stats().values()))
        assert row["chosen"] == alg.TWO_LEVEL  # the static table's pick

    def test_sync_called_at_commit_and_overrides_argmax(self):
        calls = []

        def sync(vec):
            calls.append(vec.copy())
            # Pretend the OTHER members measured flat as by far the
            # best: zero out everything else's bw sums.
            k = len(CANDS)
            out = np.zeros_like(vec)
            flat_i = CANDS.index(alg.FLAT)
            out[flat_i] = 100e9 * vec[k + flat_i]  # bw_sum
            out[k:] = vec[k:]  # counts unchanged
            return out

        t = CollectiveTuner(enabled=True)
        bw = {"flat": 1e9, "ring": 5e9, "tree": 2e9, "two_level": 3e9}
        _drive(t, bw, 12, sync=sync)
        assert calls, "sync must run at the commit point"
        assert len(calls[0]) == 2 * len(CANDS)
        assert next(iter(t.stats().values()))["chosen"] == alg.FLAT

    def test_deterministic_across_replicas(self):
        """Two members issuing the same call sequence make identical
        selections even with DIFFERENT local measurements, because
        commits ride the synced table."""
        results = []
        for noise in (1.0, 3.7):  # member-local measurement skew
            t = CollectiveTuner(enabled=True)

            def sync(vec):
                return vec  # stand-in: both members see the same table

            bw = {"flat": 1e9 * noise, "ring": 5e9 * noise,
                  "tree": 2e9 * noise, "two_level": 3e9 * noise}
            decs = _drive(t, bw, 20, sync=sync)
            results.append([d["algo"] for d in decs])
        # Explore order is call-sequence-deterministic (identical), and
        # the committed tail matches because argmax order survives scale.
        assert results[0] == results[1]


# ------------------------------------------------------- observability
class TestObservability:
    def test_collective_stats_has_tuner_table(self):
        reset_tuner()
        g = col.init_local_group("obs-t")
        try:
            x = [np.ones((1024,), np.float32)] * g.world_size
            for _ in range(10):
                g.allreduce(x)
            stats = col.collective_stats()
            assert stats["allreduce"]["ops"] >= 10
            row = next(
                v for v in stats["tuner"].values()
                if v["op"] == "allreduce"
            )
            assert row["calls"] >= 10
            assert sum(
                d["attempts"] for d in row["algorithms"].values()
            ) == row["calls"]
            # Samples flow back from the flight recorder (warm ops).
            assert sum(
                d["samples"] for d in row["algorithms"].values()
            ) > 0
        finally:
            col.destroy_collective_group("obs-t")

    def test_tuner_metrics_registered_and_recorded(self):
        from ray_tpu.util import metric_registry, metrics

        for name in (
            metric_registry.COLLECTIVE_ALGO_OPS_TOTAL,
            metric_registry.COLLECTIVE_TUNER_EXPLORATIONS_TOTAL,
            metric_registry.COLLECTIVE_TUNER_COMMITS_TOTAL,
            metric_registry.COLLECTIVE_TUNER_BEST_BANDWIDTH,
            metric_registry.COLLECTIVE_QUANTIZED_OPS_TOTAL,
            metric_registry.COLLECTIVE_QUANTIZED_BYTES_SAVED_TOTAL,
        ):
            assert metric_registry.is_registered(name)
        reset_tuner()
        g = col.init_local_group("met-t")
        try:
            x = [np.ones((4096,), np.float32)] * g.world_size
            for _ in range(10):
                g.allreduce(x)
            g.allreduce(x, quantized=True)
            with metrics._lock:
                names = {name for (name, _tags) in metrics._local}
            assert metric_registry.COLLECTIVE_ALGO_OPS_TOTAL in names
            assert (
                metric_registry.COLLECTIVE_QUANTIZED_OPS_TOTAL in names
            )
            assert (
                metric_registry.COLLECTIVE_QUANTIZED_BYTES_SAVED_TOTAL
                in names
            )
        finally:
            col.destroy_collective_group("met-t")

    def test_cluster_aggregated_view(self, ray_start_regular):
        """Satellite: collective_stats(cluster=True) merges per-group
        over workers via the owner-service metrics registry."""
        reset_tuner()
        g = col.init_local_group("clu-t")
        try:
            x = [np.ones((512,), np.float32)] * g.world_size
            for _ in range(4):
                g.allreduce(x)
            view = col.collective_stats(cluster=True)
            assert view["ops"]["allreduce"]["ops"] >= 4
            assert "clu-t" in view["groups"]
            assert view["groups"]["clu-t"]["allreduce"]["ops"] >= 4
            # Tuner decisions are visible from the driver.
            assert "allreduce" in view["algorithms"]
            assert sum(
                n for by_bucket in view["algorithms"]["allreduce"].values()
                for n in by_bucket.values()
            ) >= 4
        finally:
            col.destroy_collective_group("clu-t")


# ----------------------------------------------------- train threading
class TestTrainThreading:
    def test_collective_config_maps_to_system_config(self):
        from ray_tpu.train import CollectiveConfig

        cfg = CollectiveConfig(
            quantized_allreduce=True, quant_block_size=128, autotune=False
        )
        assert cfg.as_system_config() == {
            "collective_quantized_allreduce": True,
            "collective_quant_block_size": 128,
            "collective_autotune": False,
        }

    def test_global_default_opt_in(self):
        from ray_tpu.core.config import GlobalConfig

        reset_tuner()
        g = col.init_local_group("optin-t")
        try:
            x = [np.full((300,), 0.3, np.float32)] * g.world_size
            GlobalConfig.override(collective_quantized_allreduce=True)
            g.allreduce(x)
            stats = col.collective_stats()["tuner"]
            assert any(v["quantized"] for v in stats.values())
            # Int payloads fall back silently under the blanket opt-in.
            xi = [np.ones((8,), np.int32)] * g.world_size
            out = g.allreduce(xi)
            assert int(np.asarray(out[0])[0]) == g.world_size
        finally:
            GlobalConfig.override(collective_quantized_allreduce=False)
            col.destroy_collective_group("optin-t")

    def test_pipeline_grad_tree_quantization_roundtrip(self):
        import jax.numpy as jnp

        from ray_tpu.train.pipeline import (
            _dequantize_grad_tree,
            _quantize_grad_tree,
        )

        rng = np.random.default_rng(5)
        tree = {
            "w": rng.normal(size=(33, 9)).astype(np.float32),
            "b": np.asarray(
                jnp.asarray(rng.normal(size=(17,)), jnp.bfloat16)
            ),
            "step": np.int32(7),  # non-float leaf passes through
        }
        wire = _quantize_grad_tree(tree, 64)
        from ray_tpu.train.pipeline import _QuantizedLeaf

        assert isinstance(wire["w"], _QuantizedLeaf)
        assert wire["w"].q.dtype == np.int8
        assert wire["step"] == tree["step"]
        back = _dequantize_grad_tree(wire)
        assert back["w"].shape == tree["w"].shape
        assert back["b"].dtype == tree["b"].dtype
        amax = np.abs(tree["w"]).max()
        assert np.abs(back["w"] - tree["w"]).max() <= amax / 254.0 + 1e-6
        assert back["step"] == 7

    def test_pipeline_config_knob(self):
        from ray_tpu.train import PipelineConfig

        cfg = PipelineConfig(num_stages=2, num_microbatches=4,
                             quantized_grad_exchange=True,
                             quant_block_size=128)
        assert cfg.quantized_grad_exchange and cfg.quant_block_size == 128
        assert PipelineConfig().quantized_grad_exchange is False
