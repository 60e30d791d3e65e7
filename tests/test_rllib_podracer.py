"""Podracer RL tests (arxiv 2104.06272): jax-env parity with the numpy
envs, Anakin TPU-resident learning + placement composition, Sebulba
host/device split (IMPALA loss parity at staleness 0, staleness bound,
injected-death recovery)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import CartPole, IMPALAConfig, Pendulum
from ray_tpu.rllib.env import CartPoleJax, PendulumJax
from ray_tpu.rllib.podracer import (
    AnakinConfig,
    SebulbaConfig,
    evaluate_policy_numpy,
)


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=8)
    yield ctx
    ray_tpu.shutdown()


def _seeded_threshold(random_baseline: float, ceiling: float = 200.0,
                      close: float = 0.2) -> float:
    """PR-8 CQL pattern: the pass bar is derived from the SEEDED random
    baseline (close >= ``close`` of the gap to the env ceiling), not an
    absolute margin that drifts with box numerics."""
    assert random_baseline < ceiling
    return random_baseline + close * (ceiling - random_baseline)


# ------------------------------------------------------------ env parity
class TestJaxEnvParity:
    def test_cartpole_single_step_parity(self):
        import jax
        import jax.numpy as jnp

        je, ne = CartPoleJax(), CartPole(seed=0)
        step = jax.jit(je.step)
        rng = np.random.default_rng(0)
        compared = 0
        for _ in range(100):
            s = rng.uniform(-0.15, 0.15, 4).astype(np.float32)
            a = int(rng.integers(0, 2))
            ne.state, ne.steps = s.copy(), 0
            nobs, nrew, ndone, _ = ne.step(a)
            jstate = {"phys": jnp.asarray(s),
                      "steps": jnp.zeros((), jnp.int32)}
            _, jobs, jrew, jdone = step(
                jax.random.PRNGKey(1), jstate, jnp.int32(a)
            )
            assert bool(jdone) == ndone
            assert float(jrew) == nrew == 1.0
            if not ndone:  # post-done the jax env has auto-reset
                np.testing.assert_allclose(
                    np.asarray(jobs), nobs, atol=1e-5
                )
                compared += 1
        assert compared >= 50  # the sweep must mostly hit live states

    def test_pendulum_single_step_parity(self):
        import jax
        import jax.numpy as jnp

        jp, npd = PendulumJax(), Pendulum(seed=0)
        step = jax.jit(jp.step)
        rng = np.random.default_rng(1)
        for _ in range(100):
            th = rng.uniform(-np.pi, np.pi)
            thdot = rng.uniform(-4.0, 4.0)
            u = rng.uniform(-2.5, 2.5)  # includes the clip boundary
            npd.state, npd.steps = np.array([th, thdot]), 0
            nobs, nrew, _, _ = npd.step(u)
            jstate = {
                "phys": jnp.asarray([th, thdot], jnp.float32),
                "steps": jnp.zeros((), jnp.int32),
            }
            _, jobs, jrew, _ = step(
                jax.random.PRNGKey(1), jstate, jnp.float32(u)
            )
            np.testing.assert_allclose(np.asarray(jobs), nobs, atol=1e-4)
            np.testing.assert_allclose(float(jrew), nrew, atol=1e-4)

    def test_cartpole_auto_reset(self):
        import jax
        import jax.numpy as jnp

        je = CartPoleJax()
        # A state past the angle threshold terminates on any action...
        state = {"phys": jnp.asarray([0.0, 0.0, 0.5, 0.0], jnp.float32),
                 "steps": jnp.asarray(10, jnp.int32)}
        new_state, obs, _, done = je.step(
            jax.random.PRNGKey(0), state, jnp.int32(0)
        )
        assert bool(done)
        # ...and the returned state belongs to a FRESH episode.
        assert int(new_state["steps"]) == 0
        assert np.all(np.abs(np.asarray(new_state["phys"])) <= 0.05)
        np.testing.assert_array_equal(
            np.asarray(obs), np.asarray(new_state["phys"])
        )

    def test_pendulum_truncation_auto_reset(self):
        import jax
        import jax.numpy as jnp

        jp = PendulumJax(max_steps=5)
        state = {"phys": jnp.asarray([0.1, 0.0], jnp.float32),
                 "steps": jnp.asarray(4, jnp.int32)}
        new_state, _, _, done = jp.step(
            jax.random.PRNGKey(0), state, jnp.float32(0.0)
        )
        assert bool(done)  # 5th step truncates
        assert int(new_state["steps"]) == 0

    def test_vectorized_env_axis(self):
        import jax
        import jax.numpy as jnp

        je = CartPoleJax()
        state, obs = je.vec_reset(jax.random.PRNGKey(0), 8)
        assert obs.shape == (8, 4) and state["phys"].shape == (8, 4)
        # Distinct reset keys -> distinct initial states.
        assert len(np.unique(np.asarray(obs)[:, 0])) > 1
        keys = jax.random.split(jax.random.PRNGKey(1), 8)
        state2, obs2, rew, done = je.vec_step(
            keys, state, jnp.ones(8, jnp.int32)
        )
        assert obs2.shape == (8, 4) and rew.shape == (8,)
        assert done.shape == (8,)


# ---------------------------------------------------------------- Anakin
class TestAnakin:
    def test_anakin_learns_cartpole(self):
        cfg = AnakinConfig()
        cfg.num_envs_per_device = 32
        cfg.unroll_length = 16
        cfg.updates_per_step = 50
        cfg.num_devices = 2
        cfg.seed = 0
        algo = cfg.build()
        base = algo.evaluate(num_envs=16, seed=3)
        threshold = _seeded_threshold(base)
        best = base
        for _ in range(6):
            result = algo.train()
            best = max(best, algo.evaluate(num_envs=16, seed=3))
            if best > threshold:
                break
        assert np.isfinite(result["loss"])
        assert best > threshold, (best, threshold, base)

    def test_anakin_step_accounting_and_devices(self):
        cfg = AnakinConfig()
        cfg.num_envs_per_device = 8
        cfg.unroll_length = 4
        cfg.updates_per_step = 2
        cfg.num_devices = 2
        algo = cfg.build()
        r = algo.train()
        assert r["num_devices"] == 2
        assert r["num_env_steps_sampled"] == 2 * 8 * 4 * 2
        assert r["num_learner_updates"] == 2
        assert r["env_steps_per_s"] > 0

    def test_anakin_state_roundtrip(self):
        cfg = AnakinConfig()
        cfg.num_envs_per_device = 8
        cfg.unroll_length = 4
        cfg.updates_per_step = 2
        cfg.num_devices = 1
        algo = cfg.build()
        algo.train()
        state = algo.get_state()
        cfg2 = AnakinConfig()
        cfg2.num_envs_per_device = 8
        cfg2.unroll_length = 4
        cfg2.updates_per_step = 2
        cfg2.num_devices = 1
        algo2 = cfg2.build()
        algo2.set_state(state)
        for k, v in state["params"].items():
            np.testing.assert_array_equal(
                np.asarray(algo2.get_state()["params"][k]), np.asarray(v)
            )

    def test_anakin_jobs_share_chips_via_placement(self, cluster):
        """Two Anakin jobs pinned to actor-role bundles of ONE placement
        group train concurrently — the chip-sharing composition."""
        from ray_tpu.core.placement import podracer_placement_group
        from ray_tpu.rllib.podracer.anakin import anakin_actor

        placement = podracer_placement_group(
            num_actor_bundles=2, num_learner_bundles=0
        )
        assert placement.ready(timeout=60)
        jobs = []
        for i in range(2):
            cfg = AnakinConfig()
            cfg.num_envs_per_device = 4
            cfg.unroll_length = 4
            cfg.updates_per_step = 2
            cfg.num_devices = 1
            cfg.seed = i
            jobs.append(
                anakin_actor(
                    cfg, scheduling_strategy=placement.actor_strategy(i)
                )
            )
        results = ray_tpu.get(
            [j.train.remote() for j in jobs], timeout=180
        )
        assert all(np.isfinite(r["loss"]) for r in results)
        assert all(r["num_env_steps_sampled"] == 4 * 4 * 2 for r in results)
        for j in jobs:
            ray_tpu.kill(j)
        placement.remove()


# --------------------------------------------------------------- Sebulba
def _sync_sebulba_config(seed: int) -> SebulbaConfig:
    cfg = SebulbaConfig()
    cfg.num_env_runners = 1
    cfg.envs_per_runner = 1
    cfg.rollout_steps = 64
    cfg.batches_per_step = 3
    cfg.inference = "host"  # EnvRunner-identical numpy sampling path
    cfg.pipeline_sampling = False  # staleness 0 by construction
    cfg.seed = seed
    return cfg


class TestSebulba:
    def test_loss_parity_with_impala_at_staleness_0(self, cluster):
        """Sync Sebulba (1 runner x 1 env, host inference) IS IMPALA:
        same seeds, same sampler math, shared v-trace loss — the loss
        sequences must match."""
        s = _sync_sebulba_config(seed=7).build()
        s_losses = []
        for _ in range(2):
            r = s.train()
            s_losses.append(r["loss"])
            assert r["staleness_max"] == 0
            assert r["num_stale_trajs_dropped"] == 0
        s.stop()

        im = (
            IMPALAConfig()
            .env_runners(1, rollout_steps=64)
            .training(batches_per_step=3)
        )
        im.seed = 7
        impala = im.build()
        i_losses = [impala.train()["loss"] for _ in range(2)]
        impala.stop()
        np.testing.assert_allclose(s_losses, i_losses, rtol=1e-5)

    def test_staleness_bound_enforced(self, cluster):
        algo = _sync_sebulba_config(seed=3).build()
        try:
            algo.train()  # params now ahead of version 0
            T, B = 4, 1
            traj = {
                "obs": np.zeros((T, B, 4), np.float32),
                "actions": np.zeros((T, B), np.int32),
                "rewards": np.ones((T, B), np.float32),
                "dones": np.zeros((T, B), bool),
                "logp_old": np.full((T, B), -0.7, np.float32),
                "last_value": np.zeros(B, np.float32),
                "episode_returns": [],
                "params_version": 0,
                "env_steps": T * B,
            }
            stats = {"episode_returns": [], "env_steps": 0,
                     "staleness": [], "dropped": 0}
            # version is 3 after one train (3 updates); staleness 3 > 2.
            algo.config.max_staleness = 2
            assert algo._version == 3
            assert algo._consume_trajectory(dict(traj), stats) is None
            assert stats["dropped"] == 1
            # A fresh-enough trajectory IS consumed.
            traj["params_version"] = algo._version
            loss = algo._consume_trajectory(dict(traj), stats)
            assert loss is not None and np.isfinite(float(loss))
            # Consumed-path staleness only: the dropped trajectory is
            # accounted by the counter, never by the staleness stats
            # (staleness_max in results must respect the bound).
            assert stats["staleness"] == [0]
        finally:
            algo.stop()

    def test_sebulba_learns_cartpole(self, cluster):
        cfg = SebulbaConfig()
        cfg.num_env_runners = 2
        cfg.envs_per_runner = 4
        cfg.rollout_steps = 64
        cfg.batches_per_step = 8
        cfg.seed = 0
        algo = cfg.build()
        try:
            maker = lambda: CartPole()  # noqa: E731
            base = evaluate_policy_numpy(
                algo._np_params(), maker, episodes=4, seed=5
            )
            threshold = _seeded_threshold(base)
            best = base
            for _ in range(20):
                result = algo.train()
                best = max(best, evaluate_policy_numpy(
                    algo._np_params(), maker, episodes=4, seed=5
                ))
                if best > threshold:
                    break
            assert np.isfinite(result["loss"])
            assert best > threshold, (best, threshold, base)
            # The async pipeline really pipelines: staleness is nonzero
            # but bounded.
            assert result["staleness_max"] <= algo.config.max_staleness
        finally:
            algo.stop()

    def test_set_state_version_monotonic(self, cluster):
        """Restoring an OLDER checkpoint must not strand the runner
        fleet on the pre-restore policy: the version bumps above
        anything live and the restored params are re-pushed."""
        algo = _sync_sebulba_config(seed=11).build()
        try:
            ckpt = algo.get_state()  # version 0
            algo.train()  # version 3
            v_live = algo._version
            algo.set_state(ckpt)
            assert algo._version == v_live + 1
            # Every runner adopted the restored params under the new
            # version (a stale push of version 0 is rejected, returning
            # the version the runner actually holds).
            held = [
                ray_tpu.get(
                    a.set_params.remote(algo._np_params(), 0), timeout=60
                )
                for a in algo.runner_group.actors
            ]
            assert held == [algo._version] * len(held)
            r = algo.train()  # staleness stays non-negative post-restore
            assert r["staleness_mean"] >= 0.0
            assert np.isfinite(r["loss"])
        finally:
            algo.stop()

    def test_actor_death_recovery_converges(self, cluster):
        """Kill an env runner mid-training: the manager respawns it with
        current params, the result dict surfaces the restart, and the
        run still reaches the seeded threshold."""
        cfg = SebulbaConfig()
        cfg.num_env_runners = 2
        cfg.envs_per_runner = 4
        cfg.rollout_steps = 64
        cfg.batches_per_step = 8
        cfg.seed = 1
        algo = cfg.build()
        try:
            maker = lambda: CartPole()  # noqa: E731
            base = evaluate_policy_numpy(
                algo._np_params(), maker, episodes=4, seed=9
            )
            threshold = _seeded_threshold(base)
            algo.train()
            ray_tpu.kill(algo.runner_group.actors[0])
            restarts = 0
            best = base
            for _ in range(20):
                result = algo.train()
                restarts += result["num_runner_restarts"]
                best = max(best, evaluate_policy_numpy(
                    algo._np_params(), maker, episodes=4, seed=9
                ))
                if best > threshold and restarts >= 1:
                    break
            assert restarts >= 1
            assert best > threshold, (best, threshold, base)
        finally:
            algo.stop()


# ----------------------------------------------- IMPALA kill regression
class TestImpalaRunnerDeath:
    def test_injected_kill_is_surfaced_not_stalled(self, cluster):
        algo = (
            IMPALAConfig()
            .env_runners(2, rollout_steps=32)
            .training(batches_per_step=4)
            .build()
        )
        try:
            import time

            r = algo.train()
            assert r["num_runner_restarts"] == 0
            ray_tpu.kill(algo.runner_group.actors[1])
            # The kill propagates asynchronously (the in-flight ref only
            # errors once the connection teardown beats the RPC retry
            # loop); every step must still COMPLETE (no stall), and the
            # respawn must surface in the result dict within a bounded
            # number of harvest rounds.
            time.sleep(0.5)
            restarts = 0
            for _ in range(12):
                r = algo.train()
                assert np.isfinite(r["loss"])
                restarts += r["num_runner_restarts"]
                if restarts:
                    break
                time.sleep(0.25)
            assert restarts >= 1
        finally:
            algo.stop()

    def test_restart_budget_bounds_respawns(self, cluster):
        """A deterministically-failing sampler exhausts the budget and
        raises instead of respawning forever."""
        from ray_tpu.rllib.actor_manager import FaultTolerantActorManager

        @ray_tpu.remote
        class Crasher:
            def sample(self):
                import os

                os._exit(1)

        mgr = FaultTolerantActorManager(
            lambda i: Crasher.remote(), 1, max_restarts=2,
            on_respawn=lambda i, a: mgr.submit(i, "sample"),
            name="crash_test",
        )
        mgr.submit(0, "sample")
        with pytest.raises(RuntimeError, match="restart budget"):
            for _ in range(10):
                mgr.wait_any(timeout=60)
        assert mgr.num_replacements == 2
        mgr.kill_all()

    def test_restart_window_resets_budget(self):
        """The budget is per WINDOW (training step), not per lifetime:
        occasional deaths over a long run are absorbed indefinitely."""
        from ray_tpu.rllib.actor_manager import FaultTolerantActorManager

        mgr = FaultTolerantActorManager(
            lambda i: object(), 1, max_restarts=1, name="window_test"
        )
        mgr._replace(0, RuntimeError("death 1"))  # 1/1 this window
        with pytest.raises(RuntimeError, match="restart budget"):
            mgr._replace(0, RuntimeError("death 2"))
        mgr.new_restart_window()
        mgr._replace(0, RuntimeError("death 3"))  # absorbed again
        assert mgr.num_replacements == 2


# ------------------------------------------------------------- placement
class TestPodracerPlacement:
    def test_device_role_bundles(self, cluster):
        from ray_tpu.core.placement import PodracerPlacement

        placement = PodracerPlacement(
            num_actor_bundles=2, num_learner_bundles=1
        )
        assert placement.ready(timeout=60)
        assert placement.pg.bundle_count == 3
        assert placement.actor_strategy(1).bundle_index == 1
        assert placement.learner_strategy(0).bundle_index == 2
        with pytest.raises(IndexError):
            placement.actor_strategy(2)
        with pytest.raises(IndexError):
            placement.learner_strategy(1)
        placement.remove()

    def test_role_resources_and_validation(self):
        from ray_tpu.core.placement import PodracerPlacement

        with pytest.raises(ValueError):
            PodracerPlacement(num_actor_bundles=0)


# ---------------------------------------------------------- p2p broadcast
class TestBroadcastFanOut:
    def test_mailbox_try_take_latest(self):
        from ray_tpu.collective.p2p import Mailbox

        box = Mailbox()
        assert box.try_take_latest("edge") is None
        box.deposit("edge", 1, "v1")
        box.deposit("edge", 3, "v3")
        box.deposit("edge", 2, "v2")
        box.deposit("other", 9, "keep")
        seq, value = box.try_take_latest("edge")
        assert (seq, value) == (3, "v3")
        # Older versions were discarded with it, other edges untouched.
        assert box.try_take_latest("edge") is None
        assert len(box) == 1

    def test_broadcast_local_short_circuit(self):
        from ray_tpu.collective.p2p import StageChannel, local_mailbox

        ch = StageChannel("bcast-test")
        nbytes = ch.broadcast(
            5, {"w": np.ones(4)},
            [("bcast-test:params->0", ""), ("bcast-test:params->1", "")],
        )
        assert nbytes == 0  # every destination local: nothing serialized
        for i in range(2):
            seq, value = local_mailbox().try_take_latest(
                f"bcast-test:params->{i}"
            )
            assert seq == 5
            np.testing.assert_array_equal(value["w"], np.ones(4))
