"""Anakin — TPU-resident vectorized envs + learner in ONE jitted loop.

Podracer (arxiv 2104.06272) §2: when the environment itself is written
in jax, the entire rollout+learn cycle compiles to a single XLA program
— ``lax.scan`` unrolls the env/policy interaction, a second scan chains
whole updates, and ``pmap`` replicates the loop across devices with
gradients ``pmean``-ed over the device axis.  Parameters, env states,
and trajectories NEVER leave the chip; Python only triggers the next
compiled chunk.  Against the host-loop IMPALA (Python env stepping, one
RPC round per rollout) this is the design's difference between
thousands and millions of env steps per second (the ratio is not
measured on a chip).

The loss is IMPALA's V-trace (``rllib.impala.make_vtrace_loss``) vmapped
over the env axis; on-policy the importance ratios are exactly 1, so it
reduces to n-step actor-critic — but the SAME code path serves both, and
the same trained policy can later be served by Sebulba runners.

Chip sharing: an Anakin job binds only the devices in
``AnakinConfig.num_devices`` (default: all local), so several jobs — or
an Anakin job next to a serving workload — partition one host's chips.
``anakin_actor`` wraps a trainer in a remote actor pinned to a
``PodracerPlacement`` bundle so the placement-group scheduler arbitrates
that sharing cluster-wide.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from ..algorithm import Algorithm, AlgorithmConfig
from ..impala import make_vtrace_loss

import ray_tpu


class AnakinConfig(AlgorithmConfig):
    """Fluent config for the Anakin trainer.

    ``environment()`` takes a *jax env instance* (``CartPoleJax``-style
    functional ``reset``/``step`` with auto-reset), not a maker — the
    env is traced into the compiled loop, not instantiated per actor.
    """

    def __init__(self):
        super().__init__()
        self.jax_env: Optional[Any] = None
        self.num_envs_per_device = 64
        self.unroll_length = 16
        self.updates_per_step = 32  # scanned updates per training_step
        self.num_devices = 0  # 0 = every local device
        self.hidden = 32
        self.lr = 3e-3
        self.entropy_coeff = 0.01
        self.value_coeff = 0.5
        self.vtrace_clip_rho = 1.0
        self.vtrace_clip_c = 1.0

    def environment(self, env) -> "AnakinConfig":  # type: ignore[override]
        self.jax_env = env
        return self


class Anakin(Algorithm):
    """TPU-resident trainer: one ``pmap``-ped program per training_step."""

    def setup(self, config: AnakinConfig) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from ..env import CartPoleJax
        from ..ppo import _init_policy, _policy_forward

        env = config.jax_env if config.jax_env is not None else CartPoleJax()
        if not hasattr(env, "num_actions"):
            raise ValueError(
                "Anakin needs a discrete-action jax env (num_actions); "
                f"got {type(env).__name__}"
            )
        self.env = env
        self.devices = jax.local_devices()
        if config.num_devices:
            if config.num_devices > len(self.devices):
                raise ValueError(
                    f"num_devices={config.num_devices} > "
                    f"{len(self.devices)} local devices"
                )
            self.devices = self.devices[: config.num_devices]
        D = len(self.devices)
        E = config.num_envs_per_device
        T = config.unroll_length
        U = config.updates_per_step
        self._shape = (D, E, T, U)

        key = jax.random.PRNGKey(config.seed)
        params = _init_policy(
            key, env.observation_size, env.num_actions, config.hidden
        )
        self.tx = optax.adam(config.lr)
        opt_state = self.tx.init(params)
        tx = self.tx

        loss_fn = make_vtrace_loss(
            gamma=config.gamma,
            rho_bar=config.vtrace_clip_rho,
            c_bar=config.vtrace_clip_c,
            value_coeff=config.value_coeff,
            entropy_coeff=config.entropy_coeff,
        )

        def one_update(carry, _):
            """Rollout T steps across this device's E envs, then one
            v-trace update — all inside the compiled loop."""
            params, opt_state, env_state, obs, key = carry
            key, rollout_key = jax.random.split(key)

            def env_step(c, _):
                env_state, obs, k = c
                k, k_act, k_env = jax.random.split(k, 3)
                logits, values = _policy_forward(params, obs)
                actions = jax.random.categorical(k_act, logits)
                logp_all = jax.nn.log_softmax(logits)
                logp = jnp.take_along_axis(
                    logp_all, actions[:, None], axis=1
                )[:, 0]
                env_keys = jax.random.split(k_env, E)
                env_state, nobs, rew, done = jax.vmap(env.step)(
                    env_keys, env_state, actions
                )
                out = {
                    "obs": obs,
                    "actions": actions,
                    "rewards": rew,
                    "dones": done.astype(jnp.float32),
                    "logp_old": logp,
                }
                return (env_state, nobs, k), out

            (env_state, obs, _), traj = jax.lax.scan(
                env_step, (env_state, obs, rollout_key), None, length=T
            )
            _, last_values = _policy_forward(params, obs)
            # traj leaves are time-major (T, E, ...); the shared loss is
            # per-trajectory time-major, so vmap it over the env axis.
            batch = {
                k: jnp.moveaxis(v, 0, 1) for k, v in traj.items()
            }
            batch["last_value"] = last_values

            def mean_loss(p):
                losses, _aux = jax.vmap(
                    lambda b: loss_fn(p, b), in_axes=(0,)
                )(batch)
                return jnp.mean(losses)

            loss, grads = jax.value_and_grad(mean_loss)(params)
            grads = jax.lax.pmean(grads, axis_name="devices")
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = {
                "loss": loss,
                "reward_mean": jnp.mean(traj["rewards"]),
                "done_rate": jnp.mean(traj["dones"]),
            }
            return (params, opt_state, env_state, obs, key), metrics

        def learn_chunk(params, opt_state, env_state, obs, key):
            carry, metrics = jax.lax.scan(
                one_update, (params, opt_state, env_state, obs, key),
                None, length=U,
            )
            # Mean over the update chunk; the last update's loss is kept
            # separately as the freshest learning signal.
            summary = {
                "loss": metrics["loss"][-1],
                "loss_mean": jnp.mean(metrics["loss"]),
                "reward_mean": jnp.mean(metrics["reward_mean"]),
                "done_rate": jnp.mean(metrics["done_rate"]),
            }
            return carry, summary

        self._learn = jax.pmap(
            learn_chunk, axis_name="devices", devices=self.devices
        )

        # Greedy-policy evaluation, jitted on one device: mean FIRST-
        # episode return over eval_envs fresh envs.
        max_steps = int(getattr(env, "max_steps", 200))

        def eval_fn(params, key, num_envs):
            keys = jax.random.split(key, num_envs)
            state, obs = jax.vmap(env.reset)(keys)
            alive = jnp.ones(num_envs, jnp.float32)
            ret = jnp.zeros(num_envs, jnp.float32)

            def step(c, _):
                state, obs, alive, ret, k = c
                logits, _ = _policy_forward(params, obs)
                actions = jnp.argmax(logits, axis=-1)
                k, sub = jax.random.split(k)
                ekeys = jax.random.split(sub, num_envs)
                state, obs, rew, done = jax.vmap(env.step)(
                    ekeys, state, actions
                )
                ret = ret + rew * alive
                alive = alive * (1.0 - done.astype(jnp.float32))
                return (state, obs, alive, ret, k), None

            (_, _, _, ret, _), _ = jax.lax.scan(
                step, (state, obs, alive, ret, key), None, length=max_steps
            )
            return jnp.mean(ret)

        self._eval = jax.jit(eval_fn, static_argnums=(2,))

        # Device-resident replicated training state.
        self._params = jax.device_put_replicated(params, self.devices)
        self._opt_state = jax.device_put_replicated(opt_state, self.devices)
        reset_keys = jax.random.split(
            jax.random.PRNGKey(config.seed + 1), D * E
        ).reshape(D, E, 2)
        self._env_state, self._obs = jax.pmap(
            jax.vmap(env.reset), devices=self.devices
        )(reset_keys)
        self._keys = jax.random.split(
            jax.random.PRNGKey(config.seed + 2), D
        )
        self.total_env_steps = 0
        self.total_updates = 0

    # ------------------------------------------------------------ lifecycle
    def training_step(self) -> Dict[str, Any]:
        import jax

        from ray_tpu.util import flight_recorder

        D, E, T, U = self._shape
        t0 = time.perf_counter()
        carry, summary = self._learn(
            self._params, self._opt_state, self._env_state, self._obs,
            self._keys,
        )
        (self._params, self._opt_state, self._env_state, self._obs,
         self._keys) = carry
        summary = jax.tree.map(lambda x: float(np.asarray(x[0])), summary)
        dt = time.perf_counter() - t0
        env_steps = D * E * T * U
        self.total_env_steps += env_steps
        self.total_updates += U
        flight_recorder.record_rl_rollout("anakin", env_steps, dt, devices=D)
        flight_recorder.record_rl_update("anakin", n=U)
        done_rate = summary["done_rate"]
        return {
            "num_env_steps_sampled": env_steps,
            "env_steps_per_s": env_steps / max(dt, 1e-9),
            "num_learner_updates": U,
            "episode_len_mean": 1.0 / max(done_rate, 1e-6),
            "num_devices": D,
            "total_env_steps": self.total_env_steps,
            **summary,
        }

    def evaluate(self, num_envs: int = 16, seed: int = 0) -> float:
        """Mean greedy first-episode return of the current policy."""
        import jax

        params = jax.tree.map(lambda x: x[0], self._params)
        return float(
            self._eval(params, jax.random.PRNGKey(seed), num_envs)
        )

    def resize(self, num_devices: int) -> Dict[str, Any]:
        """Elastic world-size change: re-form the pmap gang over
        ``num_devices`` devices without losing learning progress.

        Single-replica params come off-device (``get_state``), the whole
        compiled loop is rebuilt for the new device set (``setup``), and
        the params re-replicate bit-identically (``set_state`` — the
        optimizer state re-initializes, the same policy as a
        restore-from-checkpoint crossover).  Step counters survive the
        rebuild; per-device batch shape is unchanged, so the GLOBAL batch
        scales with the device count — callers accounting for lr/batch
        coupling read ``num_devices`` out of the returned dict."""
        from ray_tpu.util import flight_recorder

        old = len(self.devices)
        if num_devices == old:
            return {"num_devices": old, "previous": old}
        state = self.get_state()
        steps, updates = self.total_env_steps, self.total_updates
        self.config.num_devices = num_devices
        self.setup(self.config)
        self.set_state(state)
        self.total_env_steps, self.total_updates = steps, updates
        flight_recorder.record_elastic_resize(
            "grow" if num_devices > old else "shrink"
        )
        return {"num_devices": len(self.devices), "previous": old}

    def get_state(self) -> Dict[str, Any]:
        import jax

        params = jax.tree.map(
            lambda x: np.asarray(x[0]), self._params
        )
        return {"params": params}

    def set_state(self, state: Dict[str, Any]) -> None:
        import jax

        self._params = jax.device_put_replicated(
            state["params"], self.devices
        )
        self._opt_state = jax.device_put_replicated(
            self.tx.init(state["params"]), self.devices
        )


AnakinConfig.ALGO_CLS = Anakin


# ------------------------------------------------- placement composition
@ray_tpu.remote
class AnakinWorker:
    """An Anakin trainer wrapped in an actor so the placement-group
    scheduler decides which chips it may bind — the chip-sharing story:
    several Anakin jobs (or Anakin next to serving) each pin to a
    ``PodracerPlacement`` actor bundle and see only their share."""

    def __init__(self, config: AnakinConfig):
        self.algo = Anakin(config)

    def train(self) -> Dict[str, Any]:
        return self.algo.train()

    def evaluate(self, num_envs: int = 16, seed: int = 0) -> float:
        return self.algo.evaluate(num_envs, seed)

    def get_state(self) -> Dict[str, Any]:
        return self.algo.get_state()

    def set_state(self, state: Dict[str, Any]) -> None:
        self.algo.set_state(state)

    def resize(self, num_devices: int) -> Dict[str, Any]:
        return self.algo.resize(num_devices)

    def prepare_evict(self) -> bytes:
        """Checkpoint-then-evict hook: pickle the learner state so the
        runtime parks it in the cluster KV (namespace ``eviction``)
        before this trainer's bundle is reclaimed — a preempted Anakin
        job resumes from here bit-identical (docs/scheduling.md)."""
        import pickle

        return pickle.dumps(self.get_state())


def anakin_actor(config: AnakinConfig, scheduling_strategy=None,
                 **actor_options):
    """Spawn an ``AnakinWorker`` (optionally pinned to a placement-group
    bundle via ``scheduling_strategy=placement.actor_strategy(i)``)."""
    opts = dict(actor_options)
    if scheduling_strategy is not None:
        opts["scheduling_strategy"] = scheduling_strategy
    if opts:
        return AnakinWorker.options(**opts).remote(config)
    return AnakinWorker.remote(config)
