"""Scaling-efficiency harness: step-time curve over growing device meshes.

Evidences the north-star ICI scaling target (BASELINE.json: >=90% at
8->256 chips) on whatever devices are present.  On a CPU host it runs
against virtual XLA devices (``--xla_force_host_platform_device_count``),
where the measured retention reflects the collective/partitioning overhead
the compiler inserts — the quantity the sharding design controls — rather
than real ICI bandwidth; on a TPU slice the same harness measures the real
thing.  Also checks ring/Ulysses sequence-parallel attention against the
dense baseline for numerical parity (reference has no SP implementation to
compare against — SURVEY.md §5).

Run standalone (JSON lines on stdout):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m ray_tpu.parallel.scaling_bench

``__graft_entry__.py``'s ``dryrun_multichip`` prints the same curve.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence


def _build_step(cfg, mesh):
    import jax
    import optax

    from ray_tpu.models import gpt2_init, gpt2_loss, gpt2_param_axes
    from ray_tpu.parallel import shard_pytree

    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    if mesh is not None:
        params = shard_pytree(params, gpt2_param_axes(), mesh)
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: gpt2_loss(p, tokens, cfg, mesh)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1)), params, opt_state


def _time_step(step, params, opt_state, tokens, n_steps: int) -> float:
    """Mean seconds/step after compile+warmup, over a pipelined window that
    ends in ``block_until_ready``.

    TWO warmup calls: the first compiles for the initial placements, and
    its RETURNED arrays can carry different shardings (donation + sharding
    propagation), so the second call may compile again — timing from the
    first loop iteration would silently include that recompile (this was
    the round-3 "partitioning overhead": a 1-device mesh appeared 5x
    slower than no mesh purely from the hidden recompile)."""
    p, o, loss = step(params, opt_state, tokens)
    loss.block_until_ready()
    p, o, loss = step(p, o, tokens)
    loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        p, o, loss = step(p, o, tokens)
    loss.block_until_ready()
    return (time.perf_counter() - t0) / n_steps


def _mesh_for(n: int, devices, seq_parallel: bool):
    from ray_tpu.parallel import MeshConfig, build_mesh

    if seq_parallel and n >= 2:
        seq = 2
        fsdp = n // 2
        cfg = MeshConfig(data=1, fsdp=fsdp, seq=seq, model=1)
    else:
        cfg = MeshConfig(data=1, fsdp=n, seq=1, model=1)
    return build_mesh(cfg, devices[:n])


def run_scaling_curve(
    device_counts: Sequence[int] = (1, 2, 4, 8),
    n_steps: int = 8,
    batch_per_device: int = 2,
    seq_len: int = 128,
) -> List[Dict]:
    """Weak-scaling partition retention across mesh sizes (FSDP axis).

    METHODOLOGY (one definition, emitted identically by this module's
    ``main`` and ``dryrun_multichip``): per-device batch is FIXED at
    ``batch_per_device`` (weak scaling).  For each mesh size n the same
    global batch (n * batch_per_device) also runs UNPARTITIONED on one
    device — identical total compute, zero partitioning — and

        retention(n) = t_unpartitioned(n) / t_partitioned(n)

    1.0 means the compiler-inserted sharding machinery (collectives,
    resharding, per-shard dispatch) is free; 0.9 means it costs 11%.
    This calibrated ratio is substrate-independent — on virtual CPU
    devices (all sharing one core) it isolates exactly the partitioning
    overhead, unpolluted by the fake "devices" contending for the core,
    which a naive per-device-throughput retention conflates.
    """
    import jax

    from ray_tpu.models import GPT2Config

    devices = jax.devices()
    counts = [n for n in device_counts if n <= len(devices)]
    cfg = GPT2Config(
        vocab_size=512, max_seq=seq_len, n_layer=4, n_head=8,
        d_model=256, dtype="float32", attention="dense",
    )
    out: List[Dict] = []
    for n in counts:
        batch = batch_per_device * n
        tokens = jax.numpy.zeros((batch, seq_len + 1), jax.numpy.int32)
        # Partitioned: n-device mesh.
        mesh = _mesh_for(n, devices, seq_parallel=False)
        step, params, opt_state = _build_step(cfg, mesh)
        dt = _time_step(step, params, opt_state, tokens, n_steps)
        # Reference: same global batch, one device, no partitioning.
        step_r, params_r, opt_r = _build_step(cfg, None)
        dt_ref = _time_step(step_r, params_r, opt_r, tokens, n_steps)
        retention = round(min(dt_ref / dt, 1.0), 4)
        # Feed the flight recorder's ICI scaling-efficiency gauge so the
        # measured retention is scrapeable from /metrics next to the
        # per-op collective telemetry (best-effort: the harness also runs
        # standalone, with no cluster to flush to).
        try:
            from ray_tpu.util import flight_recorder

            flight_recorder.record_scaling_efficiency(n, retention)
        except Exception:  # noqa: BLE001 — bench must not die on telemetry
            pass
        out.append(
            {
                "devices": n,
                "step_time_s": round(dt, 6),
                "step_time_unpartitioned_s": round(dt_ref, 6),
                "tokens_per_sec_per_device": round(
                    batch * seq_len / dt / n, 1
                ),
                "retention": retention,
            }
        )
    return out


def run_sp_parity(seq_len: int = 128) -> Dict:
    """Ring vs Ulysses vs dense: same loss on the same sharded inputs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT2Config, gpt2_init, gpt2_loss

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "needs >=2 devices"}
    n = 4 if len(devices) >= 4 else 2
    losses = {}
    tokens = None
    for attention in ("dense", "ring", "ulysses"):
        cfg = GPT2Config(
            vocab_size=512, max_seq=seq_len, n_layer=2, n_head=8,
            d_model=128, dtype="float32", attention=attention,
        )
        mesh = _mesh_for(n, devices, seq_parallel=(attention != "dense"))
        if tokens is None:
            key = jax.random.PRNGKey(7)
            tokens = jax.random.randint(
                key, (4, seq_len + 1), 0, cfg.vocab_size, jnp.int32
            )
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
        loss = jax.jit(
            lambda p, t, c=cfg, m=mesh: gpt2_loss(p, t, c, m)
        )(params, tokens)
        losses[attention] = float(loss)
    dense = losses["dense"]
    return {
        "losses": {k: round(v, 6) for k, v in losses.items()},
        "ring_matches_dense": abs(losses["ring"] - dense) < 1e-3,
        "ulysses_matches_dense": abs(losses["ulysses"] - dense) < 1e-3,
    }


def main():
    curve = run_scaling_curve()
    for row in curve:
        print(json.dumps({"scaling": row}), flush=True)
    if len(curve) > 1:
        print(
            json.dumps(
                {
                    "scaling_summary": {
                        "max_devices": curve[-1]["devices"],
                        "retention_at_max": curve[-1]["retention"],
                    }
                }
            ),
            flush=True,
        )
    print(json.dumps({"sp_parity": run_sp_parity()}), flush=True)


if __name__ == "__main__":
    main()
