"""Pipeline parallelism over the ``stage`` mesh axis (GPipe schedule).

Where the reference expresses pipelines as compiled actor DAGs with NCCL
channels (ray ``python/ray/dag/``, SURVEY.md §2.3), the TPU-native pipeline
is a single SPMD program: stage parameters are sharded over the ``stage``
axis, microbatch activations flow stage-to-stage via ``jax.lax.ppermute``
(neighbor ICI hops), and the whole schedule is one ``lax.fori_loop`` under
jit — XLA overlaps the permute of tick t with the compute of tick t+1.

Usage: a stack of structurally identical stage functions (e.g. transformer
layer groups); parameters carry a leading stage dimension.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def pipeline_local(stage_fn: Callable, stage_params, microbatches, *,
                   axis_name: str = "stage"):
    """shard_map-inner GPipe loop.

    stage_fn: (params_for_one_stage, x) -> y with x.shape == y.shape
    stage_params: this device's stage params (leading stage dim squeezed
        by the caller's in_specs, i.e. a [1, ...] tree — squeezed here)
    microbatches: [M, mb, ...] — full input, replicated across stages.
    Returns [M, mb, ...] outputs of the final stage (replicated).
    """

    n = jax.lax.axis_size(axis_name)
    my_stage = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], stage_params)
    m = microbatches.shape[0]
    ticks = m + n - 1
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    carry0 = jnp.zeros_like(microbatches[0])  # inter-stage activation buffer
    out0 = jnp.zeros_like(microbatches)

    def tick(t, state):
        carry, outs = state
        mb_idx = t - my_stage  # which microbatch this stage works on
        active = (mb_idx >= 0) & (mb_idx < m)
        # Stage 0 reads fresh input; others read what the ring delivered.
        x_in = jnp.where(
            my_stage == 0,
            jax.lax.dynamic_index_in_dim(
                microbatches, jnp.clip(mb_idx, 0, m - 1), keepdims=False
            ),
            carry,
        )
        y = stage_fn(params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # Last stage records its finished microbatch.
        is_last = my_stage == n - 1
        outs = jax.lax.cond(
            active & is_last,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(mb_idx, 0, m - 1), axis=0
            ),
            lambda o: o,
            outs,
        )
        # Ship activations to the next stage (single ICI hop).
        carry = jax.lax.ppermute(y, axis_name, perm_fwd)
        return carry, outs

    _, outs = jax.lax.fori_loop(0, ticks, tick, (carry0, out0))
    # Only the last stage holds real outputs; replicate via psum (all other
    # stages contribute zeros).
    outs = jnp.where(my_stage == n - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def pipelined(stage_fn: Callable, mesh, *, axis_name: str = "stage",
              batch_axes=("data", "fsdp")):
    """Build a jit-compatible pipelined apply:
        fn(stacked_params, microbatches) -> outputs
    stacked_params: leading dim = num stages (sharded over ``axis_name``);
    microbatches: [M, mb, ...] with the mb batch dim sharded over
    ``batch_axes``."""
    from jax.sharding import PartitionSpec as P

    inner = functools.partial(pipeline_local, stage_fn, axis_name=axis_name)

    def apply(stacked_params, microbatches):
        params_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        x_spec = P(None, batch_axes)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=(params_specs, x_spec),
            out_specs=x_spec, check_vma=False,
        )(stacked_params, microbatches)

    return apply
