"""Logical-axis sharding rules.

Models annotate arrays with *logical* axis names ("batch", "embed", "heads",
…); a rule table maps logical names to mesh axes.  Changing the parallelism
strategy = changing the table, not the model (the maxtext/flax
logical-axis-rules pattern, re-implemented standalone).

Logical axes are written as ``PartitionSpec`` of logical names (a
PartitionSpec is a pytree *leaf*, so trees of annotations map cleanly over
parameter trees):

    axes = {"wq": P("embed", "heads"), "bias": P(None)}
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

LogicalRules = Dict[str, Union[str, Tuple[str, ...], None]]

# The default recipe: batch splits over (data, fsdp); params shard their
# feature axes over fsdp (ZeRO-3) and their model-parallel axes over model;
# sequence splits over seq for context parallelism.
DEFAULT_RULES: LogicalRules = {
    "batch": ("data", "fsdp"),
    "seq": "seq",
    "embed": "fsdp",  # parameter axis (ZeRO-3 shard)
    "act_embed": None,  # activation feature axis (replicated across fsdp)
    "mlp": "model",
    "heads": "model",
    "kv": None,
    "vocab": "model",
    "stage": "stage",
    "norm": None,
    # the expert axis of per-expert params shards over the expert mesh axis
    "expert": "expert",
}


def logical_spec(logical_axes, rules: Optional[LogicalRules] = None):
    """Map a PartitionSpec (or tuple) of logical names to a mesh-axis
    PartitionSpec."""
    from jax.sharding import PartitionSpec as P

    rules = rules if rules is not None else DEFAULT_RULES
    entries = []
    for name in tuple(logical_axes):
        if name is None:
            entries.append(None)
        else:
            entries.append(rules.get(name))
    return P(*entries)


def logical_sharding(mesh, logical_axes, rules: Optional[LogicalRules] = None):
    from jax.sharding import NamedSharding

    if rules is None and "dcn" in mesh.axis_names:
        # Multi-slice mesh: batch additionally spans the cross-slice dcn
        # axis (see parallel.multislice) — models need no changes.
        from .multislice import MULTISLICE_RULES

        rules = MULTISLICE_RULES
    return NamedSharding(mesh, logical_spec(logical_axes, rules))


def with_logical_constraint(x, logical_axes, mesh=None,
                            rules: Optional[LogicalRules] = None):
    """Inside jit: constrain intermediate activations to a logical sharding.
    No-op when no mesh is provided (single-device runs)."""
    import jax

    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(mesh, logical_axes, rules)
    )


def shard_pytree(params, axes_tree, mesh, rules: Optional[LogicalRules] = None):
    """Device-put a pytree of arrays according to a matching pytree of
    logical PartitionSpecs (PartitionSpec is a leaf, so the trees align)."""
    import jax

    def place(x, axes):
        if axes is None:
            axes = (None,) * x.ndim
        return jax.device_put(x, logical_sharding(mesh, axes, rules))

    return jax.tree.map(place, params, axes_tree)


def sharding_tree(axes_tree, mesh, rules: Optional[LogicalRules] = None):
    """Turn a tree of logical PartitionSpecs into NamedShardings (for use as
    jit in_shardings/out_shardings)."""
    import jax

    return jax.tree.map(
        lambda axes: logical_sharding(mesh, axes, rules), axes_tree
    )
