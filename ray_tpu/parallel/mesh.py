"""Device-mesh construction for DP/FSDP/TP/SP/PP.

TPU-first design: the mesh is the unit of parallelism (not process groups).
Axes follow the standard recipe (scaling-book / maxtext conventions):

  - ``data``:  pure data parallelism (gradient psum over DCN or ICI)
  - ``fsdp``:  parameter/optimizer sharding (ZeRO-3 style all-gather)
  - ``model``: tensor parallelism (matmul-sharded, psum on contraction)
  - ``seq``:   sequence/context parallelism (ring attention / Ulysses)
  - ``stage``: pipeline parallelism across slices
  - ``expert``: expert parallelism (the expert axis of per-expert params)

``mesh_utils.create_device_mesh`` lays axes onto the physical ICI topology so
the innermost (most chatty) axes ride the fastest links.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import numpy as np

AXES = ("data", "fsdp", "stage", "expert", "seq", "model")


@dataclass
class MeshConfig:
    data: int = 1
    fsdp: int = 1
    stage: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.fsdp, self.stage, self.expert, self.seq,
                self.model)

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.shape))

    @classmethod
    def for_devices(cls, n: int, *, model: int = 1, seq: int = 1, stage: int = 1,
                    expert: int = 1, fsdp: Optional[int] = None) -> "MeshConfig":
        """Fill the data/fsdp axes with whatever ``n`` leaves after the
        explicitly requested axes."""
        fixed = model * seq * stage * expert
        rest = n // fixed
        if rest * fixed != n:
            raise ValueError(
                f"{n} devices not divisible by model×seq×stage×expert = "
                f"{fixed}"
            )
        if fsdp is None:
            fsdp = rest
            data = 1
        else:
            data = rest // fsdp
            if data * fsdp != rest:
                raise ValueError(f"fsdp={fsdp} does not divide {rest}")
        return cls(data=data, fsdp=fsdp, stage=stage, expert=expert, seq=seq,
                   model=model)


def build_mesh(config: MeshConfig, devices: Optional[Sequence] = None):
    """Build a jax Mesh with all five axes (size-1 axes are free)."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) != config.num_devices:
        raise ValueError(
            f"mesh {config.shape} needs {config.num_devices} devices, "
            f"have {len(devices)}"
        )
    try:
        arr = mesh_utils.create_device_mesh(config.shape, devices=devices)
    except Exception:
        arr = np.asarray(devices).reshape(config.shape)
    return Mesh(arr, AXES)


def local_mesh(**axis_sizes):
    """Convenience: mesh over all local devices, e.g.
    ``local_mesh(model=2)`` → data axis absorbs the rest."""
    import jax

    cfg = MeshConfig.for_devices(len(jax.devices()), **axis_sizes)
    return build_mesh(cfg)
