"""TPU chip / slice detection without initializing the runtime.

Equivalent of the reference's TPUAcceleratorManager detection path (ray
``python/ray/_private/accelerators/tpu.py:267-672``): chips are discovered
from device files and GCE metadata env vars — never by importing jax, which
would grab the chips.  Publishes:
  - ``TPU``: number of chips on this host
  - ``TPU-{version}`` resource (e.g. ``TPU-v5e``): same count, typed
  - ``TPU-{pod_name}-head``: 1 on worker 0 of a pod slice (gang anchor)
  - labels: accelerator type, topology, worker id — used for
    ICI-topology-aware label scheduling.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, Tuple


def num_local_chips() -> int:
    override = os.environ.get("RAY_TPU_NUM_CHIPS")
    if override is not None:
        return int(override)
    # TPU VM device files: /dev/accel* (older) or /dev/vfio/* (newer PCIe).
    chips = glob.glob("/dev/accel*")
    if chips:
        return len(chips)
    vfio = [p for p in glob.glob("/dev/vfio/*") if re.fullmatch(r".*/\d+", p)]
    if vfio:
        return len(vfio)
    return 0


def accelerator_type() -> str:
    env = os.environ.get("TPU_ACCELERATOR_TYPE", "")  # e.g. "v5litepod-16"
    if env:
        m = re.match(r"(v\d+[a-z]*)", env)
        if m:
            version = m.group(1)
            return {"v5litepod": "v5e", "v5p": "v5p"}.get(version, version)
    return os.environ.get("RAY_TPU_ACCELERATOR_VERSION", "")


def pod_name() -> str:
    return os.environ.get("TPU_NAME", os.environ.get("RAY_TPU_POD_NAME", ""))


def worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def topology() -> str:
    return os.environ.get("TPU_TOPOLOGY", os.environ.get("RAY_TPU_TOPOLOGY", ""))


VALID_TOPOLOGY_RE = re.compile(r"^\d+x\d+(x\d+)?$")


def validate_topology(topo: str) -> bool:
    return bool(VALID_TOPOLOGY_RE.match(topo))


def detect_resources_and_labels() -> Tuple[Dict[str, float], Dict[str, str]]:
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    chips = num_local_chips()
    if chips > 0:
        resources["TPU"] = float(chips)
        version = accelerator_type()
        if version:
            resources[f"TPU-{version}"] = float(chips)
            labels["tpu-version"] = version
        pod = pod_name()
        if pod:
            labels["tpu-pod-name"] = pod
            labels["tpu-worker-id"] = str(worker_id())
            if worker_id() == 0:
                resources[f"TPU-{pod}-head"] = 1.0
        topo = topology()
        if topo:
            labels["tpu-topology"] = topo
    return resources, labels


def _leased_chips() -> str:
    """The chips the node agent exported to this process ("" = none)."""
    from .config import GlobalConfig

    return os.environ.get(GlobalConfig.tpu_visible_chips_env, "")


def lease_holds_chips() -> bool:
    """True in a worker whose lease holds TPU chips that jax is meant to
    use: the agent exported them, and the operator has not pinned jax to a
    platform list without ``tpu`` (the CPU test clusters declare TPU
    resources they do not have and pin ``JAX_PLATFORMS=cpu``)."""
    if not _leased_chips():
        return False
    pinned = os.environ.get("JAX_PLATFORMS", "").lower()
    return not pinned or "tpu" in pinned.split(",")


def leased_platform_verified() -> bool:
    """Hold a chip-lease worker to its chips.  False while this process has
    not initialised a jax backend; True once jax is up on the TPU; raises
    if jax came up on anything else — an unset platform list lets jax fall
    back to the CPU in silence when the chip cannot be opened."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"this worker's lease holds TPU chip(s) {_leased_chips()} "
            f"but jax came up on "
            f"{platform!r}: the chip could not be opened (held by another "
            "process?) and jax fell back"
        )
    return True


def join_host_process_grid(rank: int, peer_addrs) -> None:
    """Environment for ``len(peer_addrs)`` processes that each lease ONE
    chip of this host and are to form one jax world.  libtpu builds the
    slice itself — ``jax.distributed.initialize`` alone leaves every such
    process in a world of one device — so each process is told the
    process grid, every peer's runtime address and its own task id, before
    jax initialises a backend.  Workers that hold a whole host need none
    of this (libtpu finds the slice's hosts itself) and return untouched.
    Only the layout a PR-22 chip run showed to come up is accepted: as
    many one-chip processes as the host has chips, on a 2x2 host."""
    chips = _leased_chips().split(",")
    host_chips, n = num_local_chips(), len(peer_addrs)
    if len(chips) >= host_chips:
        return
    hosts = {a.rsplit(":", 1)[0] for a in peer_addrs}
    if not (len(chips) == 1 and n == host_chips == 4 and len(hosts) == 1):
        raise RuntimeError(
            f"cannot join {n} workers holding {len(chips)} of a host's "
            f"{host_chips} chips into one jax world (hosts {sorted(hosts)}): "
            "supported are whole-host workers, or four one-chip workers on "
            "one 2x2 host"
        )
    for name in ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
        os.environ.pop(name, None)  # the lease's single-chip isolation
    os.environ.update(
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_BOUNDS="2,2,1",
        TPU_PROCESS_ADDRESSES=",".join(peer_addrs),
        TPU_PROCESS_PORT=peer_addrs[rank].rsplit(":", 1)[1],
        CLOUD_TPU_TASK_ID=str(rank),
    )
