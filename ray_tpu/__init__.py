"""ray_tpu — a TPU-native distributed computing framework.

Tasks, actors, immutable shared-memory objects, and gang-scheduled placement
groups, where the scheduler's first-class resource is the TPU chip and the
TPU slice with its ICI topology; plus a JAX layer in which collectives lower
to XLA collectives over ICI and device tensors stay resident as jax.Arrays.

Public API mirrors the reference framework (see SURVEY.md):

    import ray_tpu

    ray_tpu.init()

    @ray_tpu.remote
    def f(x):
        return x * 2

    ray_tpu.get(f.remote(2))  # -> 4
"""

from ._version import __version__  # noqa: F401
from .core.compile_cache import place_compile_cache as _place_compile_cache

_place_compile_cache()

from .api import (  # noqa: F401
    ActorClass,
    ActorHandle,
    ClientContext,
    ObjectRef,
    ObjectRefGenerator,
    PlacementGroup,
    RemoteFunction,
    SlicePlacementGroup,
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    init,
    is_initialized,
    kill,
    nodes,
    placement_group,
    placement_group_strategy,
    profile,
    put,
    remote,
    remove_placement_group,
    shutdown,
    state_summary,
    timeline,
    wait,
)
from .core.exceptions import (  # noqa: F401
    ActorDiedError,
    ActorUnavailableError,
    GetTimeoutError,
    ObjectLostError,
    RayTpuError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)
from .core.node import Cluster  # noqa: F401
from .core.scheduler import (  # noqa: F401
    NodeAffinityStrategy,
    NodeLabelStrategy,
    SpreadStrategy,
)

def __getattr__(name):
    # `ray_tpu.dag` loads lazily (PEP 562): it pulls numpy at import
    # time, which costs ~0.2s of every WORKER cold start on a 1-core
    # host (any `ray_tpu.core.*` import runs this package __init__).
    if name == "dag":
        import importlib

        module = importlib.import_module(".dag", __name__)
        globals()["dag"] = module
        return module
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
