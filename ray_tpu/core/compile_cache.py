"""Where JAX's persistent compilation cache lives — the one rule.

Placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set, every
process of the system uses that directory (JAX reads the variable itself;
workers inherit it through the environment ``_spawn_worker`` copies) and
nothing here touches it.  Where it is unset, the cache goes to
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key: a directory named after a pid, a session or a time would
never hit.  Applied once, when ``ray_tpu`` is imported, so driver scripts,
the node agent and every worker agree without passing anything along.

The same call puts the cache's work on the cluster trace: ONE
``jax.monitoring`` duration listener a process turns each compilation event
(the backend's compile, which holds a retrieval from the cache where there
is a hit; the retrieval itself) into an ``xla.compile`` span of
``util.tracing``, with the event's own name in ``event``.  It is registered
when jax is imported, which this module never does itself.
"""

from __future__ import annotations

import importlib.abc
import importlib.util
import os
import sys
import time

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def place_compile_cache() -> str:
    """Return the cache directory in force, exporting the in-checkout
    default when the environment names none."""
    trace_compilations()
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (unset) variable when it was imported.
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


# ------------------------------------------------- compilations as spans
_MONITORING = "jax._src.monitoring"  # what ``jax.monitoring`` re-exports


def _is_compilation(event: str) -> bool:
    """The events that are an interval of the cache's or the compiler's
    work (``compile_time_saved_sec`` is a difference, not an interval)."""
    return "backend_compile" in event or (
        "compilation_cache" in event and "saved" not in event)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not _is_compilation(event):
        return
    from ray_tpu.core.core_worker import try_global_worker
    from ray_tpu.util import tracing

    worker = try_global_worker()
    if worker is None:
        return  # no cluster, no trace
    # A duration arrives at the event's end.  Compilations run in pool
    # threads too (the engine's build), which copy no context: without one
    # the span goes under a trace of this process's own, and a reader picks
    # the process by ``worker_id``, never by trace.
    end = time.time()
    context = tracing.current_context() or (
        "proc:" + worker.worker_id.hex(), None)
    attributes = {"event": event}
    if "fun_name" in kwargs:
        attributes["fun_name"] = str(kwargs["fun_name"])
    tracing.record_span(
        "xla.compile", end - duration_secs, end, attributes, context=context)


class _WhenJaxLoads(importlib.abc.MetaPathFinder):
    """Registers the listener the moment jax's monitoring module has been
    executed: before anything can compile, and without importing jax."""

    def find_spec(self, fullname, path, target=None):
        if fullname != _MONITORING:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(fullname)
        if spec is None or spec.loader is None:
            return spec
        exec_module = spec.loader.exec_module

        def exec_and_register(module):
            exec_module(module)
            module.register_event_duration_secs_listener(_on_duration)

        spec.loader.exec_module = exec_and_register
        return spec


_tracing_compilations = False


def trace_compilations() -> None:
    """Idempotent: one listener a process, now if jax is loaded already,
    else when it loads."""
    global _tracing_compilations
    if _tracing_compilations:
        return
    _tracing_compilations = True
    monitoring = sys.modules.get(_MONITORING)
    if monitoring is not None:
        monitoring.register_event_duration_secs_listener(_on_duration)
    else:
        sys.meta_path.insert(0, _WhenJaxLoads())
