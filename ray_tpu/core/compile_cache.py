"""Where JAX's persistent compilation cache lives — the one rule.

Placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set, every
process of the system uses that directory (JAX reads the variable itself;
workers inherit it through the environment ``_spawn_worker`` copies) and
nothing here touches it.  Where it is unset, the cache goes to
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key: a directory named after a pid, a session or a time would
never hit.  Applied once, when ``ray_tpu`` is imported, so driver scripts,
the node agent and every worker agree without passing anything along.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def place_compile_cache() -> str:
    """Return the cache directory in force, exporting the in-checkout
    default when the environment names none."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (unset) variable when it was imported.
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
