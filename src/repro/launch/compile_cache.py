"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and by
``launch/train.py``'s ``main`` — never on import and never by tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that directory
and nothing else is set. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (gitignored): the path is part of what makes a later
run find an entry, so it is never built from a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
