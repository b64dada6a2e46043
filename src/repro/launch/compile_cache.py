"""JAX's persistent compilation cache for the entry points.

Entry points (the serving CLI, ``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache` once at start-up; nothing calls it at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set here. Otherwise the cache lives at a fixed path
inside the checkout: the path is part of the cache key, so it is never
built from a temp name, a pid or the time. An explicit
``JAX_ENABLE_COMPILATION_CACHE`` wins over the helper; the test suite
sets it to ``false`` so tests keep the cache off.
"""
from __future__ import annotations

import os
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
SWITCH = "JAX_ENABLE_COMPILATION_CACHE"
REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def compile_cache_dir(env: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<repo>/.jax_cache`` (listed in .gitignore)."""
    env = os.environ if env is None else env
    return env.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; returns its directory, or None where
    ``JAX_ENABLE_COMPILATION_CACHE`` is set and the choice is left to it."""
    import jax
    if os.environ.get(SWITCH):
        return compile_cache_dir() if jax.config.jax_enable_compilation_cache \
            else None
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
