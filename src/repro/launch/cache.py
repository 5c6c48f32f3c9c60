"""JAX's persistent compilation cache for the entry points.

Every script a user runs (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/*.py``) calls :func:`enable_compile_cache` first, so compiled
programs are found again by the next run.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(gitignored).  The path is part of the cache's key, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> tuple[Path, bool]:
    """``(directory, from_env)``: the environment variable wins, else the
    fixed in-checkout directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env), True
    return CHECKOUT_CACHE, False


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return the directory.  Call before the first compile."""
    path, from_env = compile_cache_dir()
    if not from_env:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
