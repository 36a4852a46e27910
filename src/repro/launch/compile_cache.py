"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
itself and :func:`enable` sets nothing.  Otherwise the cache lives in
``.jax_cache/`` at the repository root: a fixed path, so every run of the
launchers and of ``chip_smoke.py`` from this checkout finds what an
earlier one compiled.  Nothing enables the cache at import time; the
entry points call :func:`enable` first thing.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
