"""JAX's persistent compilation cache, configured in one place.

Entry points (``python -m repro.experiments.sweep``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up; no
module calls it at import.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory from the
    environment itself, and nothing is set here.
  * unset: the cache goes to ``<checkout>/.jax_cache/`` (git-ignored). The
    path is fixed because it is part of the cache key: a directory named
    after a temp dir, a process id or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
