"""JAX's persistent compilation cache for the entry points.

Called by the launchers, `benchmarks/run.py` and `chip_smoke.py` -- never
on `import repro` and never in tests.  Where `JAX_COMPILATION_CACHE_DIR` is
set, JAX reads it itself and nothing is changed; otherwise the cache lives
at the fixed path `<repo>/.jax_cache` (listed in .gitignore): the path is
part of the cache key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
