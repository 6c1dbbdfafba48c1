"""Where JAX keeps its persistent compilation cache for this repo's programs.

A cache entry is keyed by the program *and* the cache path, so a directory
that moves from run to run never hits. `enable_compile_cache` is the single
place that chooses it; every entry point (``launch.serve``,
``launch.train``, ``benchmarks.run``, ``chip_smoke.py``) calls it before
its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set: JAX already reads it, so
    nothing else is set. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
