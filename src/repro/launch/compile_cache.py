"""JAX's persistent compilation cache for the entry points.

Called from ``main`` of the launchers, the benchmark harness and
``chip_smoke.py`` — never at import, so importing the package changes no
JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax-cache (this file is <checkout>/src/repro/launch/...). A
# fixed path: a cache directory that moves between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax-cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
    it and this sets nothing; otherwise the cache lives at ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
