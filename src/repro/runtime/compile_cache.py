"""JAX's persistent compilation cache, wired the same way by every entry
point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks.run``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the path is part of the cache key and
a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
