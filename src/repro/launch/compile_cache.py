"""JAX's persistent compilation cache at a fixed path.

The cache key includes the directory, so a cache that moves never hits: it
lives at ``<repo root>/.jax_cache``, resolved from this file's location.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
