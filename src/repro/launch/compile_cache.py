"""JAX's persistent compilation cache at a stable, placeable path.

The cache key includes the directory, so a path that moves between runs
never hits. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
itself); otherwise the cache lives at ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path.

    Call from entry points (scripts, benchmarks), before the first
    compile. Sets nothing when the environment variable is set."""
    d = os.environ.get(ENV)
    if d:
        return d
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
