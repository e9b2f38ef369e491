"""JAX's persistent compilation cache, switched on by the entry points.

A cold sweep on a TPU spends a large share of its wall time compiling the
chunk programs (one per scenario and batch shape). The persistent cache
lets a later process — a resumed worker, the next launch — load those
executables instead of compiling them again. The cache directory is part
of what a cache hit depends on, so it must be a fixed path: never a
temporary, PID- or time-derived one.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call before the process's first compile (the cache is initialized on
    first use), never at import. When ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing here overrides it; otherwise the cache
    goes to ``<repo>/.jax_cache``.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
