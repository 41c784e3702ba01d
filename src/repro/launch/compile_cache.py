"""JAX's persistent compilation cache for the entry points.

Called from ``main`` of each entry point, never on import: a cache switched
on at import would also catch the compiles that tests make for a described
(not attached) chip, which can be written to the cache but never read back.
"""
from __future__ import annotations

import os

import jax

#: ``<repo>/.jax_cache``.  The cache directory is part of every entry's key,
#: so it is a fixed path: a temporary or per-run directory would never hit.
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; return the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    it stands; otherwise the cache goes to :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
