"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a cache whose path moves from
run to run never hits. The directory is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and nothing
here overrides it; otherwise the cache goes to one fixed directory.
"""

from __future__ import annotations

import os
from typing import Optional

#: ``<repo>/.cache/jax`` — the fixed default (listed in ``.gitignore``).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".cache", "jax")


def use_compile_cache(default_dir: Optional[str] = None) -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory: ``JAX_COMPILATION_CACHE_DIR`` when it is set
    (left exactly as JAX read it), else ``default_dir`` (default
    :data:`REPO_CACHE_DIR`)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    cache_dir = default_dir or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
