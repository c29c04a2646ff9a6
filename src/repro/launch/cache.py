"""JAX's persistent compilation cache for the repo's entry points."""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Keep compiled programs across runs.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
    nothing else is set; otherwise the cache goes to the fixed
    ``<root>/.jax_cache``, a path that stays the same from run to run so
    later runs find what earlier ones compiled.  Entry points call this
    before their first compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
