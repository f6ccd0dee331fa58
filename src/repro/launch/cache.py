"""JAX's persistent compilation cache for the repo's entry points.

Call ``enable_compile_cache()`` from a ``main()``, never at import and never
from the tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is set here.  Otherwise the cache lives at one fixed,
git-ignored path in the checkout (``<repo>/.jax_cache``), so the next run
finds what this one compiled; a path made from a temp name, a pid or the
time would start empty every run.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
