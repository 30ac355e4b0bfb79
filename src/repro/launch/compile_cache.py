"""Where JAX keeps its persistent compilation cache for this repo's runs.

The entry points call :func:`use_compile_cache` at the top of ``main()``,
never at import, so tests and library callers keep JAX's defaults.
"""
from __future__ import annotations

import os

import jax

# the cache key includes the path, so it is fixed: <checkout>/.jax_cache
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the place: JAX reads it
    itself and nothing here overrides it.  Otherwise the cache lives in the
    git-ignored ``.jax_cache`` directory of the checkout.

    The cache key covers each op's metadata: by default JAX strips it, and
    a program that differs from a cached one only in its ``op_name``s (the
    engine's ``tick.*`` scopes) would run the cached executable, whose ops
    a device trace then reports under the other program's names."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
