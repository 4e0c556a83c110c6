"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that changes from run to
run never hits: the directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself), and otherwise ``.jax_cache`` at the root of the
checkout.  Entry points call :func:`enable_compile_cache` before their
first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where compiled programs are cached: the environment's directory,
    else the fixed one inside the checkout."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process
    (kernels compile in about a second, under JAX's default threshold, so
    the threshold goes to 0).  Returns the directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
