"""JAX's persistent compilation cache, placed once by each entry point.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile; importing a
library module never turns the cache on.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Returns the cache directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this
    leaves it alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``,
    a fixed path (never one made from a temporary name, a process id or
    the time), so later runs from this checkout find what earlier ones
    compiled.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
