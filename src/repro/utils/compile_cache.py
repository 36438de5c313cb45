"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
scripts) call `enable_compile_cache` once at start-up; importing this module
changes nothing, so the tests run with JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (the path is part of the cache key, so a
#: directory that moves between runs never hits) and listed in .gitignore
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to
    `CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
