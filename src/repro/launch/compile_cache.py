"""JAX's persistent compilation cache, for the programs' entry points.

A cold full-width serving run spends most of its start-up compiling the
decode, prefill and multi-step window programs; with the cache on, a second
run of the same code reads them back instead.  The cache directory is part
of every entry's key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
  itself); no other directory is set;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points call :func:`enable` (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``); importing this module changes nothing, and tests
leave the cache off.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # keep every program: serving compiles many that take under JAX's default
    # one-second floor (the decode step and each window size)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
