"""JAX's persistent compilation cache, placed by the entry points.

The engine compiles one executable per (plan shape, capacities) pair, one
per vmapped batch size and one per union program, most of them small.
:func:`enable_compile_cache` turns the persistent cache on for all of
them.  Scripts call it before their first compile (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/``); importing :mod:`repro` never does.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory;
* unset: :data:`DEFAULT_DIR`, a fixed directory inside the checkout that
  git ignores.  The path is part of the cache key, so it is never built
  from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Every executable is cached, however short its compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
