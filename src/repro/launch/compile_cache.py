"""JAX's persistent compilation cache, kept where a later run finds it.

A dual-core run compiles one program per exec group per model — dozens of
small programs — so a cold process spends much of its start-up compiling.
:func:`enable_compile_cache` is called once at the start of every entry
point that compiles (``repro.launch.serve``, the fleet worker,
``chip_smoke.py``), before the first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
  sets no other directory;
* otherwise the cache lives at ``.jax_cache/`` in the checkout — a fixed
  path, since the path is part of what makes a later run hit.

Either way every program is cached, however quickly it compiled: the
exec-group programs are individually small.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root (src/repro/launch/compile_cache.py -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
