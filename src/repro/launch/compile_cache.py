"""JAX's persistent compilation cache, placed from outside the library.

A cold chip run compiles every program it touches, and a Pallas kernel
with its Philox generator takes seconds to minutes to compile.  The
program entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) call :func:`enable` once at
start-up; importing the library never does.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache
    lives there; nothing here names another directory.
  * unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path (the
    path is part of the cache key, so a moving directory never hits),
    listed in ``.gitignore``.

Every compile is cached, however short: the stream service compiles
dozens of sub-second bucket programs, and a second run should compile
next to nothing.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
