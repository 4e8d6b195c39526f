"""Where JAX's persistent compilation cache lives, for every entry point.

One rule, applied by ``chip_smoke.py``, the launchers and the benchmark
entry points before their first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
    directory is set here;
  * unset: the fixed directory ``.jax_cache`` at the root of the
    checkout (listed in ``.gitignore``).  The path is part of each
    entry's key, so it is never built from a temp name, a pid or the
    time: a directory that moves never hits.

Every compile is cached, however short: a Pallas kernel compiles in
well under JAX's default one-second floor, and a cold run is mostly
compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...).
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Call it before the first compile of the process: JAX fixes the
    cache when it first compiles.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
