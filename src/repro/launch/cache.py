"""JAX's persistent compilation cache for the repo's entry points.

Called by ``chip_smoke.py``, ``python -m repro.scenarios`` and
``python -m benchmarks.run`` before their first compile; never on import
and never by tests, so a test process compiles exactly what it traces.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory: the cache key includes the path, so a name that
# changes per run would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no directory; otherwise the cache lives in ``.jax_cache/``
    at the checkout root. Either way the cache key covers the program's
    op metadata: a profiler trace names each device operation by the
    ``jax.named_scope`` path its executable was compiled with, and a key
    blind to metadata would load an executable compiled with other
    names."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
