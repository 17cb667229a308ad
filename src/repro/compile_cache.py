"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile; nothing
calls it at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed in-checkout cache directory (listed in ``.gitignore``); a path
#: that never moves is what lets a later run of the same checkout hit it
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`, and every compile is kept: each Pallas kernel
    compiles in well under JAX's default one-second threshold, which
    would leave the cache empty.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_DIR)
