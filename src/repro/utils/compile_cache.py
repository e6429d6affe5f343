"""JAX's persistent compilation cache at a fixed place.

Entry points that compile the fleet program on a chip (``chip_smoke.py``,
``benchmarks/fleet_bench.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile.  Library
code never calls it: an import must not change where compiles go, and a
compile for a described (unattached) TPU, as ``tests/test_tpu_compile.py``
makes, writes entries that cannot be read back.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``
    (git-ignored): a fixed path, since the path is part of what a later
    process must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
