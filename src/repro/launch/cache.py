"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve`` and
``benchmarks.run`` call :func:`enable_compile_cache` at the start of their
``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: root of the checkout (this file is ``src/repro/launch/cache.py``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` — never a temporary, per-process or dated
    name, since entries are only found again under the same directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
