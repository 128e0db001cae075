"""Where JAX's persistent compilation cache lives for the entry points.

Every ``launch/`` entry point and ``chip_smoke.py`` call
``use_compile_cache()`` before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it on import and this
  module sets nothing.
* unset: the cache goes to ``<checkout>/.jax_cache``.  The path is fixed
  (never a temp, pid or time directory) because a cache entry is only
  found again by a process that looks in the same place.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV", "CHECKOUT_CACHE", "use_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``src/repro/launch/compile_cache.py`` -> ``<checkout>/.jax_cache``
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
