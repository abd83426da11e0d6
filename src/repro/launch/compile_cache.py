"""Where entry points keep JAX's persistent compilation cache.

Called by the ``main()`` of ``launch.serve`` and ``launch.train`` and by
``chip_smoke.py``; library imports and tests never call it. The cache key
includes the directory, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads the variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
