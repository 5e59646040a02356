"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run`` call
:func:`enable` before their first compile; importing ``repro`` does
not. The cache key includes the directory, so the directory is fixed:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads it itself,
  and no other directory is set in code;
* otherwise ``<checkout>/.cache/jax`` (ignored by git).
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE", "enable"]

#: the fallback cache directory: ``.cache/jax`` at the checkout root
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".cache" / "jax"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CHECKOUT_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
