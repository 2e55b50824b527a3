"""Where the entry points keep JAX's persistent compilation cache.

A compile of a full-width model takes minutes, and the cache is keyed on its
directory, so the directory must be the same from run to run:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  is set here;
* otherwise the cache lives in ``<checkout>/.jax_cache`` (git-ignored),
  resolved from this file's location -- never a temp, pid- or time-based
  path.

Only entry points call :func:`enable_compile_cache` (``repro.launch.serve``,
``repro.launch.train``, ``chip_smoke.py``); library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
