"""The jax availability gate.

The simulator core and the compiled trace path are pure numpy and must run
where jax is absent (or deliberately disabled with ``REPRO_NO_JAX=1``, the
CI fast lane): :data:`HAS_JAX` is the single gate, and :func:`require_jax`
raises a clear ImportError only when a jax feature is actually called
without it.  Everything else is written against the installed jax.
"""

from __future__ import annotations

import os

__all__ = ["HAS_JAX", "require_jax"]

if os.environ.get("REPRO_NO_JAX"):
    jax = None
else:
    try:
        import jax
    except ImportError:  # pragma: no cover - container always ships jax
        jax = None

HAS_JAX = jax is not None


def require_jax(feature: str = "this feature"):
    """Return the jax module or raise a actionable ImportError."""
    if jax is None:
        raise ImportError(
            f"{feature} needs jax, which is unavailable "
            "(REPRO_NO_JAX set or jax not installed); the numpy simulator "
            "and compiled-trace paths work without it"
        )
    return jax
