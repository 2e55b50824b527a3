"""Model-layout wrapper for the Pallas flash-attention forward kernel.

``flash_attention(q, k, v)`` takes the models' (b, s, h, d) layout and always
runs the Pallas kernel; ``interpret=True`` runs it in interpret mode (the
CPU test path).  The numerically identical pure-JAX flash core, which also
provides the backward pass, is
:func:`repro.models.layers.flash_core.flash_attention_core`; the plain
oracle is :func:`repro.kernels.flash_attention.ref.attention_ref`.
"""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention_fwd

__all__ = ["flash_attention"]


def flash_attention(
    q: jnp.ndarray,  # (b, s, h, d)
    k: jnp.ndarray,  # (b, s, kvh, d)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    out = flash_attention_fwd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
