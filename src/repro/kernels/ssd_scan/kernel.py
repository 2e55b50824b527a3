"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (forward).

One grid step processes one (batch, head, chunk) tile entirely in VMEM:

  * the within-chunk decay kernel ``L = exp(segsum(dt*A))`` (Q x Q, f32),
  * the "diagonal" contribution  ``(C B^T * L) (dt*x)``  (MXU matmuls),
  * the chunk state  ``B^T (decay * dt*x)``  -> (P, N) f32 scratch carried
    across the innermost (sequential) chunk axis -- the inter-chunk
    recurrence runs inside the kernel via the revisited scratch,
  * the "off-diagonal" contribution ``C state_prev`` with in-chunk decay.

The head-state scratch (P x N f32, e.g. 64x128 = 32 KiB) stays resident in
VMEM for the whole sequence -- the TPU-native counterpart of the SSD
algorithm's "states never leave SRAM between chunks" property on GPUs.

Layout is head-major so every block's last two dims are tile-aligned or
full: x/y tiles are ``(Q, P)``; ``dt`` arrives twice, as a ``(Q, 1)``
column (to scale rows) and a ``(1, Q)`` row (to build the segment sums
without an in-kernel transpose); ``A`` is read per head from SMEM.  The
cumulative sums are masked row/column reductions, not ``cumsum``.

Grid: (batch, heads, num_chunks), chunk axis innermost/sequential.
Single-group (g=1) B/C layout, matching the mamba2-1.3b config.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_fwd"]


def _kernel(
    a_ref,  # SMEM (h,)     A (negative), one per head
    x_ref,  # (1, 1, Q, P)  dt-unweighted input tile
    dtc_ref,  # (1, 1, Q, 1)  dt as a column
    dtr_ref,  # (1, 1, 1, Q)  dt as a row
    b_ref,  # (1, Q, N)
    c_ref,  # (1, Q, N)
    y_ref,  # (1, 1, Q, P)
    state_scr,  # VMEM (P, N) f32
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    A = a_ref[pl.program_id(1)]
    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dA_col = dtc_ref[0, 0].astype(jnp.float32) * A  # (Q, 1)
    dA_row = dtr_ref[0, 0].astype(jnp.float32) * A  # (1, Q)
    B = b_ref[0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0].astype(jnp.float32)  # (Q, N)
    Q = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = jj <= ii
    # inclusive within-chunk cumsum of dA, as a column and as a row
    cum_col = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ii <= jj, dA_col, 0.0), axis=0, keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # (1, 1)

    # within-chunk decay kernel: L[i, j] = exp(sum_{j<k<=i} dA_k), j <= i
    L = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)
    xb = x * dtc_ref[0, 0].astype(jnp.float32)  # dt-weighted input

    # diagonal: (C B^T * L) @ xb
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    y_diag = jnp.dot(scores * L, xb, preferred_element_type=jnp.float32)  # (Q, P)

    # off-diagonal: C @ state_prev^T with in-chunk decay
    state_prev = state_scr[...]  # (P, N)
    y_off = jax.lax.dot_general(
        C, state_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum_col)  # (Q, P)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state = decay_chunk * state_prev + (decay_states * xb)^T B
    xd = xb * jnp.exp(total - cum_col)  # (Q, P)
    new_contrib = jnp.dot(xd.T, B, preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state_prev * jnp.exp(total) + new_contrib


def ssd_scan_fwd(
    x: jnp.ndarray,  # (b, h, s, p)
    dt: jnp.ndarray,  # (b, h, s)  positive
    A: jnp.ndarray,  # (h,) negative
    B: jnp.ndarray,  # (b, s, n)  (single group)
    C: jnp.ndarray,  # (b, s, n)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Head-major SSD scan.  Returns y: (b, h, s, p)."""
    b, h, s, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    return pl.pallas_call(
        _kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt[..., None], dt[:, :, None, :], B, C)
