"""Pallas TPU flash-attention kernel (forward).

TPU-native adaptation of the flash algorithm (DESIGN.md Sec. 6.2): the
(q-block, kv-block) score tile lives entirely in VMEM, streamed block by
block from HBM, with f32 running max / denominator / accumulator scratch
persisted across the innermost (sequential) kv grid axis.  Tile shapes are
MXU-aligned (multiples of 128 on the lane axis; the q/kv block sizes are
sublane multiples).

Grid: ``(batch, q_heads, num_q_blocks, num_kv_blocks)`` -- the kv axis is
innermost, so the output block and the scratch accumulators are revisited
across kv steps ("arbitrary effects" only at the final step).  GQA is
handled in the index map: q head ``h`` reads kv head ``h // group``.

Causal masking skips fully-masked kv blocks via ``pl.when`` (the block is
still visited by the grid but performs no MXU work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

_NEG = -1e30


def _kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    m_scr,  # VMEM (bq,) f32
    l_scr,  # VMEM (bq,) f32
    acc_scr,  # VMEM (bq, d) f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    if causal:
        # causal block skip: kv blocks strictly above the diagonal do no work
        pl.when(k_start <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (b, h, sq, d)
    k: jnp.ndarray,  # (b, kvh, sk, d)
    v: jnp.ndarray,  # (b, kvh, sk, d)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Head-major flash attention.  Returns (b, h, sq, d)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    scale = d**-0.5

    kernel = functools.partial(
        _kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )

    grid = (b, h, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, kj: (bi, hi // group, kj, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, kj: (bi, hi // group, kj, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
