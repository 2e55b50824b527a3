"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (assignment requirement c).

The ops always run their Pallas kernel (``interpret=True`` is passed
explicitly here); references are called by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.scu_barrier.kernel import scu_barrier_kernel, scu_self_signal_kernel
from repro.kernels.scu_barrier.ops import ref_barrier_count
from repro.kernels.scu_barrier.ref import self_signal_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.layers.flash_core import flash_attention_core
from repro.sync import available_policies, get_policy

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention: shape x dtype sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize(
    "b,h,kvh,s,d,bq,bk",
    [
        (1, 4, 4, 128, 64, 64, 64),  # MHA
        (2, 8, 2, 256, 64, 64, 128),  # GQA 4:1, rectangular blocks
        (1, 4, 1, 256, 128, 128, 64),  # MQA, 128-dim heads
        (1, 2, 2, 512, 64, 128, 128),  # longer sequence
    ],
)
def test_flash_kernel_matches_ref(b, h, kvh, s, d, bq, bk, dtype, rtol):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kvh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kvh, s, d), dtype)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=rtol, atol=rtol
    )


def test_flash_kernel_non_causal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    out = flash_attention_fwd(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_ops_wrapper_layout():
    """ops.flash_attention takes models' (b, s, h, d) layout."""
    ks = jax.random.split(KEY, 3)
    b, s, h, d = 1, 128, 4, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, 2, d))
    v = jax.random.normal(ks[2], (b, s, 2, d))
    out_pallas = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    out_ref = flash_attention_core(
        q.reshape(b, s, 2, 2, d), k, v, True, 64, 64, 0
    ).reshape(b, s, h, d)
    np.testing.assert_allclose(
        np.asarray(out_pallas), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# SSD scan: shape x dtype sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [
        (1, 128, 2, 32, 16, 32),
        (2, 128, 4, 64, 32, 64),
        (1, 256, 2, 64, 128, 128),  # mamba2-1.3b-like head/state dims
    ],
)
def test_ssd_kernel_matches_ref(b, s, h, p, n, chunk, dtype, tol):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, s, n)) * 0.3).astype(dtype)
    out = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(
        x.astype(jnp.float32), dt.astype(jnp.float32), A,
        B.astype(jnp.float32), C.astype(jnp.float32), chunk=chunk,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=tol, atol=tol
    )


def test_ssd_kernel_state_carry_across_chunks():
    """Multiple chunks must agree with a single-chunk run (state carried in
    VMEM scratch across the sequential grid axis)."""
    b, s, h, p, n = 1, 128, 1, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, n)) * 0.3
    out_multi = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    out_single = ssd_scan(x, dt, A, B, C, chunk=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_multi), np.asarray(out_single), rtol=3e-4, atol=3e-4
    )


# ---------------------------------------------------------------------------
# SCU barrier: single-core event semantics + collective fallback equivalence
# ---------------------------------------------------------------------------


def test_scu_self_signal_semantics():
    x = jnp.arange(8, dtype=jnp.float32)
    out = scu_self_signal_kernel(x, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(self_signal_ref(x)))


@pytest.mark.parametrize("strategy", available_policies())
def test_barrier_strategies_equivalent(strategy):
    """Every registered discipline releases with the same arrival count."""
    n = min(4, jax.device_count())
    if n < 2:
        pytest.skip("needs >= 2 devices")
    mesh = jax.make_mesh((n,), ("x",), axis_types=(AxisType.Auto,))
    arrive = jnp.ones((n,), jnp.float32)

    @jax.jit
    def run(a):
        return jax.shard_map(
            lambda v: get_policy(strategy).chip_barrier(v, "x"),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )(a)

    out = run(arrive)
    np.testing.assert_allclose(np.asarray(out), np.full((n,), float(n)))


@pytest.mark.parametrize("arrivals", ["ones", "ramp"])
def test_scu_barrier_kernel_releases_with_the_sum(arrivals):
    """The dissemination barrier on 4 devices, run by the TPU interpreter
    (remote DMAs, per-round semaphores and the entry handshake are all
    simulated): every device releases with the psum of the arrival words."""
    n = 4
    if jax.device_count() < n:
        pytest.skip("needs 4 host devices")
    mesh = jax.make_mesh((n,), ("x",), axis_types=(AxisType.Auto,))
    arrive = (
        jnp.ones((n,), jnp.float32) if arrivals == "ones"
        else jnp.arange(1, n + 1, dtype=jnp.float32)
    )
    # the interpreter itself trips shard_map's varying-axes check
    run = jax.jit(jax.shard_map(
        lambda a: (
            scu_barrier_kernel(a, axis="x", interpret=pltpu.InterpretParams()),
            ref_barrier_count(a, "x"),
        ),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
    ))
    got, oracle = run(arrive)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("op", ["flash_attention", "ssd_scan"])
def test_ops_run_the_kernel_without_a_fallback(op):
    """``interpret=False`` means the compiled kernel, never a jnp stand-in:
    on a backend without Mosaic the call fails instead of answering."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiles on a TPU; the point is the CPU refusal")
    x = jnp.ones((1, 128, 2, 64), jnp.float32)
    with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
        if op == "flash_attention":
            flash_attention(x, x, x, block_q=64, block_k=64)
        else:
            dt = jnp.ones((1, 128, 2), jnp.float32)
            B = jnp.ones((1, 128, 16), jnp.float32)
            ssd_scan(x, dt, -jnp.ones((2,)), B, B, chunk=64)
