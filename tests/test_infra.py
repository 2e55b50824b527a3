"""Infrastructure tests: trip-count-aware HLO analyzer + continuous batcher."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_hlo
from repro.serve.batching import ContinuousBatcher, Request


# ---------------------------------------------------------------------------
# HLO analyzer: the roofline's data source must weight scan bodies correctly
# ---------------------------------------------------------------------------


def _dot_flops_of(fn, *args) -> float:
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze_hlo(txt).dot_flops


def test_hlo_analyzer_counts_scan_trip_count():
    """A matmul inside an 8-iteration scan must count ~8x one matmul."""
    d = 128
    x = jnp.ones((d, d), jnp.float32)
    w = jnp.ones((8, d, d), jnp.float32)

    def once(x, w0):
        return x @ w0

    def scanned(x, w):
        def body(c, wi):
            return c @ wi, None

        out, _ = jax.lax.scan(body, x, w)
        return out

    f_once = _dot_flops_of(once, x, w[0])
    f_scan = _dot_flops_of(scanned, x, w)
    assert f_once > 0
    ratio = f_scan / f_once
    assert 6.0 <= ratio <= 10.0, f"scan body weighting off: ratio {ratio:.2f}"


def test_hlo_analyzer_dot_flops_formula():
    """2*M*N*K for a plain matmul (within fusion-variation tolerance)."""
    m, k, n = 64, 256, 128
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    flops = _dot_flops_of(lambda a, b: a @ b, a, b)
    expect = 2 * m * k * n
    assert abs(flops - expect) / expect < 0.01


def test_hlo_analyzer_sees_collectives():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((2,), ("x",), axis_types=(AxisType.Auto,))
    sh = NamedSharding(mesh, P(None, "x"))
    rep = NamedSharding(mesh, P(None, None))
    x = jax.device_put(jnp.ones((64, 64), jnp.float32), sh)
    w = jax.device_put(jnp.ones((64, 64), jnp.float32), sh)

    with mesh:
        # contraction over the sharded axis forces a cross-device reduction
        txt = (
            jax.jit(lambda x, w: x @ w.T, out_shardings=rep)
            .lower(x, w)
            .compile()
            .as_text()
        )
    s = analyze_hlo(txt)
    assert s.total_collective_count >= 1
    assert s.total_wire_bytes > 0


# ---------------------------------------------------------------------------
# Continuous batcher
# ---------------------------------------------------------------------------


def test_batcher_admits_and_finishes():
    b = ContinuousBatcher(batch_slots=2, max_seq=32)
    for rid in range(5):
        b.submit(Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=4))
    steps = 0
    while not b.drain_done():
        b.admit()
        toks, pos = b.step_inputs()
        assert toks.shape == (2, 1) and pos.shape == (2,)
        b.observe(np.full((2,), 7, np.int64))
        steps += 1
        assert steps < 100
    assert len(b.finished) == 5
    for req in b.finished.values():
        assert req.generated == [7, 7, 7, 7]


def test_batcher_deadline_forces_finish():
    b = ContinuousBatcher(batch_slots=1, max_seq=64)
    b.submit(Request(rid=0, prompt=[1], max_new_tokens=1000, deadline_steps=3))
    b.admit()
    for _ in range(3):
        b.observe(np.zeros((1,), np.int64))
    assert 0 in b.finished  # straggler force-finished at the deadline


@settings(max_examples=30, deadline=None)
@given(
    slots=st.integers(min_value=1, max_value=4),
    n_reqs=st.integers(min_value=0, max_value=12),
    lens=st.integers(min_value=1, max_value=6),
)
def test_batcher_conservation(slots, n_reqs, lens):
    """Property: no request is lost or duplicated; slots never exceed capacity."""
    b = ContinuousBatcher(batch_slots=slots, max_seq=64)
    for rid in range(n_reqs):
        b.submit(Request(rid=rid, prompt=[1], max_new_tokens=lens))
    for _ in range(200):
        if b.drain_done():
            break
        b.admit()
        assert b.active <= slots
        b.observe(np.zeros((slots,), np.int64))
    assert sorted(b.finished) == list(range(n_reqs))
