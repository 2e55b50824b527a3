"""Compile rehearsals for a described TPU v5e: the Pallas kernels at the
widths ``chip_smoke.py`` runs them, compiled (not interpreted) for a chip
that is described, not attached.

This is the only test file that describes a chip.  The topology is built in
a module-scoped fixture (never at import, in ``skipif`` or in
``parametrize``), and the persistent compilation cache is off around the
compiles: a compile for a described chip can be written to the cache but not
read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.scu_barrier.kernel import scu_barrier_kernel, scu_self_signal_kernel
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_stablelm_widths(one_chip):
    b, h, kvh, s, d = chip_smoke.flash_shapes()
    q = _sds((b, h, s, d), jnp.bfloat16, one_chip)
    kv = _sds((b, kvh, s, d), jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(flash_attention_fwd).lower(q, kv, kv).compile())


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    b, s, h, p, n, chunk = chip_smoke.ssd_shapes()
    compiled = (
        jax.jit(ssd_scan_fwd, static_argnames="chunk")
        .lower(
            _sds((b, h, s, p), jnp.bfloat16, one_chip),
            _sds((b, h, s), jnp.bfloat16, one_chip),
            _sds((h,), jnp.float32, one_chip),
            _sds((b, s, n), jnp.bfloat16, one_chip),
            _sds((b, s, n), jnp.bfloat16, one_chip),
            chunk=chunk,
        )
        .compile()
    )
    _assert_kernel(compiled)


def test_scu_self_signal_compiles(one_chip):
    x = _sds((8, 128), jnp.float32, one_chip)
    _assert_kernel(jax.jit(scu_self_signal_kernel).lower(x).compile())


def test_scu_barrier_compiles_in_shard_map_over_four_chips(topo):
    devices = topo.devices[:4]
    mesh = Mesh(np.array(devices), ("x",), axis_types=(AxisType.Auto,))
    fn = jax.jit(jax.shard_map(
        lambda a: scu_barrier_kernel(a, axis="x"),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"),
    ))
    arrive = _sds((len(devices),), jnp.float32, NamedSharding(mesh, P("x")))
    compiled = fn.lower(arrive).compile()
    _assert_kernel(compiled)


def test_executor_compiles_at_mempool_width(one_chip):
    """The device executor's loop at MemPool's width (the ``tree`` barrier
    on 256 lanes and 1,024 banks under the 1/3/5-cycle hierarchy, the
    ``sim.mempool-256pe`` cell's job) compiles for the chip, its tables
    under a megabyte."""
    from repro.core.scu import Hierarchy
    from repro.core.scu.programs import prep_barrier_bench
    from repro.core.scu.trace import _jitted_execute, _pack_tables

    h = Hierarchy(cores_per_tile=4, tiles_per_group=16, groups=4, latency=(1, 3, 5))
    fb = prep_barrier_bench("tree", 256, iters=16, compiled=True,
                            interconnect={"banking_factor": 4, "hierarchy": h})
    tab, addrs, scu = _pack_tables(fb.config.programs, h, 1024)
    compiled = _jitted_execute().lower(
        _sds(tab.shape, jnp.int32, one_chip), _sds(addrs.shape, jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), n_banks=1024, tas_cycles=3, scu=scu, hop=h.latency,
    ).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < 1 << 20
