"""SCU barrier as a Pallas TPU kernel -- the paper's mechanism.

The SCU's event lines have a direct TPU hardware analogue: DMA semaphores.
A core that executes ``elw`` stalls until the event arrives with zero busy
cycles; a TPU core that waits on a DMA semaphore blocks in the DMA hardware
the same way -- no spin loop, no host round-trip.

``scu_barrier_kernel`` implements the paper's *barrier extension* across
the devices of one mesh axis as a dissemination barrier:

  round r in 0..log2(n)-1:
      partner = (my_id + 2^r) mod n
      remote-copy my running arrival sum to partner's round-r slot
                                                       (signal = event line)
      wait on the round-r receive semaphore             (elw = restful wait)

After ``log2(n)`` rounds every device has observed every other device's
arrival -- the same all-see-all semantics the SCU barrier status register
provides, in log(n) hops instead of a shared register (adapting the
single-cycle-shared-L1 assumption to the ICI topology).

Every round has its own receive slot and receive semaphore: a sender may
run a round ahead of its receiver, so a shared slot could be overwritten
before it is read and a shared semaphore could release the wrong round.
Before the first remote copy, each device tells the devices that will write
into its scratch that it has entered the kernel (the global barrier
semaphore), so no copy lands in a buffer that does not exist yet.

Inputs and outputs live in HBM (``pl.ANY``) and are only touched by DMA;
all arithmetic happens on the VMEM scratch.

Validation: ``tests/test_kernels.py`` runs both kernels on the CPU under
the TPU interpreter (``interpret=pltpu.InterpretParams()``, which simulates
remote DMAs and semaphores across host devices);
``tests/test_chip_compile.py`` compiles both for a described v5e, and
``chip_smoke.py --chips 4`` runs the barrier on four chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scu_barrier_kernel", "scu_self_signal_kernel"]


def _barrier_body(
    arrive_ref, out_ref, buf, copy_sem, send_sem, recv_sems, *, axis: str, n: int
):
    """Dissemination barrier over mesh axis ``axis`` (inside shard_map)."""
    my_id = jax.lax.axis_index(axis)
    rounds = n.bit_length() - 1

    cp = pltpu.make_async_copy(arrive_ref, buf.at[0], copy_sem)
    cp.start()
    cp.wait()

    # handshake: the round-r sender (my_id - 2^r) may write into my scratch
    ready = pltpu.get_barrier_semaphore()
    for r in range(rounds):
        pltpu.semaphore_signal(
            ready, 1, device_id={axis: jax.lax.rem(my_id - (1 << r) + n, n)},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
    pltpu.semaphore_wait(ready, rounds)

    for r in range(rounds):
        rdma = pltpu.make_async_remote_copy(
            src_ref=buf.at[0],
            dst_ref=buf.at[r + 1],
            send_sem=send_sem,
            recv_sem=recv_sems.at[r],
            device_id={axis: jax.lax.rem(my_id + (1 << r), n)},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()  # restful wait on the DMA semaphores (the elw analogue)
        # accumulate the received arrival sum into ours
        buf[0] = buf[0] + buf[r + 1]

    cp = pltpu.make_async_copy(buf.at[0], out_ref, copy_sem)
    cp.start()
    cp.wait()


def scu_barrier_kernel(arrivals: jnp.ndarray, *, axis: str, interpret=False):
    """All devices along ``axis`` synchronize; returns the summed arrival
    words (== n when everyone arrived).  Must run inside shard_map; the axis
    size must be a power of two (the dissemination sum covers exactly
    ``2**rounds`` devices).  ``interpret``: ``False`` compiles for the TPU;
    ``pltpu.InterpretParams()`` runs the TPU interpreter."""
    n = jax.lax.axis_size(axis)
    rounds = n.bit_length() - 1
    if (1 << rounds) != n:
        raise ValueError(f"axis {axis!r} has {n} devices, not a power of two")
    # one lane-aligned row (zero padding adds nothing to the sums): DMA
    # slices of VMEM must be whole 128-lane tiles
    words = arrivals.size
    x = jnp.pad(arrivals.reshape(1, words), ((0, 0), (0, -words % 128)))
    out = pl.pallas_call(
        functools.partial(_barrier_body, axis=axis, n=n),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        # varies over the same mesh axes as its input (shard_map checks this)
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype, vma=jax.typeof(x).vma),
        scratch_shapes=[
            pltpu.VMEM((rounds + 1,) + x.shape, x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((max(rounds, 1),)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(has_side_effects=True, collective_id=0),
    )(x)
    return out[0, :words].reshape(arrivals.shape)


def _self_signal_body(x_ref, o_ref, buf, sem):
    """Single-device event semantics: signal + restful wait + consume --
    the elw state machine on one core (interpret-testable on CPU)."""
    cp = pltpu.make_async_copy(x_ref, buf, sem)
    cp.start()
    cp.wait()  # blocks until the DMA event fires (event-buffer semantics)
    buf[...] = buf[...] + 1
    cp = pltpu.make_async_copy(buf, o_ref, sem)
    cp.start()
    cp.wait()


def scu_self_signal_kernel(x: jnp.ndarray, *, interpret: bool = False):
    """Local DMA signal/wait roundtrip (the base-unit FSM on one core)."""
    return pl.pallas_call(
        _self_signal_body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM(x.shape, x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(x)
