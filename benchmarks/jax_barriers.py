"""Chip-level barrier strategies: real wall-clock on host devices.

The Fig. 5 experiment transplanted to devices: N host devices execute
(compute-region + barrier) loops under every registered ``repro.sync``
policy; we sweep the compute-region size and report the measured overhead
curves + min region @10% -- the shape of the paper's result reproduced at
chip granularity with actual timings.

Run in a fresh process on host-CPU devices (device count must be set
before jax init); ``benchmarks.run`` spawns it this way:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.jax_barriers
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.sync import available_policies, get_policy

REGION_SIZES = [1, 2, 4, 8, 16, 32, 64]  # matmul repetitions between barriers
N_BARRIERS = 16
DIM = 128


def _make_step(mesh, strategy: str, region: int):
    def body(x, a):
        # compute region: `region` small matmuls (the SFR analogue)
        for _ in range(N_BARRIERS):
            for _ in range(region):
                x = jnp.tanh(x @ a)
            cnt = get_policy(strategy).chip_barrier(jnp.ones((), jnp.float32), "x")
            x = x + cnt * 0  # keep the barrier on the graph
        return x

    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P()), out_specs=P("x"))
    )


def _time(fn, *args, reps=5) -> float:
    fn(*args)[0].block_until_ready()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


def run(verbose: bool = True) -> Dict:
    n = jax.device_count()
    if n < 2:
        raise SystemExit(f"[jax_barriers] needs >= 2 devices, found {n}")
    mesh = jax.make_mesh((n,), ("x",), axis_types=(AxisType.Auto,))
    x = jnp.ones((n * 8, DIM), jnp.float32)
    a = jnp.eye(DIM, dtype=jnp.float32) * 0.99

    strategies = available_policies()
    results: Dict = {"devices": n, "curves": {}}
    # reference: compute-only time per region unit
    def compute_only(x, a, region=max(REGION_SIZES)):
        def body(x, a):
            for _ in range(N_BARRIERS):
                for _ in range(region):
                    x = jnp.tanh(x @ a)
            return x
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P()), out_specs=P("x")))

    t_full = _time(compute_only(x, a), x, a)
    unit = t_full / (N_BARRIERS * max(REGION_SIZES))

    for strategy in strategies:
        curve = []
        for region in REGION_SIZES:
            fn = _make_step(mesh, strategy, region)
            t = _time(fn, x, a)
            t_ideal = unit * N_BARRIERS * region
            overhead = (t - t_ideal) / t_ideal
            curve.append((region, t / N_BARRIERS * 1e6, overhead))
        results["curves"][strategy] = curve

    if verbose:
        print(f"\n== Chip-level barrier disciplines ({n} host devices) ==")
        print("region  " + "".join(f"{s:>10s}" for s in strategies))
        for i, region in enumerate(REGION_SIZES):
            row = [results["curves"][s][i][2] for s in strategies]
            print(f"{region:6d}  " + "".join(f"{o*100:9.0f}%" for o in row))
        for s in strategies:
            per_barrier = results["curves"][s][0][1]
            print(f"  {s}: ~{per_barrier:.0f} us per barrier at region=1")
    return results


if __name__ == "__main__":
    run()
