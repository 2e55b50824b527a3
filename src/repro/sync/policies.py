"""The paper's three builtin disciplines as :class:`~repro.sync.api.PolicyDef`s.

Each policy bundles the three layer implementations that were previously
scattered across ``core/scu/primitives.py`` (simulator fragments),
``kernels/scu_barrier/ops.py`` (chip-level collectives) and
``core/sync/strategies.py`` (training-schedule hooks):

  * ``sw``  -- pure software spin-locks (Sec. 6.1, "purely spin-lock based").
    Chip level: serialized ring accumulation, one contender per turn.
    Training: per-tensor optimization-barrier chain (one collective per
    parameter tensor, strictly in order).
  * ``tas`` -- software + idle-waiting on SCU notifier events.
    Chip level: log-n dissemination rounds over the shared status word.
    Training: a single coarse synchronization point after backward.
  * ``scu`` -- the paper's hardware primitives (single-``elw`` barrier).
    Chip level: one fused all-reduce of the arrival word.
    Training: fine-grain bucketed reduce-scatter onto ZeRO shards with no
    artificial barriers (XLA overlaps collectives with backward compute).

All chip-level barriers *derive the released count from the exchanged
values* -- there is no hidden ``psum`` oracle patching the result (the
oracle lives only in tests, ``ref_barrier_count``).  All disciplines are
numerically identical; they differ in schedule only.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import param_specs, zero_spec
from repro.sync.api import PolicyDef, register_policy

from repro.core.scu.primitives import (
    DEFAULT_COSTS,
    BarrierState,
    scu_barrier,
    scu_mutex_section,
    sw_barrier,
    sw_mutex_section,
    tas_barrier,
    tas_mutex_section,
    trace_sw_barrier_body,
    trace_tas_mutex_section,
)

__all__ = ["SCU", "TAS", "SW"]


# ---------------------------------------------------------------------------
# Layer (a): simulator fragments -- thin adapters over core/scu/primitives
# ---------------------------------------------------------------------------


def _no_sim_state(n_cores: int) -> None:
    """The hardware SCU keeps all barrier state in the unit itself."""
    return None


def _scu_sim_barrier(cluster, cid, state, cost_model=None):
    yield from scu_barrier(cluster, cid)


def _scu_sim_mutex(cluster, cid, t_crit, state, cost_model=None):
    yield from scu_mutex_section(cluster, cid, t_crit)


def _sw_sim_barrier(cluster, cid, state, cost_model=None):
    yield from sw_barrier(cluster, cid, state, cost_model or DEFAULT_COSTS)


def _sw_sim_mutex(cluster, cid, t_crit, state, cost_model=None):
    yield from sw_mutex_section(cluster, cid, t_crit, cost_model or DEFAULT_COSTS)


def _tas_sim_barrier(cluster, cid, state, cost_model=None):
    yield from tas_barrier(cluster, cid, state, cost_model or DEFAULT_COSTS)


def _tas_sim_mutex(cluster, cid, t_crit, state, cost_model=None):
    yield from tas_mutex_section(cluster, cid, t_crit, cost_model or DEFAULT_COSTS)


# Trace-IR lowerings (repro.core.scu.trace).  The sw/tas barriers and the
# tas mutex branch on *observed* TCDM values (arrival count, lock word), so
# sentinel tracing cannot linearize them -- they get explicit emitters that
# encode the branches as BR rows.  The sw mutex and both scu fragments are
# value-independent, so per-core sentinel tracing is declared safe instead.


def _sw_trace_barrier(tb, cluster, cid, state, cost_model=None):
    trace_sw_barrier_body(tb, cid, state, cost_model or DEFAULT_COSTS, idle_wait=False)


def _tas_trace_barrier(tb, cluster, cid, state, cost_model=None):
    trace_sw_barrier_body(tb, cid, state, cost_model or DEFAULT_COSTS, idle_wait=True)


def _tas_trace_mutex(tb, cluster, cid, t_crit, state, cost_model=None):
    trace_tas_mutex_section(tb, cid, t_crit, cost_model or DEFAULT_COSTS)


# ---------------------------------------------------------------------------
# Layer (b): chip-level barriers (inside shard_map/pmap over ``axis``)
# ---------------------------------------------------------------------------


def scu_chip_barrier(arrive: jnp.ndarray, axis: str) -> jnp.ndarray:
    """One fused synchronization event (the hardware-barrier analogue)."""
    return jax.lax.psum(arrive, axis)


def contribution_vector(arrive: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Per-device one-hot contribution slots for exchange-based barriers.

    Slot ``j`` holds device ``j``'s arrival word (or 0 until it is heard
    from); combining two vectors with ``maximum`` is a union because each
    slot only ever carries one device's non-negative arrival count.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    vec = jnp.zeros((n,) + arrive.shape, arrive.dtype)
    return vec.at[idx].set(arrive)


def tas_chip_barrier(arrive: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Log-n dissemination rounds on the shared status word.

    Round k: every device forwards what it has heard so far to the device
    ``2**k`` ahead (mod n).  After ceil(log2 n) rounds every device has heard
    from everyone (windows are contiguous and grow as min(2**k, n)), so the
    released count is the sum of the exchanged contributions -- exact for any
    group size, with no oracle correction.
    """
    n = jax.lax.axis_size(axis)
    vec = contribution_vector(arrive, axis)
    shift = 1
    while shift < n:
        perm = [(i, (i + shift) % n) for i in range(n)]
        incoming = jax.lax.ppermute(vec, axis, perm)
        vec = jnp.maximum(vec, incoming)
        shift *= 2
    return vec.sum(axis=0)


def sw_chip_barrier(arrive: jnp.ndarray, axis: str) -> jnp.ndarray:
    """n-1 serialized ring turns: each contestant's word circulates in order.

    The optimization barrier keeps XLA from fusing the turns -- the rounds
    are a dependency chain, like the spin-lock's serialized acquire order.
    The count is the sum of every token received, exact for any group size.
    """
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    total = arrive
    token = arrive
    for _ in range(n - 1):
        token = jax.lax.ppermute(token, axis, perm)
        total = total + token
        total, token = jax.lax.optimization_barrier((total, token))
    return total


# ---------------------------------------------------------------------------
# Layer (c): training-schedule hooks
# ---------------------------------------------------------------------------


def _barrier_chain(tree: Any) -> Any:
    """Serialize all leaves with an optimization-barrier dependency chain."""
    leaves, treedef = jax.tree.flatten(tree)
    token = jnp.zeros((), jnp.float32)
    out = []
    for leaf in leaves:
        leaf, token = jax.lax.optimization_barrier((leaf, token))
        token = token + 0.0  # keep the chain explicit
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _zero_specs(params_shape: Any, mesh: Mesh, cfg=None) -> Any:
    """ZeRO shard specs over the data axes for every parameter."""
    specs = param_specs(params_shape, mesh, cfg=cfg)
    return jax.tree.map(
        lambda s, p: zero_spec(s, tuple(p.shape), mesh),
        specs,
        params_shape,
        is_leaf=lambda x: isinstance(x, P),
    )


def sw_shape_gradients(grads, params_shape, mesh: Mesh, cfg=None):
    """Per-tensor serialized sync: one collective per tensor, program order."""
    return _barrier_chain(grads)


def tas_shape_gradients(grads, params_shape, mesh: Mesh, cfg=None):
    """Single coarse sync point between backward and optimizer."""
    return jax.lax.optimization_barrier(grads)


def zero_shape_gradients(grads, params_shape, mesh: Mesh, cfg=None):
    """Fine-grain reduce-scatter onto the ZeRO shards; no barriers."""
    zspecs = _zero_specs(params_shape, mesh, cfg=cfg)
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(
            g, jax.sharding.NamedSharding(mesh, s)
        ),
        grads,
        zspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def replicated_opt_state_specs(params_shape, mesh: Mesh, cfg=None):
    """Baselines keep master/m/v sharded like the params (replicated over
    data) -- the paper's 'every contestant keeps its own copy spinning'."""
    specs = param_specs(params_shape, mesh, cfg=cfg)
    return {"master": specs, "m": specs, "v": specs}


def zero_opt_state_specs(params_shape, mesh: Mesh, cfg=None):
    """ZeRO-shard the optimizer state over the data axes (shard-parallel
    'critical section': the optimizer update)."""
    specs = _zero_specs(params_shape, mesh, cfg=cfg)
    return {"master": specs, "m": specs, "v": specs}


# ---------------------------------------------------------------------------
# The builtin policies
# ---------------------------------------------------------------------------

SCU = register_policy(PolicyDef(
    name="scu",
    description=(
        "hardware SCU primitives: single-elw barrier/mutex; chip: one fused "
        "all-reduce; training: fine-grain ZeRO reduce-scatter, no barriers"
    ),
    aliases=("SCU",),
    make_sim_state=_no_sim_state,
    sim_barrier=_scu_sim_barrier,
    sim_mutex=_scu_sim_mutex,
    chip_barrier=scu_chip_barrier,
    shape_gradients=zero_shape_gradients,
    opt_state_specs=zero_opt_state_specs,
    trace_safe_barrier=True,
    trace_safe_mutex=True,
))

TAS = register_policy(PolicyDef(
    name="tas",
    description=(
        "TAS spin + SCU-notifier idle-wait; chip: log-n dissemination rounds; "
        "training: one coarse sync point after backward"
    ),
    aliases=("TAS",),
    make_sim_state=BarrierState,
    sim_barrier=_tas_sim_barrier,
    sim_mutex=_tas_sim_mutex,
    chip_barrier=tas_chip_barrier,
    shape_gradients=tas_shape_gradients,
    opt_state_specs=replicated_opt_state_specs,
    trace_barrier=_tas_trace_barrier,
    trace_mutex=_tas_trace_mutex,
))

SW = register_policy(PolicyDef(
    name="sw",
    description=(
        "pure software spin-locks; chip: n serialized ring turns; training: "
        "per-tensor optimization-barrier chain"
    ),
    aliases=("SW",),
    make_sim_state=BarrierState,
    sim_barrier=_sw_sim_barrier,
    sim_mutex=_sw_sim_mutex,
    chip_barrier=sw_chip_barrier,
    shape_gradients=sw_shape_gradients,
    opt_state_specs=replicated_opt_state_specs,
    trace_barrier=_sw_trace_barrier,
    trace_safe_mutex=True,
))
