"""The unified ``repro.sync`` policy API: registry, cross-layer parity,
and the tree-barrier extension policy."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core.scu import SCU, Cluster, Compute, run_barrier_bench
from repro.kernels.scu_barrier.ops import ref_barrier_count
from repro.sync import (
    LAYER_HOOKS,
    SyncPolicy,
    available_policies,
    canonical_name,
    get_policy,
    make_tree_policy,
    register_policy,
    unregister_policy,
)

BUILTINS = ("scu", "tas", "sw", "tree", "tree4", "tree_ew", "fifo")


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------


def test_builtins_registered_in_order():
    names = available_policies()
    assert names[:3] == ("scu", "tas", "sw")  # the paper's triad first
    for ext in ("tree", "tree4", "tree_ew", "fifo"):  # registered extensions
        assert ext in names


def _dummy_policy(name="dummy"):
    scu = get_policy("scu")
    return dataclasses.replace(scu, name=name, aliases=(name.upper(),))


def test_register_resolve_list_roundtrip():
    policy = _dummy_policy()
    try:
        register_policy(policy)
        assert "dummy" in available_policies()
        assert get_policy("dummy") is policy
        assert get_policy("DUMMY") is policy  # alias + case-insensitivity
        assert canonical_name("Dummy") == "dummy"
    finally:
        unregister_policy("dummy")
    assert "dummy" not in available_policies()


def test_double_registration_rejected():
    policy = _dummy_policy()
    try:
        register_policy(policy)
        with pytest.raises(ValueError, match="already registered"):
            register_policy(policy)
        register_policy(policy, overwrite=True)  # explicit overwrite allowed
    finally:
        unregister_policy("dummy")


def test_alias_cannot_hijack_existing_policy():
    """An alias capturing another policy's name/alias must be rejected --
    otherwise get_policy('scu') would silently return the newcomer."""
    hijacker = dataclasses.replace(_dummy_policy("ring"), aliases=("scu",))
    with pytest.raises(ValueError, match="collides"):
        register_policy(hijacker)
    legacy_hijacker = dataclasses.replace(_dummy_policy("ring"), aliases=("SW",))
    with pytest.raises(ValueError, match="collides"):
        register_policy(legacy_hijacker)
    assert get_policy("scu").name == "scu"
    assert "ring" not in available_policies()


def test_overwrite_drops_stale_aliases():
    policy = dataclasses.replace(_dummy_policy(), aliases=("DUMMY", "DMY"))
    try:
        register_policy(policy)
        replacement = dataclasses.replace(policy, aliases=("DUMMY",))
        register_policy(replacement, overwrite=True)
        assert get_policy("dummy") is replacement
        with pytest.raises(KeyError):
            get_policy("dmy")  # stale alias of the replaced policy is gone
    finally:
        unregister_policy("dummy")


def test_unknown_policy_error_names_available():
    with pytest.raises(KeyError) as e:
        get_policy("bogus")
    msg = str(e.value)
    for name in BUILTINS:
        assert name in msg, f"error should name available policy {name!r}: {msg}"


def test_incomplete_policy_rejected():
    incomplete = dataclasses.replace(_dummy_policy("broken"), chip_barrier=None)
    with pytest.raises(TypeError, match="chip_barrier"):
        register_policy(incomplete)


def test_legacy_spellings_resolve():
    # the pre-registry simulator/benchmark spellings keep working via aliases
    for legacy in ("SCU", "TAS", "SW"):
        assert get_policy(legacy).name == legacy.lower()


def test_legacy_shim_imports_warn_and_resolve():
    # the PR-1 spellings survive as one-line deprecation wrappers only:
    # each must fire DeprecationWarning and forward to the registry
    import repro.core.scu.primitives as primitives
    import repro.core.sync.strategies as strategies

    with pytest.warns(DeprecationWarning, match="available_policies"):
        assert primitives.VARIANTS == ("SCU", "TAS", "SW")
    with pytest.warns(DeprecationWarning, match="repro.sync registry"):
        assert strategies.STRATEGIES == ("scu", "tas", "sw")
    assert callable(strategies.shape_gradients)
    assert callable(strategies.opt_state_specs)


def test_legacy_strategy_wrappers_warn_and_forward():
    from repro.core.sync.strategies import opt_state_specs, shape_gradients

    policy = get_policy("scu")
    mesh = jax.make_mesh((jax.device_count(),), ("x",), axis_types=(AxisType.Auto,))
    shape = jax.ShapeDtypeStruct((8,), jnp.float32)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        got = opt_state_specs("scu", {"w": shape}, mesh)
    assert got == policy.opt_state_specs({"w": shape}, mesh)
    grads = {"w": jnp.ones((8,), jnp.float32)}
    with pytest.warns(DeprecationWarning, match="deprecated"):
        shaped = shape_gradients("scu", grads, {"w": shape}, mesh)
    ref = policy.shape_gradients(grads, {"w": shape}, mesh)
    assert jax.tree_util.tree_structure(shaped) == jax.tree_util.tree_structure(ref)


def test_legacy_ops_barrier_warns_and_forwards():
    from repro.kernels.scu_barrier import ops

    with pytest.warns(DeprecationWarning, match="chip_barrier"):
        try:
            ops.barrier(jnp.ones((), jnp.float32), "x")
        except Exception:
            pass  # outside a mesh the forwarded call may reject the axis;
            # the contract under test is that the warning fired first


# ---------------------------------------------------------------------------
# Cross-layer parity: every policy provides every hook, and the barriers
# release with the full participant count at both granularities
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", BUILTINS)
def test_policy_implements_protocol(name):
    policy = get_policy(name)
    assert isinstance(policy, SyncPolicy)
    for hook in LAYER_HOOKS:
        assert callable(getattr(policy, hook)), f"{name} missing {hook}"
    assert policy.description


@pytest.mark.parametrize("name", BUILTINS)
@pytest.mark.parametrize("n", [2, 4, 8])
def test_sim_barrier_releases_full_group(name, n):
    """No core passes the simulator barrier before the last one arrives."""
    policy = get_policy(name)
    cl = Cluster(n_cores=n, scu=SCU(n_cores=n))
    state = policy.make_sim_state(n)
    passed = []
    delays = [1 + 9 * i for i in range(n)]

    def prog(delay):
        def p(cluster, cid):
            yield Compute(delay)
            yield from policy.sim_barrier(cluster, cid, state, None)
            passed.append((cid, cluster.cycle))

        return p

    cl.load([prog(d) for d in delays])
    cl.run(max_cycles=1_000_000)
    assert len(passed) == n, f"{name}: only {len(passed)}/{n} cores released"
    last_arrival = max(delays)
    for cid, cyc in passed:
        assert cyc >= last_arrival, f"{name}: core {cid} escaped early"


@pytest.mark.parametrize("name", BUILTINS)
def test_sim_mutex_mutual_exclusion(name):
    policy = get_policy(name)
    n = 4
    cl = Cluster(n_cores=n, scu=SCU(n_cores=n))
    state = policy.make_sim_state(n)
    done = []

    def prog(cluster, cid):
        for _ in range(3):
            yield from policy.sim_mutex(cluster, cid, 5, state, None)
        done.append(cid)

    cl.load([prog] * n)
    cl.run(max_cycles=2_000_000)
    assert sorted(done) == list(range(n)), f"{name}: mutex liveness violated"


@pytest.mark.parametrize("name", BUILTINS)
def test_chip_barrier_matches_psum_oracle(name):
    """Every discipline's released count == the psum oracle (exchanged
    values must actually produce the full participant count)."""
    policy = get_policy(name)
    n = min(4, jax.device_count())
    if n < 2:
        pytest.skip("needs >= 2 devices")
    mesh = jax.make_mesh((n,), ("x",), axis_types=(AxisType.Auto,))
    arrive = jnp.ones((n,), jnp.float32)

    @jax.jit
    def run(a):
        return jax.shard_map(
            lambda v: (policy.chip_barrier(v, "x"), ref_barrier_count(v, "x")),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )(a)

    got, oracle = run(arrive)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle))
    np.testing.assert_allclose(np.asarray(got), np.full((n,), float(n)))


# ---------------------------------------------------------------------------
# Tree-policy radix parametrization (radix-k tournament)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("radix", [2, 3, 4])
@pytest.mark.parametrize("n", [8, 16])
def test_tree_radix_barrier_releases_full_group(radix, n):
    """Radix-k tournament parity: no core escapes before the last arrival,
    for non-power-of-radix group sizes too."""
    policy = make_tree_policy(radix=radix)
    cl = Cluster(n_cores=n, scu=SCU(n_cores=n))
    state = policy.make_sim_state(n)
    assert state.radix == radix
    passed = []
    delays = [1 + 9 * i for i in range(n)]

    def prog(delay):
        def p(cluster, cid):
            yield Compute(delay)
            yield from policy.sim_barrier(cluster, cid, state, None)
            passed.append((cid, cluster.cycle))

        return p

    cl.load([prog(d) for d in delays])
    cl.run(max_cycles=1_000_000)
    assert len(passed) == n, f"radix {radix}: only {len(passed)}/{n} released"
    last_arrival = max(delays)
    for cid, cyc in passed:
        assert cyc >= last_arrival, f"radix {radix}: core {cid} escaped early"


def test_tree4_is_a_registered_builtin():
    """The radix-4 tournament is a builtin with a dedicated benchmark row:
    registered, alias-resolvable, and actually radix 4."""
    t4 = get_policy("tree4")
    assert t4.name == "tree4"
    assert get_policy("TREE4") is t4  # alias round-trip
    assert "tree4" in available_policies()
    assert t4.make_sim_state(16).radix == 4


def test_tree_radix4_halves_depth_on_16_cores():
    """Radix 4 -> 2 tournament levels instead of 4 on a 16-core cluster:
    the builtin tree4 barrier must be measurably cheaper than tree."""
    r2 = run_barrier_bench("tree", 16, sfr=0, iters=8)
    r4 = run_barrier_bench("tree4", 16, sfr=0, iters=8)
    assert r4.cycles_per_iter < r2.cycles_per_iter, (
        f"radix-4 tournament ({r4.cycles_per_iter}) should beat radix-2 "
        f"({r2.cycles_per_iter}) at 16 cores"
    )


def test_tree_default_radix_is_binary():
    assert get_policy("tree").make_sim_state(8).radix == 2


# ---------------------------------------------------------------------------
# Training layer: extension policies are numerically identical to scu
# ---------------------------------------------------------------------------


def _toy_grads(seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "embed": {"table": jax.random.normal(k1, (16, 8))},
        "blocks": {"wq": jax.random.normal(k2, (4, 8, 8))},
        "norm": jax.random.normal(k3, (8,)),
    }


@pytest.mark.parametrize("name", ["tree", "tree4", "fifo"])
def test_extension_shape_gradients_matches_scu(name):
    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices")
    mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    grads = _toy_grads()
    shaped = {}
    for n in ("scu", name):
        policy = get_policy(n)
        fn = jax.jit(lambda g: policy.shape_gradients(g, grads, mesh))
        shaped[n] = fn(grads)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(shaped["scu"]),
        jax.tree_util.tree_leaves_with_path(shaped[name]),
    ):
        assert ka == kb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the discipline must not change the values, only the schedule
    for a, b in zip(jax.tree.leaves(shaped[name]), jax.tree.leaves(grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["tree", "tree4", "fifo"])
def test_extension_opt_state_specs_match_scu(name):
    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices")
    mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _toy_grads()
    )
    scu_specs = get_policy("scu").opt_state_specs(shapes, mesh)
    ext_specs = get_policy(name).opt_state_specs(shapes, mesh)
    assert jax.tree.all(
        jax.tree.map(
            lambda a, b: a == b, scu_specs, ext_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    )


# ---------------------------------------------------------------------------
# The fifo policy: pipelined-chain vertical slice
# ---------------------------------------------------------------------------


def test_fifo_policy_registered_with_pipeline_hook():
    fifo = get_policy("fifo")
    assert fifo.name == "fifo"
    assert get_policy("FIFO") is fifo  # alias round-trip
    assert callable(fifo.make_pipeline_programs)
    # the barrier-only policies fall back to the barrier-sync emulation
    assert get_policy("scu").make_pipeline_programs is None


def test_fifo_chain_beats_software_barrier_pipeline():
    """The point of the FIFO discipline: a pipelined chain under per-link
    event queues must beat the same chain under the software barrier-
    synchronous schedule (the paper's Sec. 4.3 motivation)."""
    from repro.core.scu.programs import run_chain_bench

    fifo = run_chain_bench("fifo", 8, sfr=100, iters=16, depth=8)
    sw = run_chain_bench("sw", 8, sfr=100, iters=16)
    assert fifo.cycles_per_iter < 0.75 * sw.cycles_per_iter, (
        f"fifo chain ({fifo.cycles_per_iter}) should clearly beat the "
        f"sw barrier-sync pipeline ({sw.cycles_per_iter})"
    )


def test_fifo_chain_depth_bounds_in_flight():
    """Credit depth 1 serializes neighboring stages; deeper credit windows
    must monotonically recover throughput up to full overlap."""
    from repro.core.scu.programs import run_chain_bench

    costs = [
        run_chain_bench("fifo", 4, sfr=60, iters=12, depth=d).cycles_per_iter
        for d in (1, 2, 8)
    ]
    assert costs[0] > costs[1] > costs[2], costs


def test_fifo_pipelined_app_wins_under_imbalance():
    """On an imbalanced app skeleton the global barrier pays the cluster-
    wide maximum every tick; the FIFO chain only couples neighbors, so it
    must finish faster than the barrier-synchronous pipeline."""
    from repro.core.scu.apps import APPS, run_app_pipelined

    app = APPS["livermore6"]  # highest per-section imbalance in Table 2
    fifo = run_app_pipelined(app, "fifo")
    scu = run_app_pipelined(app, "scu")
    assert fifo.cycles < scu.cycles, (
        f"fifo pipeline ({fifo.cycles}) should beat the barrier-sync "
        f"schedule ({scu.cycles}) on an imbalanced app"
    )


def test_fifo_chain_rejects_depth_beyond_fifo_capacity():
    from repro.core.scu.programs import run_chain_bench

    with pytest.raises(ValueError, match="depth"):
        run_chain_bench("fifo", 4, sfr=10, iters=64, depth=1000)


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_train_config_validates_and_canonicalizes():
    from repro.train.step import TrainConfig

    assert TrainConfig(sync_strategy="TREE").sync_strategy == "tree"
    assert TrainConfig().sync_policy.name == "scu"
    with pytest.raises(KeyError, match="available policies"):
        TrainConfig(sync_strategy="bogus")


def test_config_base_choices_track_registry():
    from repro.configs.base import sync_policy_choices, validate_sync_policy

    assert sync_policy_choices() == available_policies()
    assert validate_sync_policy("SW") == "sw"
