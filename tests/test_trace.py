"""Trace IR contract tests: lowering parity, fallback, batched executors.

The compiled path (``repro.core.scu.trace``) must be *bit-exact* against the
generator engine -- same ``ClusterStats``, cycle for cycle -- for every
builtin policy and bench shape it claims to trace, and must fall back to the
generator cleanly (still bit-exact, ``is_traced`` False) whenever it cannot
prove a program value-independent.

Matrix coverage vs runtime: the full policy x bench grid runs at 8 cores;
at 64/256 the busy-wait policies (``tas``/``sw``) are excluded from the
combos whose *generator reference* is O(n^2)-spin x many episodes (mutex at
256, chain/work_queue at 64+) -- those single references alone take minutes
of wall clock, and the trace semantics they would exercise are identical to
the 8-core runs that do cover them.
"""

import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.scu import SCU, Cluster, Compute, Mem
from repro.core.scu.engine import _COUNTERS
from repro.core.scu.programs import (
    prep_barrier_bench,
    prep_chain_bench,
    prep_mutex_bench,
    prep_work_queue_bench,
)
from repro.core.scu.trace import (
    TraceBuilder,
    TraceProgram,
    Untraceable,
    lower_or_fallback,
    run_traces_xp,
    trace_generator,
)
from repro.compat import HAS_JAX

POLICIES = ("scu", "tas", "sw", "tree", "tree4", "tree_ew", "fifo")
SPIN = ("tas", "sw")  # losers hammer the TCDM; generator reference is O(n^2)

# workloads shrink with core count so the reference runs stay test-sized
_BENCHES = {
    "barrier": lambda v, n, c: prep_barrier_bench(
        v, n, sfr=7, iters={8: 6, 64: 3, 256: 1}[n], compiled=c
    ),
    "mutex": lambda v, n, c: prep_mutex_bench(
        v, n, t_crit=3, iters={8: 4, 64: 1, 256: 1}[n], compiled=c
    ),
    "chain": lambda v, n, c: prep_chain_bench(
        v, n, sfr=5, iters=2, depth=4, compiled=c
    ),
    "work_queue": lambda v, n, c: prep_work_queue_bench(
        v, n // 2, n - n // 2, items={8: 24, 64: 48, 256: 96}[n],
        t_produce=4, t_consume=4, compiled=c
    ),
}


def _combos():
    for n in (8, 64, 256):
        for variant in POLICIES:
            for bench in _BENCHES:
                if variant in SPIN and (
                    (n >= 64 and bench in ("chain", "work_queue"))
                    or (n == 256 and bench == "mutex")
                ):
                    continue  # minutes-long O(n^2) spin reference; see module docstring
                if variant in ("tree", "tree4") and n == 256 and bench == "chain":
                    continue  # combining trees poll child flags: ~100s/ref at 256
                yield n, variant, bench


_COMBOS = list(_combos())


@pytest.mark.parametrize(
    "n,variant,bench", _COMBOS,
    ids=[f"{b}-{v}-{n}" for n, v, b in _COMBOS],
)
def test_lowering_parity(n, variant, bench):
    """Compiled path == generator path, ClusterStats bit-exact."""
    mk = _BENCHES[bench]
    ref = mk(variant, n, False).run_sequential().stats
    got = mk(variant, n, True).run_sequential().stats
    assert got == ref


# which (bench, policy) combos must lower to *real* static traces, as
# opposed to the declared generator fallback.  fifo's mutex seeds a shared
# Python-side queue in cross-core execution order, and the generic
# mutex-protected work queue branches on shared occupancy -- both are
# order-dependent, so sentinel-tracing them would be silently wrong and the
# lowering refuses outright.
_TRACED = {
    "barrier": set(POLICIES),
    "mutex": set(POLICIES) - {"fifo"},
    "chain": set(POLICIES),
    "work_queue": {"fifo"},
}


@pytest.mark.parametrize("bench", tuple(_BENCHES))
@pytest.mark.parametrize("variant", POLICIES)
def test_traceability_matrix(variant, bench):
    """Each combo lowers to a static trace exactly when it is provably (or
    by policy-declared emitter) value-independent; everything else must be
    a declared fallback -- never a wrong trace."""
    fb = _BENCHES[bench](variant, 8, True)
    progs = fb.config.programs
    assert all(isinstance(p, TraceProgram) for p in progs)
    traced = sum(p.is_traced for p in progs)
    if variant in _TRACED[bench]:
        assert traced == len(progs)
    else:
        assert traced == 0


@given(ks=st.lists(st.integers(0, 5), min_size=4, max_size=4))
@settings(max_examples=15, deadline=None)
def test_untraceable_data_dependent_loop_falls_back(ks):
    """A loop whose trip count is a loaded value cannot be traced: the
    sentinel tracer must refuse (never record one unrolling as if it were
    universal) and the fallback must stay bit-exact."""

    def prog(cluster, cid):
        yield Mem("sw", 0x200 + 4 * cid, ks[cid])
        v = yield Mem("lw", 0x200 + 4 * cid)
        for _ in range(v):  # data-dependent trip count
            yield Compute(3)

    def make_cluster():
        return Cluster(n_cores=4, scu=SCU(n_cores=4), mode="fastforward")

    cl = make_cluster()
    cl.load([prog] * 4)
    ref = cl.run()

    cl2 = make_cluster()
    with pytest.raises(Untraceable):
        trace_generator(TraceBuilder(), prog(cl2, 0))
    lowered = [lower_or_fallback(prog, cl2, cid) for cid in range(4)]
    assert all(not p.is_traced for p in lowered)
    cl2.load(lowered)
    assert cl2.run() == ref


def test_trace_program_single_use_and_clone():
    """Cursor semantics mirror FaultPlan: one run per instance, clone() for
    a fresh instance -- even after the original was consumed."""
    tb = TraceBuilder()
    tb.compute(5)
    tb.mem("sw", 0x40, 1)
    tp = tb.build(label="t")
    cl = Cluster(n_cores=1, scu=SCU(n_cores=1))

    pre_clone = tp.clone()
    assert tp(cl, 0) is not None and tp.consumed
    with pytest.raises(RuntimeError, match="single-use"):
        tp(cl, 0)
    post_clone = tp.clone()  # cloning a consumed program is fine
    for c in (pre_clone, post_clone):
        assert not c.consumed and c.is_traced
        assert c(cl, 0) is not None


def _tcdm_traces(n):
    """Small pure-TCDM per-core traces with cross-core bank contention."""
    out = []
    for cid in range(n):
        tb = TraceBuilder()
        for it in range(3):
            tb.mark()
            tb.compute(2 + cid)
            tb.mem("sw", 0x80 + 4 * cid, 10 * cid + it)
            tb.mem("lw", 0x80 + 4 * ((cid + 1) % n))
            tb.mem("lw", 0x40)  # everyone hits one bank: forced conflicts
        out.append(tb.build(label=f"xp:{cid}"))
    return out


def test_run_traces_xp_matches_engine():
    """The batched array executor reimplements TCDM issue/arbitration/
    accounting from scratch; it must agree with the engine counter for
    counter, cycle for cycle."""
    n = 8
    cl = Cluster(n_cores=n, scu=SCU(n_cores=n), mode="lockstep")
    cl.load(_tcdm_traces(n))
    ref = cl.run()

    res = run_traces_xp(_tcdm_traces(n), n_banks=cl.n_banks)
    assert res["cycles"] == ref.cycles
    assert res["bank_conflicts"] == ref.bank_conflicts
    for i, name in enumerate(_COUNTERS):
        got = res["counters"][name].tolist()
        want = [getattr(c, name) for c in ref.cores]
        assert got == want, name


def test_run_traces_xp_is_single_use():
    progs = _tcdm_traces(2)
    run_traces_xp(progs, n_banks=4)
    with pytest.raises(RuntimeError, match="consumed"):
        run_traces_xp(progs, n_banks=4)


def test_run_traces_xp_rejects_scu_rows():
    tb = TraceBuilder()
    tb.compute(1)
    tb.scu("write", 0x10, 1)
    tp = tb.build()
    with pytest.raises(ValueError, match="SCU"):
        run_traces_xp([tp], n_banks=4)


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_run_traces_jax_matches_numpy():
    from repro.core.scu.trace import run_traces_jax

    n = 4
    ref = run_traces_xp(_tcdm_traces(n), n_banks=2 * n)
    got = run_traces_jax(_tcdm_traces(n), n_banks=2 * n)
    assert got["cycles"] == ref["cycles"]
    assert got["bank_conflicts"] == ref["bank_conflicts"]
    for name in _COUNTERS:
        assert got["counters"][name].tolist() == ref["counters"][name].tolist()
    assert got["tcdm"] == ref["tcdm"]


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executors_match_engine_on_table1_sw_barrier(xp_name):
    """The Table 1 ``sw`` barrier spins on a tas lock that every core polls:
    only the granted lane may write the lock word, even when idle lanes'
    pending rows alias the same address."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    ref = prep_barrier_bench("sw", 8, sfr=0, iters=4, compiled=True).run_sequential().stats
    fb = prep_barrier_bench("sw", 8, sfr=0, iters=4, compiled=True)
    res = run(fb.config.programs, n_banks=fb.config.cluster.n_banks)
    assert res["cycles"] == ref.cycles
    assert res["bank_conflicts"] == ref.bank_conflicts
    for name in _COUNTERS:
        assert res["counters"][name].tolist() == [getattr(c, name) for c in ref.cores]


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executors_raise_when_cut_at_max_cycles(xp_name):
    """Neither executor returns partial counters for an unfinished run."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    with pytest.raises(RuntimeError, match="did not finish within 20 cycles"):
        run(_tcdm_traces(4), n_banks=8, max_cycles=20)


_PHASES = ["scu.pack", "scu.stage", "scu.loop", "scu.wait", "scu.readback"]


def _spanned_runs(xp_name, calls=1):
    """Run the executor ``calls`` times; the spans and counters of the calls."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro import obs
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    t0 = time.perf_counter()
    for _ in range(calls):
        run(_tcdm_traces(4), n_banks=8)
    return obs.events(t0, time.perf_counter())


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executor_records_one_run_of_five_phases(xp_name):
    ev = _spanned_runs(xp_name, calls=2)
    roots = [e for e in ev if e.name == "scu.run"]
    assert len(roots) == 2
    for root in roots:
        assert root.parent is None and root.root == root.id
        kids = [e for e in ev if e.parent == root.id and e.n is None]
        assert [e.name for e in kids] == _PHASES
        assert all(e.root == root.id for e in ev if e.start_ns >= root.start_ns
                   and e.end_ns <= root.end_ns)
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executor_phases_cover_the_run(xp_name):
    ev = _spanned_runs(xp_name)
    (root,) = [e for e in ev if e.name == "scu.run"]
    kids = [e for e in ev if e.parent == root.id and e.n is None]
    covered = sum(e.end_ns - e.start_ns for e in kids)
    assert covered >= 0.95 * (root.end_ns - root.start_ns)


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executor_counts_each_trace_of_its_loop(xp_name):
    """One ``scu.loop_traces`` per jax call, under that call's ``scu.loop``;
    the numpy loop is never traced."""
    ev = _spanned_runs(xp_name, calls=2)
    traces = [e for e in ev if e.name == "scu.loop_traces"]
    loops = [e.id for e in ev if e.name == "scu.loop"]
    if xp_name == "numpy":
        assert traces == []
    else:
        assert [(e.n, e.parent) for e in traces] == [(1, loops[0]), (1, loops[1])]


def test_executor_rejects_a_cycle_cap_beyond_int32():
    with pytest.raises(ValueError, match="int32"):
        run_traces_xp(_tcdm_traces(2), n_banks=4, max_cycles=2**31)


def test_compiled_fleet_row_is_jumping():
    """The >=5x headline mechanism: under fastforward with all-trace
    cursors the run monitor must actually collapse periodic spans (tree
    converges after a few iterations), and diagnostics must say so."""
    fb = prep_barrier_bench("tree", 8, sfr=0, iters=64, compiled=True)
    ref = prep_barrier_bench("tree", 8, sfr=0, iters=64).run_sequential()
    got = fb.run_sequential()
    assert got.stats == ref.stats
    cl = fb.config.cluster
    assert cl.trace_jumps >= 1
    assert 0 < cl.trace_jump_cycles < got.stats.cycles
