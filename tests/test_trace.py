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

import dataclasses
import functools
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.scu import SCU, Cluster, Compute, Hierarchy, Mem
from repro.core.scu.engine import _COUNTERS, SlotFleet
from repro.core.scu.programs import (
    make_fleet,
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


def _tcdm_traces(n, base=0):
    """Small pure-TCDM per-core traces with cross-core bank contention;
    ``base`` offsets the stored values (same table shape, other contents)."""
    out = []
    for cid in range(n):
        tb = TraceBuilder()
        for it in range(3):
            tb.mark()
            tb.compute(2 + cid)
            tb.mem("sw", 0x80 + 4 * cid, base + 10 * cid + it)
            tb.mem("lw", 0x80 + 4 * ((cid + 1) % n))
            tb.mem("lw", 0x40)  # everyone hits one bank: forced conflicts
        out.append(tb.build(label=f"xp:{cid}"))
    return out


def _ragged_contended_traces(n):
    """Pure-TCDM traces whose lengths differ by far more than 4x (lane 0
    has 5 rows, lane ``n - 1`` has ``15 n - 10``; the shorter tables are
    padded with HALT rows), in which the lanes contend for one
    lock word every cycle: a tas, a delta store and a load on it.  First
    the other lanes poll a flag in another bank, which lane 0 sets after
    its round on the lock, so their polls miss until then."""
    out = []
    for cid in range(n):
        tb = TraceBuilder()
        if cid:
            tb.poll("lw", 0x44, 1, 1, 2)
        for _ in range(1 + 5 * cid):
            tb.mem("tas", 0x40)
            tb.mem_delta("sw", 0x40, 1 + cid)
            tb.mem("lw", 0x40)
        if not cid:
            tb.mem("sw", 0x44, 1)
        out.append(tb.build(label=f"ragged:{cid}", roll=False))
    return out


_TRACE_SETS = {"tcdm": _tcdm_traces, "ragged-contended": _ragged_contended_traces}


@pytest.mark.parametrize("traces", tuple(_TRACE_SETS))
def test_run_traces_xp_matches_engine(traces):
    """The batched array executor reimplements TCDM issue/arbitration/
    accounting from scratch; it must agree with the engine counter for
    counter, cycle for cycle, down to the retire cycles and TCDM words."""
    n = 8
    make = _TRACE_SETS[traces]
    cl = Cluster(n_cores=n, scu=SCU(n_cores=n), mode="lockstep")
    cl.load(make(n))
    ref = cl.run()

    _assert_matches_engine(run_traces_xp(make(n), n_banks=cl.n_banks), cl, ref)


def test_run_traces_xp_is_single_use():
    progs = _tcdm_traces(2)
    run_traces_xp(progs, n_banks=4)
    with pytest.raises(RuntimeError, match="consumed"):
        run_traces_xp(progs, n_banks=4)


@pytest.mark.parametrize("kind,addr,why", [
    ("elw", ("fifo", 2, "pop"), "event FIFO"),
    ("write", ("fifo", 0, "push"), "event FIFO"),
    ("write", 0x10, "does not encode"),
    ("elw", ("notifier", 8, "wait"), "no such extension instance"),
])
def test_run_traces_xp_rejects_scu_rows(kind, addr, why):
    """The SCU ops the executor does not run, the event FIFO's first, are
    refused by name before anything runs."""
    tb = TraceBuilder()
    tb.compute(1)
    tb.scu(kind, addr, 1)
    tp = tb.build()
    with pytest.raises(ValueError, match=f"SCU op .*{addr[0] if isinstance(addr, tuple) else ''}.*{why}"):
        run_traces_xp([tp], n_banks=4)


# Table 1's SCU columns and their baselines (the ``sim.table1-scu-8pe`` mix):
# (primitive, policy, t_crit, sfr).  Every job sleeps and wakes through elw.
_SCU_JOBS = [
    ("barrier", "scu", 0, 0), ("barrier", "tas", 0, 0), ("barrier", "tree_ew", 0, 0),
    ("mutex", "scu", 0, 0), ("mutex", "scu", 10, 0), ("mutex", "tas", 0, 0),
    ("mutex", "tas", 10, 0), ("barrier", "scu", 0, 42),
]


def _scu_job(job, n, iters=3):
    prim, policy, t_crit, sfr = job
    if prim == "barrier":
        return prep_barrier_bench(policy, n, sfr=sfr, iters=iters, compiled=True, mode="lockstep")
    return prep_mutex_bench(policy, n, t_crit=t_crit, sfr=sfr, iters=iters, compiled=True,
                            mode="lockstep")


def _assert_matches_engine(res, cl, ref):
    assert res["cycles"] == ref.cycles
    assert res["bank_conflicts"] == ref.bank_conflicts
    for name in _COUNTERS:
        assert res["counters"][name].tolist() == [getattr(c, name) for c in ref.cores], name
    assert res["finished_at"].tolist() == [c.finished_at for c in ref.cores]
    assert res["tcdm"] == {a: cl.tcdm.get(a, 0) for a in res["tcdm"]}


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("job", _SCU_JOBS, ids=["-".join(map(str, j)) for j in _SCU_JOBS])
def test_executors_match_engine_on_scu_jobs(job, n, xp_name):
    """The SCU phase (comparators, private links, elw sleep and wake, the
    barrier, mutex and notifier extensions) against the lockstep engine, bit
    for bit: cycles, the nine counters with ``gated_cycles`` and
    ``scu_accesses``, conflicts, retire cycles and the final TCDM words."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    fb = _scu_job(job, n)
    cl = fb.config.cluster
    cl.load(fb.config.programs)
    ref = cl.run()
    fb = _scu_job(job, n)
    res = run(fb.config.programs, n_banks=fb.config.cluster.n_banks)
    _assert_matches_engine(res, cl, ref)
    assert ref.total_scu > 0 and (job[1] == "scu" or ref.total_tcdm > 0)


def _random_scu_traces(seed, n, rounds=4):
    """Per-lane traces over every SCU op the executor encodes, each round
    closed by the hardware barrier.  Every response (a read, an elw) is
    stored in a TCDM word of its own, so the final words show it.  Some
    rounds line up a lane's read of the barrier or mutex with other lanes'
    arrivals or unlock in the same cycle, where the lanes' order decides."""
    import random

    rng = random.Random(seed)
    kinds = [rng.randrange(3) for _ in range(rounds)]
    bases = [rng.randrange(1, 7) for _ in range(rounds)]
    progs = []
    for cid in range(n):
        tb = TraceBuilder()
        slots = iter(range(1_000_000))

        def store(delta=0):
            tb.mem_delta("sw", 0x1000 + 4 * (cid + n * next(slots)), delta)

        if cid % 2:  # a store at row 0 (idle lanes' rows read as row 0's)
            store()
        for r in range(rounds):
            if kinds[r] == 1:  # barrier reads beside same-cycle arrivals
                tb.compute(1)
                if cid % 2 == 0:
                    tb.scu("read", ("barrier", 0, "status"))
                    store()
            elif kinds[r] == 2:  # mutex reads beside its owner's unlock
                if cid == 0:
                    tb.scu("elw", ("mutex", 0, "lock"))
                    store()
                    tb.compute(1)
                    tb.scu("write", ("mutex", 0, "unlock"), 77)
                else:
                    tb.compute(bases[r] + cid)
                    tb.scu("read", ("mutex", 0, "status"))
                    store()
            for _ in range(rng.randint(1, 3) if kinds[r] == 0 else 0):
                tb.compute(rng.randint(1, 3))
                k, e = rng.randrange(8), rng.randrange(8)
                if k == 0:
                    tb.scu("write", ("notifier", e, "trigger"),
                           rng.choice([0, rng.randrange(1, 1 << n)]))
                elif k == 1:
                    tb.scu("write", ("buffer", "clear"), rng.randrange(1 << 10))
                elif k == 2:
                    tb.scu("read", rng.choice([("buffer", "event"), ("barrier", 0, "status"),
                                               ("mutex", 0, "status")]))
                    store()
                elif k in (3, 4, 7):  # a wait that this lane's own trigger ends
                    if k == 4:
                        tb.scu("write", ("mask", "event"), (1 << e) | rng.randrange(1 << 10))
                    elif k == 7:
                        tb.scu("write", ("mask", "event"), 0)
                    tb.scu("write", ("notifier", e, "trigger"), 1 << cid)
                    tb.scu("elw", ("notifier", e, "wait") if k == 3 else ("event", "wait_any"))
                    store()
                elif k == 5:
                    tb.scu("elw", ("mutex", 0, "lock"))
                    store()
                    tb.compute(rng.randint(1, 4))
                    tb.scu("write", ("mutex", 0, "unlock"), rng.randrange(1, 1000))
                else:
                    tb.mem("lw", 0x1000 + 4 * rng.randrange(n))
                    store(1)
            tb.scu("elw", ("barrier", 0, "wait_all"))
            store()
        progs.append(tb.build(roll=False))
    return progs


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_run_traces_xp_matches_engine_on_random_scu_traces(seed):
    """Every encoded SCU op (masks, buffer clears, targeted and broadcast
    notifiers, wait-any, reads, mutex messages), in the orders the lanes
    meet them within a cycle, against the lockstep engine."""
    for n in (2, 3, 4, 8):
        cl = Cluster(n_cores=n, scu=SCU(n_cores=n), mode="lockstep")
        cl.load(_random_scu_traces(seed, n))
        ref = cl.run(max_cycles=100_000)
        _assert_matches_engine(run_traces_xp(_random_scu_traces(seed, n), n_banks=2 * n),
                               cl, ref)


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_run_traces_jax_matches_numpy_on_random_scu_traces():
    from repro.core.scu.trace import run_traces_jax

    for seed in (3, 4):
        ref = run_traces_xp(_random_scu_traces(seed, 4), n_banks=8)
        got = run_traces_jax(_random_scu_traces(seed, 4), n_banks=8)
        assert got["cycles"] == ref["cycles"]
        for name in _COUNTERS:
            assert got["counters"][name].tolist() == ref["counters"][name].tolist(), name
        assert got["finished_at"].tolist() == ref["finished_at"].tolist()
        assert got["tcdm"] == ref["tcdm"]


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_table_without_scu_rows_carries_no_scu_state():
    """The SCU state and phase exist only for tables with SCU rows: a
    pure-TCDM table's loop carries the 14 arrays of the TCDM state, the
    iteration count and the live flag, and has no ``scu.sync`` scope."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.scu.trace import _execute, _jitted_execute, _pack_tables

    def loop(policy):
        fb = prep_barrier_bench(policy, 4, sfr=0, iters=2, compiled=True)
        tab, addrs, scu = _pack_tables(fb.config.programs)
        args = (tab.astype(np.int32), np.zeros(max(len(addrs), 1), np.int32), np.int32(100))
        kw = dict(n_banks=8, tas_cycles=3, scu=scu)
        jaxpr = jax.make_jaxpr(functools.partial(_execute, jnp, **kw))(*args)
        (w,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
        text = _jitted_execute().lower(*args, **kw).as_text(debug_info=True)
        return scu, len(w.outvars), "scu.sync" in text

    assert loop("sw") == (None, 16, False)
    scu, carried, scoped = loop("scu")
    assert scu == (1, 1) and carried > 16 and scoped


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("policy", ["sw", "scu", "tree-hierarchy"])
def test_loop_body_has_no_gather_or_scatter(policy):
    """Every read and write of the loop body is a dense one-hot select: the
    outer cycle loop and the decode loop nested in it hold no gather and no
    scatter, each of which is a device kernel of its own.  The same holds
    for a hierarchical table's response countdown."""
    import jax
    import jax.numpy as jnp

    from repro.core.scu.trace import _execute, _pack_tables

    if policy == "tree-hierarchy":
        fb = _HIER_JOBS["tree"](_HIER)
        tab, addrs, scu = _pack_tables(fb.config.programs, _HIER, 64)
        kw = dict(n_banks=64, tas_cycles=3, scu=scu, hop=_HIER.latency)
    else:
        fb = prep_barrier_bench(policy, 8, sfr=0, iters=2, compiled=True)
        tab, addrs, scu = _pack_tables(fb.config.programs)
        kw = dict(n_banks=16, tas_cycles=3, scu=scu)
    args = (tab.astype(np.int32), np.zeros(max(len(addrs), 1), np.int32), np.int32(100))
    jaxpr = jax.make_jaxpr(functools.partial(_execute, jnp, **kw))(*args)
    (outer,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    seen = []

    def walk(eqn):
        seen.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                for e in getattr(sub, "eqns", ()):
                    walk(e)

    walk(outer)
    assert seen.count("while") == 2  # the cycle loop and the decode loop
    assert not [p for p in seen if p.startswith(("gather", "scatter"))]


def _parity_job(job):
    """A fresh job for the numpy-vs-jax parity test: its programs and the
    executor's keywords.  The SCU mutex has no TCDM word; ``hierarchy``
    is the MemPool-style tree barrier of :data:`_HIER_JOBS`."""
    if job in _TRACE_SETS:
        n = 4 if job == "tcdm" else 8
        return _TRACE_SETS[job](n), dict(n_banks=2 * n)
    fb = _scu_job(("mutex", "scu", 10, 0), 4) if job == "scu" else _HIER_JOBS["tree"](_HIER)
    cl = fb.config.cluster
    return fb.config.programs, dict(n_banks=cl.n_banks, hierarchy=cl.hierarchy)


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("traces", (*_TRACE_SETS, "scu", "hierarchy"))
def test_run_traces_jax_matches_numpy(traces):
    """Both namespaces return the same result, field for field and type
    for type: ints for the cycles and conflicts, int32 rows for the
    counters and retire cycles, and the TCDM words as a dict of ints
    (empty for a table with no TCDM word)."""
    from repro.core.scu.trace import run_traces_jax

    progs, kw = _parity_job(traces)
    ref = run_traces_xp(progs, **kw)
    got = run_traces_jax(_parity_job(traces)[0], **kw)
    assert got.keys() == ref.keys()
    for res in (ref, got):
        assert type(res["cycles"]) is int and type(res["bank_conflicts"]) is int
        for row in (*res["counters"].values(), res["finished_at"]):
            assert type(row) is np.ndarray and row.dtype == np.int32 and row.shape == (len(progs),)
        assert type(res["tcdm"]) is dict
        assert all(type(k) is int and type(v) is int for k, v in res["tcdm"].items())
        assert (res["tcdm"] == {}) == (traces == "scu")
    assert got["cycles"] == ref["cycles"]
    assert got["bank_conflicts"] == ref["bank_conflicts"]
    assert list(got["counters"]) == list(ref["counters"]) == list(_COUNTERS)
    for name in _COUNTERS:
        assert got["counters"][name].tolist() == ref["counters"][name].tolist()
    assert got["finished_at"].tolist() == ref["finished_at"].tolist()
    assert got["tcdm"] == ref["tcdm"]


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("traces", ["tcdm", "hierarchy"], ids=["flat", "hierarchy"])
def test_jax_executor_reads_its_result_back_in_one_transfer(traces, monkeypatch):
    """The jax executor fetches its whole result in one explicit
    ``jax.device_get`` inside ``scu.readback``, and turns no device array
    into host data any other way.  On the CPU the transfer guard does not
    fire, so the array class's host conversions raise outside
    ``device_get`` for the call."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core.scu.trace import run_traces_jax

    progs, kw = _parity_job(traces)
    run_traces_jax(_parity_job(traces)[0], **kw)  # compile outside the guarded call
    fetches, inside = [], [False]
    device_get = jax.device_get

    def counted_get(x):
        fetches.append(time.perf_counter_ns())
        inside[0] = True
        try:
            return device_get(x)
        finally:
            inside[0] = False

    array_cls = type(jnp.zeros(1))
    for name in ("__array__", "__buffer__", "__int__", "__index__", "__bool__", "__float__",
                 "tolist", "item"):
        conversion = getattr(array_cls, name)

        def guarded(self, *a, _conversion=conversion, _name=name, **k):
            if not inside[0]:
                raise AssertionError(f"implicit device-to-host transfer through {_name}")
            return _conversion(self, *a, **k)

        monkeypatch.setattr(array_cls, name, guarded)
    monkeypatch.setattr(jax, "device_get", counted_get)
    t0 = time.perf_counter()
    res = run_traces_jax(progs, **kw)
    monkeypatch.undo()
    (readback,) = [e for e in obs.events(t0, time.perf_counter()) if e.name == "scu.readback"]
    assert len(fetches) == 1 and readback.start_ns <= fetches[0] <= readback.end_ns
    assert res["cycles"] > 0


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


@pytest.mark.parametrize("xp_name,job", [
    ("numpy", "tcdm"), ("jax", "tcdm"), ("numpy", "scu"), ("jax", "scu"),
], ids=["numpy", "jax", "numpy-scu", "jax-scu"])
def test_executors_raise_when_cut_at_max_cycles(xp_name, job):
    """Neither executor returns partial counters for an unfinished run,
    also when the cut finds lanes asleep on an elw (an ``scu`` mutex with
    a long critical section: the waiters sleep through it)."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    if job == "tcdm":
        progs, n_banks = _tcdm_traces(4), 8
    else:
        ref = _scu_job(("mutex", "scu", 10, 0), 4).run_sequential().stats
        assert ref.total_gated > 0
        fb = _scu_job(("mutex", "scu", 10, 0), 4)
        progs, n_banks = fb.config.programs, fb.config.cluster.n_banks
    with pytest.raises(RuntimeError, match="did not finish within 20 cycles"):
        run(progs, n_banks=n_banks, max_cycles=20)


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("policy", ["scu", "sw"])
def test_executor_counts_its_scu_transactions(xp_name, policy):
    """One run records one ``scu.sync_ops`` count, the lanes' summed
    ``scu_accesses``: 0 for a pure-TCDM table."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro import obs
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    fb = prep_barrier_bench(policy, 4, sfr=0, iters=3, compiled=True)
    t0 = time.perf_counter()
    res = run(fb.config.programs, n_banks=fb.config.cluster.n_banks)
    ev = obs.events(t0, time.perf_counter())
    counts = [e.n for e in ev if e.name == "scu.sync_ops"]
    assert counts == [int(res["counters"]["scu_accesses"].sum())]
    assert (counts[0] > 0) == (policy == "scu")
    assert [e.n for e in ev if e.name == "scu.remote_accesses"] == [0]  # a flat cluster


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
    """The numpy loop is never traced.  The jax loop is traced once per new
    table shape, under the ``scu.loop`` of the call that meets it: a second
    call with the same shape and other contents runs from jax's cache, and
    a call with a new shape traces once more."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro import obs
    from repro.core.scu.trace import _jitted_execute, run_traces_jax

    run = run_traces_xp
    if xp_name == "jax":
        run = run_traces_jax
        _jitted_execute().clear_cache()  # earlier tests may have met these shapes
    t0 = time.perf_counter()
    for progs in (_tcdm_traces(4), _tcdm_traces(4, base=100), _tcdm_traces(2)):
        run(progs, n_banks=8)
    ev = obs.events(t0, time.perf_counter())
    traces = [e for e in ev if e.name == "scu.loop_traces"]
    loops = [e.id for e in ev if e.name == "scu.loop"]
    assert len(loops) == 3
    if xp_name == "numpy":
        assert traces == []
    else:
        assert [(e.n, e.parent) for e in traces] == [(1, loops[0]), (1, loops[2])]


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_jax_executor_cache_hits_match_numpy():
    """Fig. 5's three ``sw`` barrier jobs (SFR 250, 1000, 4000) share one
    table shape, ``(8, 38, 9)`` over 3 addresses as in the benchmark's 16
    iterations, so the second and third jax calls reuse the first call's
    program: each still equals numpy bit for bit.  A later call with a
    smaller cycle cap reuses it too, and is still cut at that cap."""
    from repro import obs
    from repro.core.scu.trace import _jitted_execute, run_traces_jax

    def progs(sfr):
        fb = prep_barrier_bench("sw", 8, sfr=sfr, iters=4, compiled=True)
        return fb.config.programs, fb.config.cluster.n_banks

    _jitted_execute().clear_cache()
    t0 = time.perf_counter()
    for sfr in (250, 1000, 4000):
        p, n_banks = progs(sfr)
        got = run_traces_jax(p, n_banks=n_banks)
        p, n_banks = progs(sfr)
        ref = run_traces_xp(p, n_banks=n_banks)
        assert got["cycles"] == ref["cycles"]
        assert got["bank_conflicts"] == ref["bank_conflicts"]
        for name in _COUNTERS:
            assert got["counters"][name].tolist() == ref["counters"][name].tolist(), name
        assert got["finished_at"].tolist() == ref["finished_at"].tolist()
        assert got["tcdm"] == ref["tcdm"]
    p, n_banks = progs(250)
    with pytest.raises(RuntimeError, match="did not finish within 100 cycles"):
        run_traces_jax(p, n_banks=n_banks, max_cycles=100)
    traces = [e for e in obs.events(t0, time.perf_counter()) if e.name == "scu.loop_traces"]
    assert sum(e.n for e in traces) == 1


def _counted_run(xp_name, progs, **kw):
    """One executor call: its result and its ``scu.loop_iterations`` count."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro import obs
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    t0 = time.perf_counter()
    res = run(progs, **kw)
    (iters,) = [e.n for e in obs.events(t0, time.perf_counter()) if e.name == "scu.loop_iterations"]
    return res, iters


def _engine_and_executor(xp_name, prep):
    """The lockstep engine's run of ``prep()``, and the executor's."""
    fb = prep()
    cl = fb.config.cluster
    cl.load(fb.config.programs)
    ref = cl.run()
    fb = prep()
    res, iters = _counted_run(xp_name, fb.config.programs, n_banks=fb.config.cluster.n_banks)
    _assert_matches_engine(res, cl, ref)
    return ref, res, iters


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("policy,sfr", [("sw", 4000), ("tree4", 1000)])
def test_executors_jump_fig5_compute_spans(xp_name, policy, sfr):
    """A Fig. 5 barrier between long compute spans: the loop jumps each span
    in one iteration, bit for bit against the engine, in at most a tenth
    as many iterations as cycles."""
    ref, res, iters = _engine_and_executor(
        xp_name, lambda: prep_barrier_bench(policy, 8, sfr=sfr, iters=4, compiled=True,
                                            mode="lockstep"))
    assert iters <= res["cycles"] / 10


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executors_step_table1_arbitration_cycle_by_cycle(xp_name):
    """The Table 1 ``sw`` barrier at SFR 0 contends for its lock in almost
    every cycle, so the jump almost never engages: at least 98% as many
    iterations as cycles (the cycles it skips are the few in which every
    core counts down a branch or the TAS latency at once)."""
    fb = prep_barrier_bench("sw", 8, sfr=0, iters=64, compiled=True)
    res, iters = _counted_run(xp_name, fb.config.programs, n_banks=fb.config.cluster.n_banks)
    assert res["cycles"] == 11016
    assert 0.98 * res["cycles"] <= iters < res["cycles"]


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_executors_jump_while_waiters_sleep(xp_name):
    """The SCU mutex with t_crit 10: the waiters sleep through each critical
    section while the owner computes, and the loop jumps those cycles with
    the sleepers' ``gated_cycles`` counted as the engine counts them."""
    ref, res, iters = _engine_and_executor(xp_name, lambda: _scu_job(("mutex", "scu", 10, 0), 8))
    assert ref.total_gated > 0
    assert iters < res["cycles"] / 2


def _asleep_for_good(n):
    """Lanes that compute, then sleep on a notifier no lane triggers."""
    out = []
    for _ in range(n):
        tb = TraceBuilder()
        tb.compute(5)
        tb.scu("elw", ("notifier", 3, "wait"))
        out.append(tb.build())
    return out


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("job", ["tcdm", "scu", "asleep"])
def test_cycle_cap_inside_a_compute_span_is_not_passed(xp_name, job):
    """A cap that falls inside a jumped span (Fig. 5's 4000-cycle compute,
    the SCU barrier's 42-cycle one) stops the loop at the cap, not past it,
    and the run still raises.  Lanes asleep for good bound no jump but keep
    the loop running to the cap: it never takes them for halted."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro.core.scu.trace import _execute, _jitted_execute, _pack_tables, run_traces_jax

    n_banks = 16
    if job == "tcdm":
        progs, cap = lambda: prep_barrier_bench("sw", 8, sfr=4000, iters=2,
                                                compiled=True).config.programs, 2000
    elif job == "scu":
        progs, cap = lambda: _scu_job(("barrier", "scu", 0, 42), 8).config.programs, 30
    else:
        progs, cap = lambda: _asleep_for_good(8), 30
    tab, addrs, scu = _pack_tables(progs())
    addr_bank = ((addrs >> 2) % n_banks).astype(np.int32) if len(addrs) else np.zeros(1, np.int32)
    args = (tab.astype(np.int32), addr_bank, np.int32(cap))
    run = functools.partial(_execute, np) if xp_name == "numpy" else _jitted_execute()
    state = run(*args, n_banks=n_banks, tas_cycles=3, scu=scu)
    assert int(state["cycle"]) == cap and bool(state["live"])
    cnt = np.asarray(state["cnt"])
    assert (cnt[0] + cnt[3]).tolist() == [cap] * 8  # active_cycles + gated_cycles
    assert int(state["iters"]) < cap
    if job == "asleep":
        assert cnt[3].min() > 0
    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    with pytest.raises(RuntimeError, match=f"did not finish within {cap} cycles"):
        run(progs(), n_banks=n_banks, max_cycles=cap)


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("job", [("barrier", "sw", 0, 1000), ("mutex", "scu", 10, 0),
                                 ("barrier", "scu", 0, 42), ("barrier", "tree_ew", 0, 0)],
                         ids=["sw-1000", "scu-mutex-10", "scu-42", "tree_ew"])
def test_numpy_and_jax_count_the_same_iterations(job):
    counts = []
    for xp_name in ("numpy", "jax"):
        fb = _scu_job(job, 8)
        res, iters = _counted_run(xp_name, fb.config.programs, n_banks=fb.config.cluster.n_banks)
        counts.append((res["cycles"], iters))
    assert counts[0] == counts[1]
    assert counts[0][1] < counts[0][0]


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


# A MemPool-style cluster at test size: 16 cores in 4 tiles of 4, 2 tiles a
# group, 2 groups and 64 banks (banking factor 4), so that every hop class
# (tile, group, remote) is present.  Each job takes the hierarchy and the
# engine mode.
_HIER = Hierarchy(cores_per_tile=4, tiles_per_group=2, groups=2, latency=(1, 3, 5))
_HIER_JOBS = {
    "sw": lambda h, mode="lockstep": prep_barrier_bench(
        "sw", 16, sfr=0, iters=3, compiled=True, mode=mode,
        interconnect={"banking_factor": 4, "hierarchy": h}),
    "tree": lambda h, mode="lockstep": prep_barrier_bench(
        "tree", 16, sfr=0, iters=4, compiled=True, mode=mode,
        interconnect={"banking_factor": 4, "hierarchy": h}),
    "tree4": lambda h, mode="lockstep": prep_barrier_bench(
        "tree4", 16, sfr=20, iters=4, compiled=True, mode=mode,
        interconnect={"banking_factor": 4, "hierarchy": h}),
    "mutex": lambda h, mode="lockstep": prep_mutex_bench(
        "sw", 16, t_crit=3, iters=2, compiled=True, mode=mode,
        interconnect={"banking_factor": 4, "hierarchy": h}),
}


def _engine_run(fb):
    """The lockstep engine's run of a prepared job: its stats, its cluster
    and the count of its grants to a bank outside the granted core's tile."""
    cl = fb.config.cluster
    remote = [0]
    grant = cl._grant_mem

    def counted(core):
        bank = cl._bank_of(core.pending.addr)
        remote[0] += int(cl.hierarchy.hop_class(core.cid, bank, cl.n_banks)) != 0
        grant(core)

    if cl.hierarchy is not None:
        cl._grant_mem = counted
    cl.load(fb.config.programs)
    return cl.run(), cl, remote[0]


def _hier_run(xp_name, fb):
    """One executor call on ``fb``'s cluster: its result and its
    ``scu.remote_accesses`` counts."""
    if xp_name == "jax" and not HAS_JAX:
        pytest.skip("jax unavailable")
    from repro import obs
    from repro.core.scu.trace import run_traces_jax

    run = run_traces_xp if xp_name == "numpy" else run_traces_jax
    cl = fb.config.cluster
    t0 = time.perf_counter()
    res = run(fb.config.programs, n_banks=cl.n_banks, hierarchy=cl.hierarchy)
    ev = obs.events(t0, time.perf_counter())
    return res, [e.n for e in ev if e.name == "scu.remote_accesses"]


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("job", tuple(_HIER_JOBS))
def test_executors_match_engine_on_a_hierarchy(job, xp_name):
    """MemPool's hop latencies (1, 3 and 5 cycles) against the lockstep
    engine, bit for bit: cycles, the nine counters, conflicts, retire
    cycles and the final TCDM words; and ``scu.remote_accesses`` is the
    engine's count of grants outside the core's tile.  The hops change the
    run: the flat cluster's differs."""
    ref, cl, remote = _engine_run(_HIER_JOBS[job](_HIER))
    res, counted = _hier_run(xp_name, _HIER_JOBS[job](_HIER))
    _assert_matches_engine(res, cl, ref)
    assert counted == [remote] and remote > 0
    flat, _, _ = _engine_run(_HIER_JOBS[job](None))
    assert flat != ref


@pytest.mark.parametrize("xp_name", ["engine", "numpy", "jax"])
@pytest.mark.parametrize("job", tuple(_HIER_JOBS))
def test_one_cycle_hops_are_the_flat_cluster(job, xp_name):
    """A hierarchy whose every latency is 1 runs as the flat cluster, bit
    for bit; only the remote count tells them apart (0 when flat)."""
    ones = dataclasses.replace(_HIER, latency=(1, 1, 1))
    if xp_name == "engine":
        (got, cl_got, remote), (ref, cl_ref, _) = (
            _engine_run(_HIER_JOBS[job](h)) for h in (ones, None))
        assert got == ref and cl_got.tcdm == cl_ref.tcdm and remote > 0
        return
    (got, remote), (ref, flat) = (_hier_run(xp_name, _HIER_JOBS[job](h)) for h in (ones, None))
    assert got["cycles"] == ref["cycles"]
    assert got["bank_conflicts"] == ref["bank_conflicts"]
    for name in _COUNTERS:
        assert got["counters"][name].tolist() == ref["counters"][name].tolist(), name
    assert got["finished_at"].tolist() == ref["finished_at"].tolist()
    assert got["tcdm"] == ref["tcdm"]
    assert flat == [0] and remote[0] > 0


@pytest.mark.parametrize("tier", ["fastforward", "fleet", "slot_fleet"])
def test_fast_forward_tiers_refuse_a_hierarchy(tier):
    """The fast-forward tiers and the fleets model the flat interconnect:
    they refuse a hierarchical cluster by name rather than run it flat."""
    fb = _HIER_JOBS["tree"](_HIER, mode="fastforward")
    with pytest.raises(ValueError, match="flat interconnect"):
        if tier == "fastforward":
            fb.run_sequential()
        elif tier == "fleet":
            make_fleet([fb])
        else:
            SlotFleet(n_slots=1, slot_cores=16, banking_factor=4).admit(fb.config)


def test_a_hierarchy_must_describe_the_cluster():
    with pytest.raises(ValueError, match="8 cores"):
        Cluster(n_cores=8, hierarchy=_HIER)
    with pytest.raises(ValueError, match="latencies >= 1"):
        Cluster(n_cores=16, hierarchy=dataclasses.replace(_HIER, latency=(0, 3, 5)))
    with pytest.raises(ValueError, match="8 lanes"):
        run_traces_xp(_tcdm_traces(8), n_banks=32, hierarchy=_HIER)


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("hierarchy", [None, _HIER], ids=["flat", "hierarchy"])
def test_only_a_hierarchical_table_carries_response_state(hierarchy):
    """A flat table's loop carries the 16 arrays of the TCDM state and has
    no ``scu.hop`` scope, as before hierarchies; a hierarchical one carries
    each lane's response countdown and remote count besides, under
    ``scu.hop``."""
    import jax
    import jax.numpy as jnp

    from repro.core.scu.trace import _execute, _jitted_execute, _pack_tables

    fb = _HIER_JOBS["tree"](hierarchy)
    tab, addrs, scu = _pack_tables(fb.config.programs, hierarchy, 64)
    assert tab.shape[2] == (9 if hierarchy is None else 10)
    args = (tab.astype(np.int32), np.zeros(len(addrs), np.int32), np.int32(100))
    kw = dict(n_banks=64, tas_cycles=3, scu=scu, hop=hierarchy and hierarchy.latency)
    jaxpr = jax.make_jaxpr(functools.partial(_execute, jnp, **kw))(*args)
    (w,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    text = _jitted_execute().lower(*args, **kw).as_text(debug_info=True)
    assert (len(w.outvars), "scu.hop" in text) == ((16, False) if hierarchy is None else (18, True))
