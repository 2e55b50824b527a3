"""Tests for the continuous-batching sweep service and slot fleet engine.

The load-bearing property is the tentpole guarantee: every job's
``ClusterStats`` is **bit-exact** against a sequential ``Cluster.run()`` of
the same config, no matter when it was admitted or what shared a batched
step with it -- including admissions landing mid-quiescent-span of a
co-resident slot, staggered random arrivals, slot recycling and a
co-resident job timing out.
"""

import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.scu import SCU, Cluster, Compute, Scu
from repro.core.scu.energy import DEFAULT_ENERGY, Activity
from repro.core.scu.engine import FleetConfig, SlotFleet
from repro.core.scu.programs import (
    prep_barrier_bench,
    prep_chain_bench,
    prep_mutex_bench,
    prep_work_queue_bench,
)
from repro.serve.arrivals import bursty_trace, poisson_trace
from repro.serve.energy import job_energy
from repro.core.scu.faults import FaultEvent, FaultPlan, Watchdog
from repro.serve.fleet_service import (
    BreakerPolicy,
    DomainHealth,
    FleetService,
    QueueFull,
    RetryPolicy,
)

POLICIES = ("scu", "tas", "sw", "tree", "tree4", "tree_ew", "fifo")


def make_cluster(n, mode="fastforward"):
    return Cluster(n_cores=n, scu=SCU(n_cores=n), mode=mode)


def _random_stream_benches(seed):
    """A mixed job stream: policies x 8/16/64 cores x several shapes and
    iteration counts, deterministic in ``seed`` (same recipe as the static
    fleet parity suite, sized for a serving stream)."""
    rng = random.Random(seed)
    benches = []
    for _ in range(rng.randint(6, 10)):
        policy = rng.choice(POLICIES)
        n = rng.choice((8, 8, 8, 16, 64))
        shape = rng.choice(("barrier", "mutex", "chain", "wq")) if n <= 16 \
            else "barrier"
        iters = rng.randint(2, 8)
        if shape == "barrier":
            benches.append(prep_barrier_bench(
                policy, n, sfr=rng.choice((0, 13, 100, 900)), iters=iters
            ))
        elif shape == "mutex":
            benches.append(prep_mutex_bench(
                policy, n, t_crit=rng.randint(0, 12),
                sfr=rng.choice((0, 37)), iters=iters,
            ))
        elif shape == "chain":
            benches.append(prep_chain_bench(
                policy, n, sfr=rng.choice((20, 150)), iters=iters,
                depth=rng.choice((1, 4, 8)),
            ))
        else:
            benches.append(prep_work_queue_bench(
                policy, n // 2, n - n // 2, items=2 * n,
                t_produce=rng.randint(1, 40), t_consume=rng.randint(1, 40),
            ))
    return benches


def _serve_stream(svc, benches, arrivals, max_rounds=5_000_000):
    """Drive a service: submit bench i when the round clock passes its
    arrival, step until everything drains.  Returns jobs in submit order."""
    jobs = [None] * len(benches)
    i = 0
    rounds = 0
    while i < len(benches) or svc.pending or svc.active:
        while i < len(benches) and arrivals[i] <= svc.round:
            jobs[i] = svc.submit(benches[i].config)
            i += 1
        svc.step()
        rounds += 1
        assert rounds < max_rounds, "service failed to drain"
    return jobs


# ---------------------------------------------------------------------------
# Tentpole: bit-exact parity under streamed admission
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=9999))
def test_streamed_jobs_match_sequential_bit_exact(seed):
    """Randomized mixed-config stream with staggered Poisson arrivals:
    every job's ClusterStats must be identical to a sequential run of the
    same config -- the service's core contract."""
    seq = [b.run_sequential() for b in _random_stream_benches(seed)]
    benches = _random_stream_benches(seed)
    arrivals = poisson_trace(rate=0.005, n_jobs=len(benches), seed=seed)
    svc = FleetService(n_slots=3, slot_cores=64, queue_limit=64)
    jobs = _serve_stream(svc, benches, arrivals)
    for job, b, ref in zip(jobs, benches, seq):
        assert job.error is None
        assert b.finalize(job.stats) == ref, (
            f"stream diverged (seed={seed}): {ref.variant}/{ref.primitive}"
            f"@{ref.n_cores}"
        )
        assert job.latency_rounds >= 1
        assert job.queue_rounds >= 0


def test_admission_mid_quiescent_span_of_co_resident_slot():
    """Adversarial timing: slot 0 runs an all-cores-asleep long compute
    span; a FIFO churner is admitted while that span is in flight (and
    vice versa, a sleeper admitted mid-churn).  Both must stay bit-exact,
    and the sleeper's span must still be covered by fast-forward jumps."""

    def sleeper_cfg(span=50_000):
        from repro.core.scu.primitives import scu_barrier

        cl = make_cluster(8)

        def prog(cluster, cid):
            yield Compute(span)
            yield from scu_barrier(cluster, cid)

        return FleetConfig(cluster=cl, programs=[prog] * 8)

    def churner_cfg(items=200):
        cl = make_cluster(8)

        def producer(cluster, cid):
            for v in range(items):
                yield Compute(3)
                yield Scu("elw", ("fifo", 1, "push_wait"), v % 256)

        def consumer(cluster, cid):
            for _ in range(items):
                yield Scu("elw", ("fifo", 1, "pop"))

        def idle(cluster, cid):
            yield Compute(1)

        return FleetConfig(cluster=cl, programs=[producer, consumer] + [idle] * 6)

    ref = []
    for mk in (sleeper_cfg, churner_cfg):
        cfg = mk()
        cfg.cluster.load(cfg.programs)
        ref.append(cfg.cluster.run())

    for first, second, ref_first, ref_second in (
        (sleeper_cfg, churner_cfg, ref[0], ref[1]),
        (churner_cfg, sleeper_cfg, ref[1], ref[0]),
    ):
        fleet = SlotFleet(n_slots=2, slot_cores=8)
        cfg_a = first()
        slot_a = fleet.admit(cfg_a)
        # one round: A's generators advance and latch their countdowns --
        # the admission below lands mid-quiescent-span, before A's jump
        assert not fleet.advance()
        cfg_b = second()
        slot_b = fleet.admit(cfg_b)
        done = {}
        rounds = 0
        while fleet.occupied:
            for m in fleet.advance():
                done[m.index] = m.cluster.stats
                fleet.free(m.index)
            rounds += 1
            assert rounds < 10**6
        assert done[slot_a] == ref_first
        assert done[slot_b] == ref_second
        if first is sleeper_cfg:
            assert cfg_a.cluster.ff_cycles > 0.9 * ref_first.cycles, (
                "sleeper degraded to stepping while sharing the fleet"
            )


def test_slot_recycling_preserves_parity():
    """A slot that hosted a dirty job (FIFO traffic, latched elw waits)
    must be indistinguishable from a fresh one for its next occupant."""
    ref = prep_barrier_bench("scu", 8, sfr=10, iters=3).run_sequential()

    fleet = SlotFleet(n_slots=1, slot_cores=16)
    results = []
    for policy in ("tas", "scu", "fifo", "scu"):
        b = prep_barrier_bench(policy, 8, sfr=10, iters=3)
        slot = fleet.admit(b.config)
        assert slot == 0  # single slot, recycled every time
        rounds = 0
        while fleet.occupied:
            for m in fleet.advance():
                results.append((policy, b.finalize(m.cluster.stats)))
                fleet.free(m.index)
            rounds += 1
            assert rounds < 10**6
    for policy, res in results:
        if policy == "scu":
            assert res == ref, "recycled slot diverged from fresh run"


def test_timeout_contained_to_one_slot():
    """A deadlocked job must burn to its cap and fail alone -- with the
    exact message the sequential engine raises -- while a co-resident job
    finishes untouched; the failed slot must be recyclable."""
    def sleeper(cluster, cid):
        yield Scu("elw", ("notifier", 5, "wait"))

    def finisher(cluster, cid):
        yield Compute(3)

    dead = FleetConfig(
        cluster=make_cluster(2), programs=[sleeper, finisher], max_cycles=4096
    )
    # sequential reference failure
    seq = make_cluster(2)
    seq.load([sleeper, finisher])
    with pytest.raises(RuntimeError, match="did not finish") as exc:
        seq.run(max_cycles=4096)

    ok_bench = prep_barrier_bench("scu", 8, sfr=10, iters=3)
    ok_ref = prep_barrier_bench("scu", 8, sfr=10, iters=3).run_sequential()

    svc = FleetService(n_slots=2, slot_cores=8)
    j_dead = svc.submit(dead)
    j_ok = svc.submit(ok_bench.config)
    svc.run_until_drained()
    assert j_ok.error is None
    assert ok_bench.finalize(j_ok.stats) == ok_ref
    assert j_dead.failed
    assert j_dead.error == str(exc.value)
    assert "SLEEP" in j_dead.error  # deadlock state captured at the cap
    assert dead.cluster.cycle == 4096
    # the poisoned slot must serve the next job cleanly
    b2 = prep_barrier_bench("scu", 8, sfr=10, iters=3)
    j2 = svc.submit(b2.config)
    svc.run_until_drained()
    assert j2.error is None
    assert b2.finalize(j2.stats) == ok_ref


# ---------------------------------------------------------------------------
# Scheduling semantics: FIFO, backpressure, drain baseline, accounting
# ---------------------------------------------------------------------------


def test_jobs_admitted_fifo():
    """With one slot, jobs must be admitted -- and therefore finish -- in
    submission order, whatever their relative lengths."""
    svc = FleetService(n_slots=1, slot_cores=8, queue_limit=16)
    jobs = [
        svc.submit(prep_barrier_bench(p, 8, sfr=s, iters=i).config)
        for p, s, i in (("sw", 400, 6), ("scu", 0, 2), ("tas", 10, 3))
    ]
    done = svc.run_until_drained()
    assert [j.job_id for j in done] == [j.job_id for j in jobs]
    admits = [j.admitted_round for j in jobs]
    assert admits == sorted(admits)
    assert all(
        a.finished_round < b.admitted_round for a, b in zip(jobs, jobs[1:])
    ), "one slot: next job admits only after the previous finished"


def test_backpressure_rejects_deterministically():
    """A full queue must reject with QueueFull (the documented choice) and
    accept again after a slot drains the backlog."""
    svc = FleetService(n_slots=1, slot_cores=8, queue_limit=2)

    def mk():
        return prep_barrier_bench("scu", 8, sfr=0, iters=2).config

    svc.submit(mk())
    svc.submit(mk())
    with pytest.raises(QueueFull, match="queue full"):
        svc.submit(mk())
    assert svc.try_submit(mk()) is None  # non-raising twin, same decision
    assert svc.pending == 2
    svc.run_until_drained()
    assert svc.try_submit(mk()) is not None  # capacity is back


def test_submit_validates_configs_upfront():
    """Inadmissible configs never enter the queue: too-wide jobs, wrong
    engine mode and already-used clusters are rejected at submit()."""
    svc = FleetService(n_slots=2, slot_cores=8)

    with pytest.raises(ValueError, match="slot width"):
        svc.submit(prep_barrier_bench("scu", 16, sfr=0, iters=2).config)

    def prog(cluster, cid):
        yield Compute(1)

    with pytest.raises(ValueError, match="fastforward"):
        svc.submit(FleetConfig(
            cluster=make_cluster(2, mode="lockstep"), programs=[prog] * 2
        ))
    used = make_cluster(2)
    used.load([prog] * 2)
    used.run()
    with pytest.raises(ValueError, match="fresh"):
        svc.submit(FleetConfig(cluster=used, programs=[prog] * 2))
    assert svc.pending == 0


def test_continuous_beats_drain_on_stream():
    """Same stream, same fleet geometry: continuous admission must finish
    no later and waste fewer lane-rounds than the drain baseline -- the
    utilization argument the service exists for."""
    def build():
        return [
            prep_barrier_bench(p, n, sfr=s, iters=i)
            for p, n, s, i in (
                ("sw", 8, 400, 8), ("scu", 8, 0, 2), ("tas", 8, 10, 3),
                ("scu", 16, 0, 2), ("fifo", 8, 10, 4), ("scu", 8, 900, 2),
            )
        ]

    totals = {}
    for mode in ("continuous", "drain"):
        svc = FleetService(
            n_slots=2, slot_cores=16, admission=mode, queue_limit=16
        )
        for b in build():
            svc.submit(b.config)
        svc.run_until_drained()
        totals[mode] = (svc.round, svc.idle_lane_fraction)
    assert totals["continuous"][0] <= totals["drain"][0]
    assert totals["continuous"][1] < totals["drain"][1]


def test_latency_accounting_spans_queue_and_service():
    """latency = queue wait + service rounds (inclusive); the second job on
    a single-slot fleet must carry the first job's service time as queue
    rounds."""
    svc = FleetService(n_slots=1, slot_cores=8)
    a = svc.submit(prep_barrier_bench("scu", 8, sfr=100, iters=4).config)
    b = svc.submit(prep_barrier_bench("scu", 8, sfr=0, iters=2).config)
    svc.run_until_drained()
    assert a.queue_rounds == 0 and a.admitted_round == 0
    assert b.queue_rounds == a.finished_round + 1 - b.submitted_round
    for j in (a, b):
        assert j.latency_rounds == j.finished_round - j.submitted_round + 1


def test_slot_fleet_rejects_misuse():
    fleet = SlotFleet(n_slots=1, slot_cores=8)
    with pytest.raises(ValueError, match="at least one slot"):
        SlotFleet(n_slots=0, slot_cores=8)
    with pytest.raises(ValueError, match="already free"):
        fleet.free(0)
    b = prep_barrier_bench("scu", 8, sfr=0, iters=2)
    fleet.admit(b.config)
    with pytest.raises(ValueError, match="still running"):
        fleet.free(0)
    with pytest.raises(RuntimeError, match="no free slot"):
        fleet.admit(prep_barrier_bench("scu", 8, sfr=0, iters=2).config)


# ---------------------------------------------------------------------------
# Recovery: retry with backoff, degradation, terminal failures
# ---------------------------------------------------------------------------


def _lost_wake_plan(victim=3):
    # lose the barrier wake (EV.BARRIER = line 8) on one core: the whole
    # barrier deadlocks and the job burns to its cycle cap
    return FaultPlan([FaultEvent("lost_wake", cycle=10, core=victim,
                                 lines=1 << 8)])


def _transient_factory(attempt):
    """Faulty on attempt 1, clean after -- the retryable failure."""
    fb = prep_barrier_bench("scu", 8, sfr=20, iters=6)
    fb.config.max_cycles = 4096
    if attempt == 1:
        fb.config.cluster.faults = _lost_wake_plan()
    return fb.config


def _persistent_factory(attempt):
    """Every scu attempt loses the wake -- only degradation can help."""
    fb = prep_barrier_bench("scu", 8, sfr=20, iters=6)
    fb.config.max_cycles = 4096
    fb.config.cluster.faults = _lost_wake_plan()
    return fb.config


def _sw_fallback(attempt):
    fb = prep_barrier_bench("sw", 8, sfr=20, iters=6)
    return fb.config


def test_run_until_drained_terminates_on_permanent_failures():
    """Regression (the satellite fix): a queue holding only jobs that fail
    terminally must drain -- failed jobs leave the system instead of
    spinning the loop to max_rounds."""
    svc = FleetService(n_slots=1, slot_cores=8,
                       retry=RetryPolicy(max_attempts=2, backoff_rounds=1))
    jobs = [svc.submit(factory=_persistent_factory) for _ in range(3)]
    done = svc.run_until_drained(max_rounds=200_000)
    assert len(done) == 3
    assert all(j.state == "failed" and j.failed for j in jobs)
    assert all(j.attempts == 2 and len(j.fault_log) == 2 for j in jobs)
    assert all(j.finished_round is not None for j in jobs)
    assert not svc.pending and not svc._backoff and not svc.active


def test_retry_recovers_transient_fault():
    svc = FleetService(n_slots=2, slot_cores=8,
                       retry=RetryPolicy(max_attempts=3))
    j = svc.submit(factory=_transient_factory)
    svc.run_until_drained()
    assert j.state == "done" and j.error is None
    assert j.attempts == 2 and j.degraded is False
    assert len(j.fault_log) == 1
    log = j.fault_log[0]
    assert log["attempt"] == 1 and log["cycles"] == 4096
    assert "did not finish" in log["error"]
    assert j.wasted_cycles == 4096  # exactly the failed attempt's burn
    assert j.stats is not None


def test_no_retry_policy_keeps_fail_fast():
    svc = FleetService(n_slots=1, slot_cores=8)
    j = svc.submit(factory=_persistent_factory)
    svc.run_until_drained()
    assert j.state == "failed" and j.attempts == 1
    assert j.error is not None and "did not finish" in j.error


def test_retry_relowers_trace_configs():
    """Regression (PR-8 satellite): ``TraceProgram``s are single-use
    (cursor semantics mirror ``FaultPlan``), and a retry factory commonly
    rebuilds only the cluster while reusing the lowered traces -- lowering
    is the expensive part.  The service must hand every attempt fresh
    cursors instead of letting attempt 2 crash on the consumed programs."""
    fb = prep_barrier_bench("tas", 8, sfr=20, iters=6, compiled=True)
    traced = fb.config.programs
    assert all(getattr(p, "is_traced", False) for p in traced)
    ref = prep_barrier_bench("tas", 8, sfr=20, iters=6).run_sequential().stats

    def factory(attempt):
        fresh = prep_barrier_bench("tas", 8, sfr=20, iters=6)
        # attempt 1 is capped far below the real runtime, so it fails and
        # forces a retry over the *same* trace objects
        cap = 64 if attempt == 1 else 4_000_000
        return FleetConfig(
            cluster=fresh.config.cluster, programs=traced, max_cycles=cap
        )

    svc = FleetService(
        n_slots=2, slot_cores=8, retry=RetryPolicy(max_attempts=3)
    )
    j = svc.submit(factory=factory)
    svc.run_until_drained()
    assert j.state == "done" and j.error is None
    assert j.attempts == 2
    assert j.stats == ref  # retried attempt is still bit-exact


def test_backoff_grows_exponentially():
    """With backoff_rounds=2, factor=3 the re-queue delays are 2 then 6
    rounds: the gap between consecutive failures must grow while the
    per-attempt service time stays constant."""
    svc = FleetService(
        n_slots=1, slot_cores=8,
        retry=RetryPolicy(max_attempts=3, backoff_rounds=2, backoff_factor=3),
    )
    j = svc.submit(factory=_persistent_factory)
    svc.run_until_drained()
    assert j.attempts == 3 and j.state == "failed"
    r1, r2, r3 = (e["round"] for e in j.fault_log)
    assert r2 - r1 >= 1 + 2  # backoff + re-service
    assert (r3 - r2) - (r2 - r1) == 4  # delay grew 2 -> 6
    assert all(e["degraded"] is False for e in j.fault_log)


def test_degrade_to_fallback_policy():
    svc = FleetService(
        n_slots=2, slot_cores=8,
        retry=RetryPolicy(max_attempts=3, degrade_after=1),
    )
    j = svc.submit(factory=_persistent_factory, fallback_factory=_sw_fallback)
    svc.run_until_drained()
    assert j.state == "done" and j.degraded is True
    assert j.attempts == 2 and j.error is None
    # the successful attempt ran the sw fallback: stats match a clean sw run
    ref = prep_barrier_bench("sw", 8, sfr=20, iters=6).run_sequential()
    assert j.stats == ref.stats


def test_degrade_without_fallback_exhausts_attempts():
    svc = FleetService(
        n_slots=1, slot_cores=8,
        retry=RetryPolicy(max_attempts=3, degrade_after=1),
    )
    j = svc.submit(factory=_persistent_factory)  # no fallback given
    svc.run_until_drained()
    assert j.state == "failed" and j.attempts == 3 and j.degraded is False


def test_submit_requires_config_xor_factory():
    svc = FleetService(n_slots=1, slot_cores=8)
    with pytest.raises(ValueError, match="exactly one"):
        svc.submit()
    with pytest.raises(ValueError, match="exactly one"):
        svc.submit(prep_barrier_bench("scu", 8, iters=2).config,
                   factory=_transient_factory)


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="backoff_rounds"):
        RetryPolicy(backoff_rounds=-1)
    with pytest.raises(ValueError, match="backoff_factor"):
        RetryPolicy(backoff_factor=0)
    with pytest.raises(ValueError, match="degrade_after"):
        RetryPolicy(degrade_after=0)


def test_backoff_requeue_bypasses_queue_bound():
    """Satellite contract: a retry re-queue never competes with fresh
    submissions for queue space -- it lands even when the queue is at its
    bound (where try_submit is already rejecting)."""
    svc = FleetService(
        n_slots=1, slot_cores=8, queue_limit=1,
        retry=RetryPolicy(max_attempts=2, backoff_rounds=3),
    )
    j = svc.submit(factory=_persistent_factory)
    # run until the first failure puts the job into backoff
    rounds = 0
    while j.state != "backoff":
        svc.step()
        rounds += 1
        assert rounds < 200_000
    # fill the queue to its bound while the retry waits out the backoff
    filler = svc.submit(prep_barrier_bench("scu", 8, sfr=0, iters=2).config)
    assert svc.try_submit(
        prep_barrier_bench("scu", 8, sfr=0, iters=2).config
    ) is None, "the bound must reject fresh submissions"
    while j.state == "backoff":
        svc.step()
        assert svc.pending <= svc.queue_limit + 1
    assert j.state in ("queued", "running", "failed"), \
        "the requeue must have bypassed the full queue"
    svc.run_until_drained()
    assert filler.state == "done"
    assert j.state == "failed" and j.attempts == 2


def test_retry_config_leaves_clean_traffic_untouched():
    """The recovery machinery must be invisible to jobs that never fail:
    same stream, with and without a RetryPolicy, identical outcomes."""
    def run(retry):
        svc = FleetService(n_slots=2, slot_cores=16, retry=retry,
                           queue_limit=16)
        benches = [
            prep_barrier_bench(p, n, sfr=s, iters=i)
            for p, n, s, i in (
                ("scu", 8, 0, 3), ("tas", 8, 40, 3), ("scu", 16, 10, 2),
                ("fifo", 8, 25, 4),
            )
        ]
        jobs = [svc.submit(b.config) for b in benches]
        svc.run_until_drained()
        return [(j.state, j.attempts, j.stats, j.finished_round)
                for j in jobs], svc.round

    plain, rounds_plain = run(None)
    with_retry, rounds_retry = run(RetryPolicy(max_attempts=3))
    assert plain == with_retry and rounds_plain == rounds_retry
    assert all(state == "done" and attempts == 1
               for state, attempts, _, _ in plain)


# ---------------------------------------------------------------------------
# Tenant isolation under faults (slot scrub fuzz)
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=9999))
def test_tenant_isolation_under_fault_chains(seed):
    """Randomized admit/fail/free/admit chains on a recycled slot: however
    the previous tenant died (deadlock, blackout, armed-but-unfired drop
    filters), the next tenant's run is bit-exact against a fresh fleet and
    its SCU base-unit fault state starts scrubbed."""
    rng = random.Random(seed)
    ref = prep_barrier_bench("scu", 8, sfr=10, iters=3).run_sequential()

    fleet = SlotFleet(n_slots=2, slot_cores=8)
    for _ in range(rng.randint(2, 4)):
        # a faulty tenant: random kind, possibly deadlocking
        kind = rng.choice((
            "lost_wake", "stall", "bank_blackout", "spurious",
            "droop", "scu_blackout", "domain_blackout",
        ))
        fb = prep_barrier_bench(
            rng.choice(("scu", "tas", "fifo")), 8,
            sfr=rng.choice((0, 20)), iters=rng.randint(2, 5),
        )
        if kind == "lost_wake":
            # arm drops on several lines; some never fire before death
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("lost_wake", cycle=rng.randrange(5, 50),
                           core=rng.randrange(8), lines=0xFFFFFFFF)
            ])
            fb.config.max_cycles = 2048
        elif kind == "stall":
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("stall", rng.randrange(5, 50),
                           core=rng.randrange(8), span=rng.randrange(1, 60))
            ])
        elif kind == "bank_blackout":
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("bank_blackout", rng.randrange(5, 50),
                           span=rng.randrange(1, 30), banks=(0, 3))
            ])
        elif kind == "droop":
            # correlated domain droop: half the cores stall at one cycle
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("droop", rng.randrange(5, 50),
                           cores=tuple(range(4)), span=rng.randrange(1, 60),
                           domain="dom0")
            ])
        elif kind == "scu_blackout":
            # a window where the dying tenant's SCU neither fires nor
            # grants -- armed state must not leak into the next tenant
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("scu_blackout", rng.randrange(5, 50),
                           span=rng.randrange(1, 40), domain="dom0")
            ])
        elif kind == "domain_blackout":
            # domain-wide bank blackout: every bank of one domain half
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("bank_blackout", rng.randrange(5, 50),
                           span=rng.randrange(1, 30),
                           banks=tuple(range(8)), domain="dom0")
            ])
        else:
            fb.config.cluster.faults = FaultPlan([
                FaultEvent("spurious_wake", rng.randrange(5, 50),
                           core=rng.randrange(8),
                           line=rng.choice((0, 8, 9, 10)))
            ])
            fb.config.max_cycles = 2048
        slot = fleet.admit(fb.config)
        done_first = False
        rounds = 0
        while not done_first:
            for m in fleet.advance():
                done_first = done_first or m.index == slot
                fleet.free(m.index)
            rounds += 1
            assert rounds < 10**6

        # the recycled slot must serve a clean tenant bit-exactly
        b2 = prep_barrier_bench("scu", 8, sfr=10, iters=3)
        s2 = fleet.admit(b2.config)
        assert s2 == slot or fleet.n_slots > 1
        # scrubbed fault state: no armed drops leak across tenants
        scu = b2.config.cluster.scu
        assert not scu.base.drop.any() and not scu.base._drop_armed
        assert scu.base.dropped_events == 0
        rounds = 0
        while fleet.occupied:
            for m in fleet.advance():
                if m.index == s2:
                    assert m.error is None
                    assert b2.finalize(m.cluster.stats) == ref, (
                        f"seed={seed}: recycled slot leaked fault state"
                    )
                fleet.free(m.index)
            rounds += 1
            assert rounds < 10**6


# ---------------------------------------------------------------------------
# Fault domains: health-aware routing, quarantine, reroute
# ---------------------------------------------------------------------------


def _clean_factory(attempt):
    fb = prep_barrier_bench("scu", 8, sfr=20, iters=4)
    fb.config.max_cycles = 4096
    return fb.config


def _victim_inject(victims):
    """An inject hook arming a deadlocking lost-wake plan on every config
    admitted to a victim domain -- faults tied to the *domain*, which is
    why rerouting escapes them."""
    def inject(domain, config):
        if domain in victims:
            config.cluster.faults = _lost_wake_plan()
        return config
    return inject


def test_pool_validation():
    with pytest.raises(ValueError, match="n_domains"):
        FleetService(n_domains=0, n_slots=1, slot_cores=8)
    with pytest.raises(ValueError, match="placement"):
        FleetService(n_domains=2, n_slots=1, slot_cores=8, placement="random")
    with pytest.raises(ValueError, match="queue_limit"):
        FleetService(n_domains=2, n_slots=1, slot_cores=8, queue_limit=0)
    with pytest.raises(ValueError, match="probation_after"):
        BreakerPolicy(probation_after=0)
    with pytest.raises(ValueError, match="cooldown_rounds"):
        BreakerPolicy(cooldown_rounds=0)
    with pytest.raises(ValueError, match="probe_successes"):
        BreakerPolicy(probe_successes=0)
    with pytest.raises(ValueError, match="window"):
        DomainHealth(window=0)


def test_pool_placement_is_deterministic():
    """round-robin cycles domains in index order; least-loaded picks the
    emptiest domain with ties to the lower id -- both pure functions of
    the pool state, no randomness anywhere."""
    rr = FleetService(n_domains=3, n_slots=2, slot_cores=8,
                      placement="round-robin")
    doms = [rr.submit(_clean_factory(1)).domain for _ in range(6)]
    assert doms == [0, 1, 2, 0, 1, 2]

    ll = FleetService(n_domains=3, n_slots=2, slot_cores=8,
                      placement="least-loaded")
    doms = [ll.submit(_clean_factory(1)).domain for _ in range(6)]
    assert doms == [0, 1, 2, 0, 1, 2]  # load ties break to the lower id


def test_pool_clean_stream_matches_single_fleet_service():
    """With one domain and no faults the service schedules this stream
    exactly as the single-fleet scheduler did before fault domains were
    folded into it: pinned per-job admission and finish rounds and cycles,
    total rounds and lane accounting -- the domain layer adds routing,
    not scheduling drift.  Stats stay bit-exact against sequential runs."""
    def build():
        return [
            prep_barrier_bench(p, 8, sfr=s, iters=i)
            for p, s, i in (
                ("scu", 0, 3), ("tas", 40, 3), ("fifo", 25, 4), ("sw", 10, 2),
            )
        ]

    seq = [b.run_sequential() for b in build()]
    benches = build()
    pool = FleetService(n_domains=1, n_slots=2, slot_cores=8, queue_limit=16)
    pool_jobs = [pool.submit(b.config) for b in benches]
    pool.run_until_drained()

    assert [
        (j.state, j.admitted_round, j.finished_round, j.stats.cycles)
        for j in pool_jobs
    ] == [
        ("done", 0, 12, 19),
        ("done", 0, 445, 642),
        ("done", 13, 256, 349),
        ("done", 257, 494, 372),
    ]
    for j, b, ref in zip(pool_jobs, benches, seq):
        assert b.finalize(j.stats) == ref
    assert pool.round == 495
    assert pool.idle_lane_fraction == 0.04949494949494948


def test_priority_admission_is_per_domain():
    """Under admission_order="priority" each domain admits the best job of
    its own queue: highest priority first within the domain, and a
    higher-priority job queued on another domain never takes its lane."""
    svc = FleetService(
        n_domains=2, n_slots=1, slot_cores=8, placement="round-robin",
        admission_order="priority",
    )
    prios = (0, 0, 1, 5, 9, 2)
    jobs = [svc.submit(_clean_factory(1), priority=p) for p in prios]
    assert [j.domain for j in jobs] == [0, 1, 0, 1, 0, 1]
    svc.run_until_drained(max_rounds=500_000)
    assert all(j.state == "done" for j in jobs)

    def admission_order(d):
        on_d = [j for j in jobs if j.domain == d]
        return [j.job_id for j in sorted(on_d, key=lambda j: j.admitted_round)]

    assert admission_order(0) == [4, 2, 0]
    assert admission_order(1) == [3, 5, 1]
    # each domain's best job starts in round 0, side by side
    assert jobs[4].admitted_round == jobs[3].admitted_round == 0
    assert svc.preemptions == 0


def test_pool_fifo_fairness_per_domain():
    """Jobs placed on the same domain are admitted in submission order --
    and a rerouted retry joins the *tail* of its new domain's queue, never
    jumping the fresh submissions already waiting there."""
    pool = FleetService(
        n_domains=2, n_slots=1, slot_cores=8, placement="round-robin",
        retry=RetryPolicy(max_attempts=2, backoff_rounds=0, reroute=True),
        inject=_victim_inject({0}),
    )
    # six jobs alternate 0,1,0,1,0,1; domain-0 jobs fail and reroute to 1
    jobs = [pool.submit(factory=_clean_factory) for _ in range(6)]
    pool.run_until_drained(max_rounds=500_000)
    assert all(j.state == "done" for j in jobs), \
        "every domain-0 casualty must complete after its reroute"
    assert pool.reroutes == 3
    d1_first = [j for j in jobs if j.domain == 1 and j.attempts == 1]
    rerouted = [j for j in jobs if j.attempts == 2]
    assert all(j.domain == 1 for j in rerouted)
    # FIFO per domain: among same-domain admissions, submit order holds,
    # and every fresh domain-1 job was admitted before any rerouted one
    # arrived in that queue
    for bucket in (d1_first, rerouted):
        admits = [j.admitted_round for j in bucket]
        assert admits == sorted(admits)
    assert max(j.admitted_round for j in d1_first) <= \
        min(j.admitted_round for j in rerouted)


def test_reroute_completes_jobs_inplace_retry_loses():
    """The tentpole serve claim, in miniature: a domain-pinned fault kills
    in-place retries (every attempt lands back in the blast radius) while
    reroute=True completes the same job on a healthy domain."""
    def run(reroute):
        pool = FleetService(
            n_domains=2, n_slots=1, slot_cores=8,
            retry=RetryPolicy(max_attempts=2, backoff_rounds=1,
                              reroute=reroute),
            inject=_victim_inject({0}),
        )
        job = pool.submit(factory=_clean_factory)
        assert job.domain == 0  # least-loaded tie breaks to the victim
        pool.run_until_drained(max_rounds=500_000)
        return job, pool

    lost, _ = run(reroute=False)
    assert lost.state == "failed" and lost.attempts == 2
    assert all(e["domain"] == 0 for e in lost.fault_log)

    saved, pool = run(reroute=True)
    assert saved.state == "done" and saved.attempts == 2
    assert saved.domain == 1 and pool.reroutes == 1
    assert saved.fault_log[0]["domain"] == 0  # blame names the sick domain


def test_breaker_walks_the_state_machine():
    """healthy -> probation (window failures) -> quarantined (probation
    failure) -> probation (cooldown expiry) -> healthy (probe successes),
    all round-counted and observable."""
    sick = {"on": True}

    def inject(domain, config):
        if sick["on"]:
            config.cluster.faults = _lost_wake_plan()
        return config

    breaker = BreakerPolicy(probation_after=2, cooldown_rounds=4,
                            probe_successes=2)
    pool = FleetService(
        n_domains=1, n_slots=2, slot_cores=8, breaker=breaker,
        retry=RetryPolicy(max_attempts=1), inject=inject,
    )
    # two failures in the window drop the domain to probation
    for _ in range(2):
        pool.submit(factory=_clean_factory)
    pool.run_until_drained(max_rounds=500_000)
    assert pool.states[0] == "probation"
    # a probation (probe) failure quarantines with a round-counted cooldown
    pool.submit(factory=_clean_factory)
    pool.run_until_drained(max_rounds=500_000)
    assert pool.states[0] == "quarantined"
    assert pool.quarantines == 1
    until = pool._cooldown_until[0]
    # a job queued against the quarantined domain waits out the cooldown
    sick["on"] = False
    j = pool.submit(factory=_clean_factory)
    pool.run_until_drained(max_rounds=500_000)
    assert j.state == "done"
    assert j.admitted_round >= until, "no admission before cooldown expiry"
    assert pool.states[0] == "probation"  # one success < probe_successes
    pool.submit(factory=_clean_factory)
    pool.run_until_drained(max_rounds=500_000)
    assert pool.states[0] == "healthy"  # second consecutive probe success


def test_quarantine_cuts_wasted_cycles_vs_reroute_alone():
    """With a stream arriving over rounds, reroute alone keeps feeding the
    victim domain (every placement there burns a full failed attempt);
    the breaker stops the bleeding after it trips -- strictly fewer wasted
    cycles, same 100% completion."""
    def run(breaker):
        pool = FleetService(
            n_domains=2, n_slots=1, slot_cores=8,
            retry=RetryPolicy(max_attempts=3, backoff_rounds=0, reroute=True),
            breaker=breaker, inject=_victim_inject({0}),
        )
        # initial burst: least-loaded alternates 0,1,0,1 so the victim
        # domain holds a queued job when its first admission fails -- that
        # job becomes the probation probe whose failure quarantines
        jobs = [pool.submit(factory=_clean_factory) for _ in range(4)]
        for _ in range(2):
            for _ in range(40):  # stagger the tail across rounds
                pool.step()
            jobs.append(pool.submit(factory=_clean_factory))
        pool.run_until_drained(max_rounds=500_000)
        return jobs, pool

    jobs_r, pool_r = run(None)
    jobs_q, pool_q = run(BreakerPolicy(probation_after=1, cooldown_rounds=50,
                                       probe_successes=1))
    assert all(j.state == "done" for j in jobs_r)
    assert all(j.state == "done" for j in jobs_q)
    assert pool_q.quarantines >= 1
    assert pool_q.wasted_cycles < pool_r.wasted_cycles, (
        "quarantine must stop feeding the victim domain"
    )


def test_watchdog_trip_escalates_to_domain_blame():
    """The escalation chain: a slot-level watchdog trip surfaces as the
    member's DeadlockError, lands in the job's fault_log with domain blame
    and the WaitForGraph dump, counts on the domain's health record, and
    the breaker quarantines the domain."""
    def factory(attempt):
        fb = prep_barrier_bench("scu", 8, sfr=20, iters=6)
        fb.config.cluster.faults = _lost_wake_plan()
        fb.config.cluster.scu.watchdog = Watchdog(timeout=150, mode="raise")
        return fb.config

    pool = FleetService(
        n_domains=2, n_slots=1, slot_cores=8,
        breaker=BreakerPolicy(probation_after=1, cooldown_rounds=10,
                              probe_successes=1),
        retry=RetryPolicy(max_attempts=1),
        inject=None,
    )
    j = pool.submit(factory=factory)
    d = j.domain
    pool.run_until_drained(max_rounds=500_000)
    assert j.state == "failed"
    assert "watchdog tripped" in j.error and "wait-for graph" in j.error
    entry = j.fault_log[0]
    assert entry["domain"] == d and entry["watchdog"] is True
    assert pool.health[d].watchdog_trips == 1
    assert pool.watchdog_trips == 1
    assert pool.states[d] == "probation", \
        "one window failure (probation_after=1) must demote the domain"
    report = pool.domain_report()
    assert report[d]["watchdog_trips"] == 1
    assert report[d]["state"] == "probation"


def test_pool_backpressure_and_requeue_bypass():
    """The global queue bound rejects fresh submissions (QueueFull /
    try_submit None) but a retry requeue bypasses it -- the pool-level
    twin of the FleetService satellite contract."""
    pool = FleetService(
        n_domains=2, n_slots=1, slot_cores=8, queue_limit=2,
        retry=RetryPolicy(max_attempts=2, backoff_rounds=3, reroute=True),
        inject=_victim_inject({0}),
    )
    j = pool.submit(factory=_clean_factory)
    assert j.domain == 0
    rounds = 0
    while j.state != "backoff":
        pool.step()
        rounds += 1
        assert rounds < 200_000
    fillers = [
        pool.submit(prep_barrier_bench("scu", 8, sfr=0, iters=2).config)
        for _ in range(2)
    ]
    with pytest.raises(QueueFull, match="pool queue full"):
        pool.submit(prep_barrier_bench("scu", 8, sfr=0, iters=2).config)
    assert pool.try_submit(
        prep_barrier_bench("scu", 8, sfr=0, iters=2).config
    ) is None
    pool.run_until_drained(max_rounds=500_000)
    assert j.state == "done" and j.domain == 1  # requeued + rerouted
    assert all(f.state == "done" for f in fillers)


# ---------------------------------------------------------------------------
# Arrival traces
# ---------------------------------------------------------------------------


def test_arrival_traces_deterministic_and_well_formed():
    for trace in (
        poisson_trace(0.05, 40, seed=7),
        bursty_trace(4, 10, gap_rounds=500, seed=7, jitter=20),
    ):
        assert len(trace) == 40
        assert all(isinstance(t, int) for t in trace)
        assert trace == sorted(trace), "arrivals must be non-decreasing"
        assert trace[0] >= 0
    assert poisson_trace(0.05, 40, seed=7) == poisson_trace(0.05, 40, seed=7)
    assert poisson_trace(0.05, 40, seed=8) != poisson_trace(0.05, 40, seed=7)
    assert bursty_trace(4, 10, 500, seed=7, jitter=20) == \
        bursty_trace(4, 10, 500, seed=7, jitter=20)
    # a zero-jitter burst is a same-round batch at each gap multiple
    assert bursty_trace(3, 2, 100, seed=0) == [0, 0, 100, 100, 200, 200]
    with pytest.raises(ValueError, match="rate"):
        poisson_trace(0.0, 4, seed=0)
    with pytest.raises(ValueError, match="gap_rounds"):
        bursty_trace(2, 2, -1, seed=0)


# ---------------------------------------------------------------------------
# Per-job energy split
# ---------------------------------------------------------------------------


def test_job_energy_components_sum_exactly():
    """The idle/spin/compute/static split is a regrouping of the calibrated
    model: components must sum to EnergyModel.energy_pj exactly."""
    st_ = prep_barrier_bench("tas", 8, sfr=10, iters=4).run_sequential().stats
    e = job_energy(st_)
    total = DEFAULT_ENERGY.energy_pj(Activity.from_stats(st_))
    assert e.total_pj == pytest.approx(total, abs=1e-9)
    assert e.wait_pj == pytest.approx(e.idle_pj + e.spin_pj, abs=1e-9)


def test_job_energy_separates_disciplines():
    """The whole point of the split: SCU mutex losers sleep clock-gated
    (idle energy), TAS losers hammer the TCDM (spin energy)."""
    scu_st = prep_mutex_bench(
        "scu", 8, t_crit=12, iters=8
    ).run_sequential().stats
    tas_st = prep_mutex_bench(
        "tas", 8, t_crit=12, iters=8
    ).run_sequential().stats
    e_scu = job_energy(scu_st)
    e_tas = job_energy(tas_st)
    assert e_scu.idle_pj > 0
    assert e_tas.spin_pj > e_scu.spin_pj
    assert e_scu.idle_pj > e_tas.idle_pj
