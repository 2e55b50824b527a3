"""Checkpoint/restore parity suite.

The crown invariant: a run suspended at a round boundary and restored --
into the same slot, a different slot, a different fleet, or a standalone
cluster in either engine mode -- produces **bit-identical**
``ClusterStats`` to the uninterrupted run, including under active
``FaultPlan``s whose cursor straddles the checkpoint.  Plus the serve
layer built on top: priority admission with aging, preemption,
checkpoint-resume retries, live migration and whole-service suspend/resume.
"""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.scu import (
    Hierarchy,
    NotCheckpointable,
    capture_cluster,
    restore_cluster,
)
from repro.core.scu.engine import SlotFleet
from repro.core.scu.faults import FaultEvent, FaultPlan, Watchdog
from repro.core.scu.programs import prep_barrier_bench
from repro.serve.fleet_service import (
    CheckpointPolicy,
    FleetService,
    RetryPolicy,
)

POLICIES = ("scu", "tas", "sw", "tree", "tree4", "tree_ew", "fifo")
CORES = (8, 16, 64)

_BARRIER_LINE = 1 << 8


def _bench(policy, n, iters=6, sfr=10, max_cycles=100_000):
    fb = prep_barrier_bench(policy, n, sfr=sfr, iters=iters, compiled=True)
    fb.config.max_cycles = max_cycles
    return fb.config


def _run_fleet(fleet):
    fin = []
    while not fin:
        fin = fleet.advance()
    m = fin[0]
    assert m.error is None, m.error
    return m.cluster.stats


def _reference(policy, n, faults=None, **kw):
    cfg = _bench(policy, n, **kw)
    if faults is not None:
        cfg.cluster.faults = faults
    fl = SlotFleet(2, n)
    fl.admit(cfg)
    return _run_fleet(fl)


def _suspend_at(policy, n, k, faults=None, **kw):
    """Admit, run ``k`` rounds, suspend.  Returns (fleet, ckpt) or
    (fleet, None) when the member finished before round ``k``."""
    cfg = _bench(policy, n, **kw)
    if faults is not None:
        cfg.cluster.faults = faults
    fl = SlotFleet(2, n)
    slot = fl.admit(cfg)
    for _ in range(k):
        if fl.advance():
            return fl, None
    return fl, fl.suspend(slot)


def _mid_plan(n):
    """Non-deadlocking plan whose events straddle any early checkpoint."""
    return FaultPlan([
        FaultEvent("spurious_wake", cycle=9, core=1, line=2),
        FaultEvent("stall", cycle=25, core=0, span=7),
        FaultEvent("bank_blackout", cycle=45, banks=(1,), span=9),
        FaultEvent("droop", cycle=70, cores=(2, 3), span=11, domain="d0"),
        FaultEvent("spurious_wake", cycle=120, core=n - 1, line=5),
    ])


@pytest.mark.parametrize("n", CORES)
@pytest.mark.parametrize("policy", POLICIES)
def test_roundtrip_bit_exact_all_paths(policy, n):
    """Suspend at round k, restore five ways; every path reproduces the
    uninterrupted ClusterStats exactly."""
    iters = 4 if n == 64 else 6
    ref = _reference(policy, n, iters=iters)
    fl, ckpt = _suspend_at(policy, n, k=5, iters=iters)
    assert ckpt is not None, "job finished before the suspension round"
    assert ckpt.cycle > 0

    # same fleet, same (lowest-free) slot
    fl.restore(ckpt, slot=0)
    assert _run_fleet(fl) == ref
    # same fleet, the other slot
    fl.restore(ckpt, slot=1)
    assert _run_fleet(fl) == ref
    # a different fleet entirely
    other = SlotFleet(3, n)
    other.restore(ckpt)
    assert _run_fleet(other) == ref
    # standalone clusters, both engine tiers
    for mode in ("fastforward", "lockstep"):
        cl = restore_cluster(ckpt, mode=mode)
        assert cl.run(ckpt.max_cycles) == ref


@pytest.mark.parametrize("n", (8, 16))
@pytest.mark.parametrize("policy", ("scu", "tas", "tree_ew", "fifo"))
def test_roundtrip_with_fault_cursor_mid_plan(policy, n):
    """The FaultPlan cursor resumes mid-plan: events before the checkpoint
    stay applied, events after it land exactly once."""
    ref = _reference(policy, n, faults=_mid_plan(n))
    for k in (2, 6, 14):
        fl, ckpt = _suspend_at(policy, n, k=k, faults=_mid_plan(n))
        if ckpt is None:
            continue
        assert ckpt.faults is not None
        fl.restore(ckpt)
        assert _run_fleet(fl) == ref
        cl = restore_cluster(ckpt, mode="lockstep")
        assert cl.run(ckpt.max_cycles) == ref


def test_restored_plan_does_not_replay_applied_events():
    """An event already applied before the checkpoint must not re-fire."""
    plan = FaultPlan([FaultEvent("stall", cycle=5, core=0, span=50)])
    fl, ckpt = _suspend_at("scu", 8, k=12, faults=plan)
    assert ckpt is not None
    events, cursor, applied = ckpt.faults
    if ckpt.cycle > 5:
        assert cursor == 1 and len(applied) == 1
    fl.restore(ckpt)
    assert _run_fleet(fl) == _reference("scu", 8, faults=FaultPlan(
        [FaultEvent("stall", cycle=5, core=0, span=50)]))


def test_watchdog_state_carries_across_restore():
    """A release-mode watchdog's progress clock and release budget resume;
    the restored run still recovers from the lost wake exactly."""
    def cfg():
        c = _bench("scu", 8, iters=6)
        c.cluster.faults = FaultPlan([
            FaultEvent("lost_wake", cycle=10, core=2, lines=_BARRIER_LINE)])
        c.cluster.scu.watchdog = Watchdog(200, mode="release")
        return c

    fl = SlotFleet(1, 8)
    fl.admit(cfg())
    ref = _run_fleet(fl)

    fl2 = SlotFleet(1, 8)
    slot = fl2.admit(cfg())
    for _ in range(8):
        assert not fl2.advance()
    ckpt = fl2.suspend(slot)
    assert ckpt.scu.watchdog is not None
    fl2.restore(ckpt)
    assert _run_fleet(fl2) == ref


def test_generator_programs_are_not_checkpointable():
    cfg = prep_barrier_bench("scu", 8, sfr=10, iters=6).config  # not compiled
    fl = SlotFleet(1, 8)
    slot = fl.admit(cfg)
    fl.advance()
    with pytest.raises(NotCheckpointable):
        fl.snapshot(slot)
    # suspend must not evict on failure: the member keeps running
    with pytest.raises(NotCheckpointable):
        fl.suspend(slot)
    assert fl.members[slot] is not None and not fl.members[slot].done
    _run_fleet(fl)  # still completes


def test_hierarchical_clusters_are_not_checkpointable():
    """A hierarchical cluster runs in lockstep only, and its cores' response
    countdowns are not captured: a snapshot refuses it rather than restore
    it flat."""
    h = Hierarchy(cores_per_tile=4, tiles_per_group=1, groups=2, latency=(1, 3, 5))
    fb = prep_barrier_bench("tree", 8, sfr=0, iters=2, compiled=True, mode="lockstep",
                            interconnect={"banking_factor": 2, "hierarchy": h})
    cl = fb.config.cluster
    cl.load(fb.config.programs)
    for _ in range(5):
        cl.step()
    with pytest.raises(NotCheckpointable, match="hierarchical"):
        capture_cluster(cl)


def test_snapshot_restore_slot_errors():
    fl = SlotFleet(2, 8)
    with pytest.raises(ValueError):
        fl.snapshot(0)  # free slot
    slot = fl.admit(_bench("scu", 8))
    for _ in range(3):
        fl.advance()
    ckpt = fl.snapshot(slot)
    with pytest.raises(RuntimeError):
        fl.restore(ckpt, slot=slot)  # occupied slot is not free
    fl.restore(ckpt, slot=1)
    with pytest.raises(RuntimeError):
        fl.restore(ckpt)  # no slot free at all


def test_capture_finished_cluster_rejected():
    fl = SlotFleet(1, 8)
    slot = fl.admit(_bench("scu", 8))
    for _ in range(3):
        fl.advance()
    ckpt = fl.snapshot(slot)
    cl = restore_cluster(ckpt, mode="fastforward")
    cl.run(ckpt.max_cycles)
    with pytest.raises(NotCheckpointable):
        capture_cluster(cl)


def test_checkpoint_is_reusable_and_nondestructive():
    """snapshot() leaves the member running; one checkpoint backs many
    restores, each bit-exact."""
    ref = _reference("tree", 8)
    cfg = _bench("tree", 8)
    fl = SlotFleet(1, 8)
    slot = fl.admit(cfg)
    for _ in range(4):
        assert not fl.advance()
    ckpt = fl.snapshot(slot)
    assert _run_fleet(fl) == ref  # original keeps going after snapshot
    for _ in range(3):  # one checkpoint, many restores
        other = SlotFleet(1, 8)
        other.restore(ckpt)
        assert _run_fleet(other) == ref


@settings(max_examples=6, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    policy=st.sampled_from(POLICIES),
    k=st.integers(min_value=1, max_value=20),
)
def test_recycled_slot_residue_free(seed, policy, k):
    """Property: restoring into a slot previously occupied by an arbitrary
    (even timed-out) tenant is residue-free -- stats match the clean run."""
    import random

    rng = random.Random(seed)
    n = rng.choice((8, 16))
    ref = _reference(policy, n)

    fl = SlotFleet(1, n)
    # dirty the slot: the previous tenant burns to a tight max_cycles cap,
    # leaving lanes mid-SLEEP/STALL with latched events and pending ops.
    # The cap lies below the tenant's own uncapped length, so it is cut.
    prev_policy = rng.choice(POLICIES)
    full = _reference(prev_policy, n, iters=8).cycles
    prev = _bench(prev_policy, n, iters=8,
                  max_cycles=rng.randrange(min(60, full // 2), min(400, full)))
    slot = fl.admit(prev)
    while True:
        fin = fl.advance()
        if fin:
            assert fin[0].error is not None
            break
    fl.free(slot)

    fl2, ckpt = _suspend_at(policy, n, k=k)
    if ckpt is None:
        return
    fl.restore(ckpt)
    assert _run_fleet(fl) == ref


# --------------------------------------------------------------------------
# serve layer: priority admission, preemption, resume, migration, restart
# --------------------------------------------------------------------------


def _factory(policy="scu", iters=64, n=8, max_cycles=100_000):
    def make(attempt):
        return _bench(policy, n, iters=iters, max_cycles=max_cycles)
    return make


def test_priority_admission_order_and_tiebreak():
    """Higher priority admits first; ties resolve by earlier submission
    then lower job id -- deterministically."""
    svc = FleetService(1, 8, admission_order="priority")
    a = svc.submit(factory=_factory(iters=4), priority=0)
    b = svc.submit(factory=_factory(iters=4), priority=5)
    c = svc.submit(factory=_factory(iters=4), priority=5)
    svc.run_until_drained()
    assert b.admitted_round < c.admitted_round < a.admitted_round


def test_priority_aging_prevents_starvation():
    """With aging, a low-priority job eventually outranks a stream of
    fresh high-priority arrivals; without it, it drains last."""
    def run(aging):
        svc = FleetService(1, 8, admission_order="priority",
                           aging_rounds=aging, queue_limit=256)
        low = svc.submit(factory=_factory(iters=4), priority=0)
        hi_jobs = []
        for i in range(6):
            hi_jobs.append(svc.submit(factory=_factory(iters=4), priority=3))
            for _ in range(4):
                svc.step()
        svc.run_until_drained()
        return low, hi_jobs

    low, hi_jobs = run(aging=None)
    assert all(h.admitted_round < low.admitted_round for h in hi_jobs)
    low, hi_jobs = run(aging=2)
    assert any(h.admitted_round > low.admitted_round for h in hi_jobs)


def test_preemption_high_priority_takes_lane_and_victim_is_bit_exact():
    ref = _reference("scu", 8, iters=64)
    svc = FleetService(1, 8, admission_order="priority", preempt=True)
    low = svc.submit(factory=_factory(iters=64), priority=0)
    for _ in range(6):
        svc.step()
    hi = svc.submit(factory=_factory(iters=8), priority=5)
    svc.run_until_drained()
    assert svc.preemptions == 1 and low.preemptions == 1
    # the high-priority job took the lane the round it arrived
    assert hi.admitted_round == hi.submitted_round
    assert hi.finished_round < low.finished_round
    # the preempted job resumed and its stats are bit-exact
    assert low.state == "done" and low.stats == ref
    assert low.wasted_cycles == 0  # preemption loses zero cycles


def test_preemption_requires_priority_mode():
    with pytest.raises(ValueError):
        FleetService(1, 8, preempt=True)
    with pytest.raises(ValueError):
        FleetService(1, 8, admission_order="sjf")
    with pytest.raises(ValueError):
        CheckpointPolicy(0)


def test_service_checkpoint_resume_saves_cycles():
    """A failed attempt resumes from its last checkpoint: wasted cycles
    stay below one full attempt, and the final stats are bit-exact."""
    ref = _reference("scu", 8, iters=128)

    def factory(attempt):
        cfg = _bench("scu", 8, iters=128, max_cycles=4000)
        if attempt == 1:  # only the first attempt is stalled into timeout
            cfg.cluster.faults = FaultPlan([
                FaultEvent("droop", cycle=2000, cores=tuple(range(8)),
                           span=1_000_000, domain="d0")])
        return cfg

    svc = FleetService(
        1, 8, retry=RetryPolicy(max_attempts=2, backoff_rounds=0),
        checkpoint=CheckpointPolicy(interval_rounds=4),
    )
    job = svc.submit(factory=factory)
    svc.run_until_drained()
    assert job.state == "done"
    assert job.stats == ref
    assert 0 < job.wasted_cycles < 4000  # resume redid only the tail


def test_pool_live_migration_beats_restart_reroute():
    def inject(domain, config):
        if domain == 0:
            config.cluster.faults = FaultPlan([
                FaultEvent("droop", cycle=2000, cores=tuple(range(8)),
                           span=1_000_000, domain="sick")])
        return config

    def run_pool(ckpt):
        pool = FleetService(
            n_domains=2, n_slots=1, slot_cores=8,
            retry=RetryPolicy(max_attempts=3, backoff_rounds=0, reroute=True),
            inject=inject, checkpoint=ckpt,
        )
        jobs = [pool.submit(factory=_factory(iters=128, max_cycles=4000))
                for _ in range(2)]
        pool.run_until_drained(max_rounds=200_000)
        return pool, jobs

    migrate, jobs_m = run_pool(CheckpointPolicy(4))
    restart, jobs_r = run_pool(None)
    assert all(j.state == "done" for j in jobs_m + jobs_r)
    assert migrate.migrations >= 1
    assert migrate.wasted_cycles < restart.wasted_cycles
    ref = _reference("scu", 8, iters=128)
    for j in jobs_m:
        assert j.stats == ref


def test_service_suspend_all_resumes_bit_exact():
    """Whole-service restart: suspend every member mid-flight, keep
    stepping, and every job's stats match the uninterrupted service."""
    def run(suspend_at):
        svc = FleetService(2, 8, checkpoint=CheckpointPolicy(4))
        jobs = [svc.submit(factory=_factory(iters=64)) for _ in range(3)]
        for _ in range(suspend_at):
            svc.step()
        if suspend_at:
            suspended = svc.suspend_all()
            assert svc.fleets[0].occupied == 0
            assert all(j.checkpoint is not None for j in suspended)
        svc.run_until_drained()
        return [j.stats for j in jobs]

    assert run(suspend_at=6) == run(suspend_at=0)


def test_pool_suspend_all_resumes_bit_exact():
    def run(suspend_at):
        pool = FleetService(n_domains=2, n_slots=1, slot_cores=8,
                            checkpoint=CheckpointPolicy(4))
        jobs = [pool.submit(factory=_factory(iters=64)) for _ in range(3)]
        for _ in range(suspend_at):
            pool.step()
        if suspend_at:
            suspended = pool.suspend_all()
            assert all(f.occupied == 0 for f in pool.fleets)
            assert suspended
        pool.run_until_drained()
        return [j.stats for j in jobs]

    assert run(suspend_at=6) == run(suspend_at=0)
