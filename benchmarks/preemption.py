"""Checkpoint/restore benchmark: live migration and preemptive priority.

Two deterministic experiments over the checkpoint/restore machinery
(:mod:`repro.core.scu.checkpoint`), both counted in cycles and scheduler
rounds of seeded runs, so every number is bit-exact across machines and
hard-gated by ``scripts/bench_compare.py``.

**Migration** -- a :class:`repro.serve.fleet_service.FleetService` of
two single-slot fault domains serves compiled 8-core SCU barrier jobs.
Domain 0 is sick: every admission there is armed with a voltage droop
that freezes all eight cores mid-run, so the attempt burns to its
``max_cycles`` cap and times out.  The identical schedule runs twice:

* ``restart`` -- plain reroute: the retry is rebuilt from scratch on the
  healthy domain, so the whole failed attempt (``max_cycles`` cycles) is
  wasted;
* ``migrate`` -- a :class:`repro.serve.fleet_service.CheckpointPolicy`
  checkpoints in-flight members every few rounds; the retry *resumes* from
  the last pre-fault checkpoint on the healthy domain (the plan is
  stripped -- the fault was the domain's, not the job's), so only the
  cycles since that checkpoint are lost.

**Preemptive scheduling** -- a single-lane
:class:`repro.serve.fleet_service.FleetService` runs long low-priority
jobs; a short high-priority job arrives while the lane is busy and the
queue is deep.  Three admission disciplines run the identical stream:

* ``fifo``     -- arrival order: the high-priority job drains last;
* ``priority`` -- the queue is priority-ordered, but the running job
  holds the lane until it finishes;
* ``preempt``  -- the running job is checkpointed and evicted, the
  high-priority job takes its lane the round it arrives, and the victim
  resumes from its checkpoint later -- losing zero cycles.

The headline claims are asserted in-run, not just reported: migration
wastes strictly fewer cycles than restart-reroute on the same fault
script, and the preempting service admits the high-priority job before
any queued low-priority job while wasting no cycles on the victim.

    PYTHONPATH=src python -m benchmarks.preemption [--json PATH]
"""

from __future__ import annotations

import argparse
import json
from typing import Dict

from repro.core.scu.faults import FaultEvent, FaultPlan
from repro.core.scu.programs import prep_barrier_bench
from repro.serve.fleet_service import (
    CheckpointPolicy,
    FleetService,
    RetryPolicy,
)

SLOT_CORES = 8
SFR = 20

# migration experiment: two single-slot domains, domain 0 sick
N_DOMAINS = 2
MIG_JOBS = 2
MIG_ITERS = 128  # ~3.3k cycles clean, so the droop below lands mid-run
MIG_MAX_CYCLES = 4000  # a frozen attempt burns exactly this much
DROOP_CYCLE = 2000  # fault fires past several checkpoint boundaries
CKPT_INTERVAL = 4  # rounds between in-flight checkpoints
VICTIM_DOMAIN = 0

# scheduling experiment: one lane, deep queue of long jobs
LOW_JOBS = 3
LOW_ITERS = 128
HI_ITERS = 8
HI_PRIORITY = 5
HI_ARRIVAL_ROUND = 6  # service rounds before the high-priority job lands

SCHED_MODES = ("fifo", "priority", "preempt")
MIG_MODES = ("restart", "migrate")


def _job_config(iters: int, max_cycles: int = 10_000_000):
    """A compiled (trace-lowered, hence checkpointable) SCU barrier job."""
    fb = prep_barrier_bench("scu", SLOT_CORES, sfr=SFR, iters=iters,
                            compiled=True)
    fb.config.max_cycles = max_cycles
    return fb.config


def _droop_plan() -> FaultPlan:
    """Freeze every core long past the cycle budget: the attempt times
    out at ``max_cycles`` with its (uncorrupted) state stuck mid-run."""
    return FaultPlan([
        FaultEvent("droop", cycle=DROOP_CYCLE, cores=tuple(range(SLOT_CORES)),
                   span=1_000_000, domain="sick")
    ])


def _inject(domain: int, config):
    """Domain-scoped chaos: every fresh admission to the victim domain is
    droop-armed (checkpoint-resumed admissions skip this hook -- the
    fault belongs to the domain, not the resumed job)."""
    if domain == VICTIM_DOMAIN:
        config.cluster.faults = _droop_plan()
    return config


def _factory(attempt: int):
    return _job_config(MIG_ITERS, MIG_MAX_CYCLES)


def _run_migration_cell(mode: str) -> Dict:
    pool = FleetService(
        n_domains=N_DOMAINS, n_slots=1, slot_cores=SLOT_CORES,
        retry=RetryPolicy(max_attempts=3, backoff_rounds=0, reroute=True),
        inject=_inject,
        checkpoint=CheckpointPolicy(CKPT_INTERVAL) if mode == "migrate"
        else None,
    )
    jobs = [pool.submit(factory=_factory) for _ in range(MIG_JOBS)]
    pool.run_until_drained(max_rounds=200_000)

    failed = [j for j in jobs if j.state == "failed"]
    lat = [j.latency_rounds for j in jobs]
    return {
        "failure_rate": len(failed) / MIG_JOBS,
        "failed_jobs": len(failed),
        "completed_jobs": MIG_JOBS - len(failed),
        "total_attempts": sum(j.attempts for j in jobs),
        "wasted_cycles": pool.wasted_cycles,
        "reroutes": pool.reroutes,
        "migrations": pool.migrations,
        "rounds": pool.round,
        "mean_latency_rounds": sum(lat) / MIG_JOBS,
    }


def _run_schedule_cell(mode: str) -> Dict:
    svc = FleetService(
        1, SLOT_CORES,
        admission_order="fifo" if mode == "fifo" else "priority",
        preempt=(mode == "preempt"),
    )
    lows = [svc.submit(_job_config(LOW_ITERS)) for _ in range(LOW_JOBS)]
    for _ in range(HI_ARRIVAL_ROUND):
        svc.step()
    hi = svc.submit(_job_config(HI_ITERS), priority=HI_PRIORITY)
    svc.run_until_drained()

    jobs = lows + [hi]
    assert all(j.state == "done" for j in jobs), [j.state for j in jobs]
    if mode == "preempt":
        # the headline: the high-priority job took a busy lane the round
        # it arrived, ahead of every queued low-priority job, and the
        # suspended victim lost zero cycles
        assert svc.preemptions >= 1, "preempt cell never preempted"
        assert hi.admitted_round == hi.submitted_round
        queued_lows = [j for j in lows if j.admitted_round > hi.submitted_round]
        assert all(hi.admitted_round < j.admitted_round for j in queued_lows)
        assert sum(j.wasted_cycles for j in jobs) == 0, (
            "preemption must not waste victim cycles"
        )
    lat = [j.latency_rounds for j in jobs]
    return {
        "failure_rate": 0.0,
        "completed_jobs": len(jobs),
        "preemptions": svc.preemptions,
        "wasted_cycles": sum(j.wasted_cycles for j in jobs),
        "rounds": svc.round,
        "mean_latency_rounds": sum(lat) / len(jobs),
        "hi_latency_rounds": hi.latency_rounds,
        "hi_queue_rounds": hi.queue_rounds,
    }


def run(verbose: bool = True) -> Dict:
    migration = {mode: _run_migration_cell(mode) for mode in MIG_MODES}
    schedule = {mode: _run_schedule_cell(mode) for mode in SCHED_MODES}

    # headline claims, asserted (not just reported)
    mig, res = migration["migrate"], migration["restart"]
    assert res["failure_rate"] == 0.0 and mig["failure_rate"] == 0.0, (
        "both recovery modes must complete the stream"
    )
    assert mig["migrations"] >= 1, "migrate cell never migrated"
    assert mig["wasted_cycles"] < res["wasted_cycles"], (
        "resuming from a checkpoint must waste strictly fewer cycles "
        f"than restarting: {mig['wasted_cycles']} vs {res['wasted_cycles']}"
    )
    hi_lat = {m: schedule[m]["hi_latency_rounds"] for m in SCHED_MODES}
    assert hi_lat["preempt"] < hi_lat["priority"] <= hi_lat["fifo"], (
        f"priority/preemption must cut high-priority latency: {hi_lat}"
    )

    result = {
        "geometry": {"slot_cores": SLOT_CORES, "n_domains": N_DOMAINS,
                     "victim_domain": VICTIM_DOMAIN,
                     "checkpoint_interval_rounds": CKPT_INTERVAL},
        "migration": migration,
        "schedule": schedule,
    }

    if verbose:
        print(f"\n== Live migration ({MIG_JOBS} jobs, {N_DOMAINS} domains "
              f"x 1x{SLOT_CORES} lanes, domain {VICTIM_DOMAIN} droops at "
              f"cycle {DROOP_CYCLE}, budget {MIG_MAX_CYCLES}) ==")
        print(f"{'mode':8s} {'wasted cyc':>10s} {'attempts':>8s} "
              f"{'reroutes':>8s} {'migrations':>10s} {'rounds':>7s}")
        for mode in MIG_MODES:
            c = migration[mode]
            print(f"{mode:8s} {c['wasted_cycles']:10d} "
                  f"{c['total_attempts']:8d} {c['reroutes']:8d} "
                  f"{c['migrations']:10d} {c['rounds']:7d}")
        print(f"-> migration saves {res['wasted_cycles'] - mig['wasted_cycles']}"
              f" of {res['wasted_cycles']} wasted cycles on the same fault")

        print(f"\n== Preemptive priority ({LOW_JOBS} long low-priority jobs, "
              f"one priority-{HI_PRIORITY} arrival at round "
              f"{HI_ARRIVAL_ROUND}, single lane) ==")
        print(f"{'mode':9s} {'hi latency':>10s} {'hi queued':>9s} "
              f"{'mean lat':>8s} {'preempt':>7s} {'wasted':>6s}")
        for mode in SCHED_MODES:
            c = schedule[mode]
            print(f"{mode:9s} {c['hi_latency_rounds']:10d} "
                  f"{c['hi_queue_rounds']:9d} {c['mean_latency_rounds']:8.1f} "
                  f"{c['preemptions']:7d} {c['wasted_cycles']:6d}")
        print(f"-> preemption admits the high-priority job in its arrival "
              f"round (latency {hi_lat['fifo']} -> {hi_lat['priority']} -> "
              f"{hi_lat['preempt']} rounds) at zero wasted victim cycles")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH", help="write results as JSON")
    args = ap.parse_args()
    result = run()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
