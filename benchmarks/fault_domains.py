"""Fault-domain chaos sweep: domain fault rate x routing-policy matrix.

A fixed, deterministic stream of 8-core SCU barrier jobs is served by a
:class:`repro.serve.fleet_service.FleetService` of three single-slot
fleets -- three *fault domains*.  Domain 0 is sick: a seeded inject hook
arms a lost-barrier-wake :class:`repro.core.scu.faults.FaultPlan` on a
fraction of the configs admitted there (the *domain fault rate*), so any
attempt that lands in the blast radius deadlocks and burns its whole cycle
budget.  The other domains stay clean.  Three routing policies run the
identical arrival schedule:

* ``inplace``    -- ``RetryPolicy(reroute=False)``: a failed attempt
  retries on the *same* domain.  The fault is pinned to the domain, so
  every retry lands back in the blast radius and the job is lost;
* ``reroute``    -- ``reroute=True``: the retry is resubmitted to a
  different healthy domain first, escaping the fault.  Every job
  completes, but the victim domain keeps receiving *fresh* placements
  (it looks least loaded precisely because its jobs keep failing), each
  one a full wasted attempt;
* ``quarantine`` -- ``reroute=True`` plus a :class:`BreakerPolicy`:
  after the health window trips, the domain is demoted
  (healthy -> probation -> quarantined) and the router stops feeding it,
  cutting wasted cycles while still completing 100% of the stream.

Reported per (rate, policy) cell: failure rate, total attempts, wasted
cycles, reroutes, quarantines, scheduler rounds and recovery latency.
Everything is counted in cycles or rounds of a seeded deterministic
simulation, so the numbers are bit-exact across machines and hard-gated
by ``scripts/bench_compare.py``; the artifact is identical under
``--fast`` and full runs.

    PYTHONPATH=src python -m benchmarks.fault_domains [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import random
from typing import Dict

from repro.core.scu.faults import FaultEvent, FaultPlan
from repro.core.scu.programs import prep_barrier_bench
from repro.serve.fleet_service import BreakerPolicy, FleetService, RetryPolicy

# pool geometry: three single-slot fault domains, so placement decisions
# are legible and every domain-0 admission is a countable wasted attempt
N_DOMAINS = 3
N_SLOTS = 1
SLOT_CORES = 8
ITERS = 4
SFR = 20
# cycle budget per attempt: a deadlocked attempt burns exactly this much
MAX_CYCLES = 4000

# arrival schedule: an initial burst (so the sick domain holds a queued
# job that becomes its probation probe), then a staggered tail (so the
# breaker's routing decisions have fresh arrivals to protect)
BURST_JOBS = 6
TAIL_JOBS = 3
TAIL_GAP_ROUNDS = 40
N_JOBS = BURST_JOBS + TAIL_JOBS

VICTIM_DOMAIN = 0
# the barrier event line (EV.BARRIER); losing it on one core deadlocks
# the whole barrier
_BARRIER_LINE_MASK = 1 << 8

FAULT_RATES = (0.0, 1.0)
POLICIES = ("inplace", "reroute", "quarantine")

_SEED = 0xD0A1A


def _fault_plan(victim_core: int) -> FaultPlan:
    """Lose the barrier wake on one core early in the attempt (plans are
    single-use, so build a fresh one per admission)."""
    return FaultPlan([
        FaultEvent("lost_wake", cycle=10, core=victim_core,
                   lines=_BARRIER_LINE_MASK)
    ])


def _inject(rate: float):
    """Domain-scoped chaos: admissions to the victim domain are armed
    with a deadlocking plan at ``rate``.  The rng is seeded and drawn in
    admission order (which is deterministic), so the sweep is bit-exact."""
    rng = random.Random(_SEED)

    def inject(domain: int, config):
        if domain == VICTIM_DOMAIN and rng.random() < rate:
            config.cluster.faults = _fault_plan(rng.randrange(SLOT_CORES))
        return config
    return inject


def _factory(attempt: int):
    fb = prep_barrier_bench("scu", SLOT_CORES, sfr=SFR, iters=ITERS)
    fb.config.max_cycles = MAX_CYCLES
    return fb.config


def _run_cell(rate: float, policy: str) -> Dict:
    retry = RetryPolicy(max_attempts=2, backoff_rounds=0,
                        reroute=(policy != "inplace"))
    breaker = None
    if policy == "quarantine":
        breaker = BreakerPolicy(probation_after=1, cooldown_rounds=200,
                                probe_successes=1)

    pool = FleetService(
        n_domains=N_DOMAINS, n_slots=N_SLOTS, slot_cores=SLOT_CORES,
        queue_limit=N_JOBS, retry=retry, breaker=breaker,
        inject=_inject(rate),
    )

    jobs = [pool.submit(factory=_factory) for _ in range(BURST_JOBS)]
    for _ in range(TAIL_JOBS):
        for _ in range(TAIL_GAP_ROUNDS):
            pool.step()
        jobs.append(pool.submit(factory=_factory))
    pool.run_until_drained(max_rounds=500_000)

    failed = [j for j in jobs if j.state == "failed"]
    done = [j for j in jobs if j.state == "done"]
    assert len(failed) + len(done) == N_JOBS
    lat = [j.latency_rounds for j in jobs]
    return {
        "failure_rate": len(failed) / N_JOBS,
        "failed_jobs": len(failed),
        "completed_jobs": len(done),
        "total_attempts": sum(j.attempts for j in jobs),
        "reroutes": pool.reroutes,
        "quarantines": pool.quarantines,
        "wasted_cycles": pool.wasted_cycles,
        "rounds": pool.round,
        "mean_latency_rounds": sum(lat) / N_JOBS,
        "watchdog_trips": pool.watchdog_trips,
    }


def run(verbose: bool = True) -> Dict:
    cells: Dict[str, Dict[str, Dict]] = {}
    for rate in FAULT_RATES:
        key = f"rate{rate:g}"
        cells[key] = {policy: _run_cell(rate, policy) for policy in POLICIES}

    # the headline claims, asserted (not just reported): at a domain
    # fault rate where in-place retry loses jobs, rerouting completes
    # 100% of the stream, and quarantine does so with strictly fewer
    # wasted cycles than reroute alone
    faulty = cells[f"rate{FAULT_RATES[-1]:g}"]
    assert faulty["inplace"]["failed_jobs"] > 0, (
        "domain fault rate too low to matter"
    )
    for policy in ("reroute", "quarantine"):
        assert faulty[policy]["failure_rate"] == 0.0, (
            f"{policy} lost jobs: {faulty[policy]}"
        )
    assert faulty["quarantine"]["quarantines"] >= 1
    assert (faulty["quarantine"]["wasted_cycles"]
            < faulty["reroute"]["wasted_cycles"]), (
        "quarantine must stop feeding the victim domain"
    )
    # and clean traffic is untouched by the routing machinery
    clean = cells[f"rate{FAULT_RATES[0]:g}"]
    for c in clean.values():
        assert c["failure_rate"] == 0.0
        assert c["reroutes"] == 0 and c["quarantines"] == 0
        assert c["total_attempts"] == N_JOBS

    result = {
        "pool": {"n_domains": N_DOMAINS, "n_slots": N_SLOTS,
                 "slot_cores": SLOT_CORES, "victim_domain": VICTIM_DOMAIN},
        "n_jobs": N_JOBS,
        "max_cycles": MAX_CYCLES,
        "fault_rates": list(FAULT_RATES),
        "cells": cells,
    }

    if verbose:
        print(f"\n== Fault-domain chaos sweep ({N_JOBS} jobs, "
              f"{N_DOMAINS} domains x {N_SLOTS}x{SLOT_CORES} lanes, "
              f"domain {VICTIM_DOMAIN} sick) ==")
        print(f"{'rate':>5s} {'policy':10s} {'fail%':>6s} {'attempts':>8s} "
              f"{'wasted cyc':>10s} {'reroute':>7s} {'quar':>4s} "
              f"{'rounds':>7s} {'mean lat':>8s}")
        for rate in FAULT_RATES:
            for policy in POLICIES:
                c = cells[f"rate{rate:g}"][policy]
                print(
                    f"{rate:5.2f} {policy:10s} {c['failure_rate']:6.0%} "
                    f"{c['total_attempts']:8d} {c['wasted_cycles']:10d} "
                    f"{c['reroutes']:7d} {c['quarantines']:4d} "
                    f"{c['rounds']:7d} {c['mean_latency_rounds']:8.1f}"
                )
        f = faulty
        print(
            f"\nat a fully sick domain: in-place retry loses "
            f"{f['inplace']['failed_jobs']}/{N_JOBS} jobs; reroute and "
            f"reroute+quarantine complete {N_JOBS}/{N_JOBS} "
            f"(wasted cycles {f['inplace']['wasted_cycles']} -> "
            f"{f['reroute']['wasted_cycles']} -> "
            f"{f['quarantine']['wasted_cycles']})"
        )
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
