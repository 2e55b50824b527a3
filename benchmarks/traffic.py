"""Sweep-service traffic benchmark: continuous batching vs drain baseline.

A fixed, deterministic stream of heterogeneous sweep jobs (every registered
policy x barrier/mutex/chain/work-queue shapes x 8/16 cores) is served by
the slot-recycling fleet (``repro.serve.fleet_service``) under two arrival
processes -- bursty and Poisson -- and two admission modes on the *same*
engine:

* ``continuous`` -- finished jobs free lanes mid-flight, queued jobs take
  them at the next scheduling round;
* ``drain`` -- the submit-in-fixed-batches baseline: admissions wait until
  the whole fleet has drained, the utilization loss continuous batching
  removes.

Reported per scenario and mode: completion rounds, p50/p99 job latency and
the idle-lane fraction -- all counted in **scheduler rounds**, so they are
bit-deterministic and hard-gated by ``scripts/bench_compare.py`` like every
cycle metric.  Wall-clock enters only as the same-run ``speedup`` ratio
(drain wall / continuous wall), soft-gated like the engine_perf ratios.
The per-job energy split (``repro.serve.energy``) adds tail energy per
discipline: p99 spin vs idle energy across each policy's jobs.

    PYTHONPATH=src python -m benchmarks.traffic [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core.scu.programs import (
    FleetBench,
    prep_barrier_bench,
    prep_chain_bench,
    prep_mutex_bench,
    prep_work_queue_bench,
)
from repro.serve.arrivals import bursty_trace, poisson_trace
from repro.serve.energy import job_energy
from repro.serve.fleet_service import FleetService
from repro.sync import available_policies

# fixed service geometry: 6 slots x 16 lanes; 8-core jobs occupy half a
# slot (the wasted tail lanes are charged to the idle fraction honestly)
N_SLOTS = 6
SLOT_CORES = 16

ADMISSION_MODES = ("continuous", "drain")


def _job_mix() -> List[Tuple[str, FleetBench]]:
    """The deterministic job stream: 6 shapes per registered policy.

    Service times spread over two orders of magnitude (short hardware
    barriers to long software mutex herds), which is what makes fixed
    batches straggle.  Fresh benches every call -- generators are
    single-use."""
    jobs: List[Tuple[str, FleetBench]] = []
    for p in available_policies():
        jobs += [
            (p, prep_barrier_bench(p, 8, sfr=0, iters=6)),
            (p, prep_mutex_bench(p, 8, t_crit=10, iters=6)),
            (p, prep_barrier_bench(p, 8, sfr=400, iters=4)),
            (p, prep_barrier_bench(p, 16, sfr=50, iters=4)),
            (p, prep_chain_bench(p, 8, sfr=100, iters=4, depth=4)),
            (p, prep_work_queue_bench(p, 4, 4, items=16)),
        ]
    return jobs


def _arrival_traces(n_jobs: int) -> Dict[str, List[int]]:
    """Both scenarios, deterministic in the fixed seeds.

    Bursty: bursts wider than the fleet, long gaps between them -- the
    adversarial case for drain dispatch.  Poisson: steady random load."""
    assert n_jobs % 7 == 0, "mix is 6 shapes x policies; bursts of 7 tile it"
    return {
        "bursty": bursty_trace(
            n_bursts=n_jobs // 7, burst_size=7, gap_rounds=600,
            seed=17, jitter=40,
        ),
        "poisson": poisson_trace(rate=0.01, n_jobs=n_jobs, seed=17),
    }


def _serve(benches, arrivals, mode: str):
    """Run one (scenario, mode) cell; returns (service, jobs, wall_s)."""
    svc = FleetService(
        n_slots=N_SLOTS, slot_cores=SLOT_CORES,
        queue_limit=len(benches), admission=mode,
    )
    jobs = []
    i = 0
    guard = 0
    t0 = time.perf_counter()
    while i < len(benches) or svc.pending or svc.active:
        while i < len(benches) and arrivals[i] <= svc.round:
            jobs.append(svc.submit(benches[i][1].config))
            i += 1
        svc.step()
        guard += 1
        if guard > 50_000_000:
            raise RuntimeError("traffic benchmark failed to drain")
    wall = time.perf_counter() - t0
    return svc, jobs, wall


def _pct(values, q) -> float:
    """Deterministic percentile (no interpolation -- an observed value)."""
    return float(np.percentile(np.asarray(values, dtype=np.int64), q,
                               method="lower"))


def run(verbose: bool = True) -> Dict:
    mix = _job_mix()
    traces = _arrival_traces(len(mix))

    scenarios: Dict[str, Dict] = {}
    wall_totals = {m: 0.0 for m in ADMISSION_MODES}
    energy_jobs = None  # per-policy tail energy, from the bursty/continuous cell
    for name, trace in traces.items():
        cell: Dict[str, Dict] = {}
        for mode in ADMISSION_MODES:
            benches = _job_mix()  # fresh generators per cell
            svc, jobs, wall = _serve(benches, trace, mode)
            assert len(jobs) == len(mix)
            assert all(j.error is None for j in jobs)
            lat = [j.latency_rounds for j in jobs]
            cell[mode] = {
                "rounds": svc.round,
                "p50_latency_rounds": _pct(lat, 50),
                "p99_latency_rounds": _pct(lat, 99),
                "idle_lane_fraction": svc.idle_lane_fraction,
                "wall_s": wall,
            }
            wall_totals[mode] += wall
            if name == "bursty" and mode == "continuous":
                energy_jobs = [(label, j) for (label, _), j in zip(benches, jobs)]
        scenarios[name] = {
            "arrivals": {"first": trace[0], "last": trace[-1]},
            **cell,
        }

    # tail energy per discipline: p99 of the idle/spin split across each
    # policy's jobs (deterministic -- pure function of the gated stats)
    energy_tail: Dict[str, Dict[str, float]] = {}
    for policy in available_policies():
        splits = [job_energy(j.stats) for label, j in energy_jobs
                  if label == policy]
        energy_tail[policy] = {
            "p99_spin_pj": _pct([round(e.spin_pj) for e in splits], 99),
            "p99_idle_pj": _pct([round(e.idle_pj) for e in splits], 99),
        }

    result = {
        "fleet": {"n_slots": N_SLOTS, "slot_cores": SLOT_CORES},
        "n_jobs": len(mix),
        "scenarios": scenarios,
        "energy_tail": energy_tail,
        # same-run dispatch ratio (the soft-gated key): how much wall time
        # the drain baseline costs relative to continuous admission
        "speedup": wall_totals["drain"] / max(wall_totals["continuous"], 1e-9),
    }

    if verbose:
        print(f"\n== Sweep-service traffic ({len(mix)} jobs, "
              f"{N_SLOTS}x{SLOT_CORES}-lane fleet) ==")
        print(f"{'scenario':9s} {'mode':11s} {'rounds':>8s} {'p50 lat':>9s} "
              f"{'p99 lat':>9s} {'idle':>6s}")
        for name, sc in scenarios.items():
            for mode in ADMISSION_MODES:
                r = sc[mode]
                print(
                    f"{name:9s} {mode:11s} {r['rounds']:8d} "
                    f"{r['p50_latency_rounds']:9.0f} "
                    f"{r['p99_latency_rounds']:9.0f} "
                    f"{r['idle_lane_fraction']:6.1%}"
                )
        b = scenarios["bursty"]
        print(
            f"\nbursty p99 latency: drain {b['drain']['p99_latency_rounds']:.0f}"
            f" -> continuous {b['continuous']['p99_latency_rounds']:.0f} rounds"
            f"; idle lanes {b['drain']['idle_lane_fraction']:.1%} -> "
            f"{b['continuous']['idle_lane_fraction']:.1%}"
        )
        print(f"wall-clock: drain/continuous = {result['speedup']:.2f}x")
        tail = ", ".join(
            f"{p}: spin {v['p99_spin_pj']:.0f} / idle {v['p99_idle_pj']:.0f}"
            for p, v in energy_tail.items()
        )
        print(f"p99 energy per discipline (pJ): {tail}")
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
