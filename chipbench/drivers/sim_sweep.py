"""Driver ``sim_sweep``: a sweep of cluster microbenchmark jobs through the
program's lowering and device executor, one job at a time.

A job is lowered to static traces by ``repro.core.scu.programs.prep_*_bench
(compiled=True)`` with the configuration's cost model, and run by
``repro.core.scu.trace.run_traces_jax`` with the configuration's TAS
latency; its result is on the host when the call returns.  Set-up runs
every distinct job of the mix once (each call compiles its own program, and
the persistent cache then holds it).  The window runs passes over the whole
mix, each in an order drawn from the seed, until the time is up; the pass
in flight then is finished and counted, so that every seed's window holds
the same work (whole passes) in another order.  Host spans ``lower`` and
``execute`` mark the two phases, and a ``jax.monitoring`` listener sums the
tracing, lowering and compile (or cache read) time of each execution.  A
traced run traces the first pass only: the profiler records every operation
of the executor's loop, and reading a whole window of them would outlast
the run's time limit.

The check: every job of the window is compared bit for bit with the plain
reference (``chipbench/reference/<reference>.py``), run once per distinct
program after the window.
"""

from __future__ import annotations

import contextlib
import importlib
import time
from typing import Dict, List

import numpy as np

# jax.monitoring duration events that make up a call's trace-and-compile time
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Driver:
    SPANS = ("lower", "execute")

    def __init__(self, run):
        self.run = run
        self.config = run.config
        self.n = int(run.config["n_pes"])
        self.jobs: List[Dict] = list(run.traffic["jobs"])
        self.rng = np.random.default_rng(run.seed)
        self.ref_mod = importlib.import_module(f"chipbench.reference.{run.config['reference']}")
        self._compile_s = 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self._compile_s += duration

    def lower(self, job: Dict):
        from repro.core.scu.primitives import CostModel
        from repro.core.scu.programs import prep_barrier_bench, prep_mutex_bench

        cm = CostModel(**self.config["cost_model"])
        if job["primitive"] == "barrier":
            fb = prep_barrier_bench(job["policy"], self.n, sfr=job["sfr"], iters=job["iters"],
                                    cost_model=cm, compiled=True)
        else:
            fb = prep_mutex_bench(job["policy"], self.n, t_crit=job["t_crit"], sfr=job["sfr"],
                                  iters=job["iters"], cost_model=cm, compiled=True)
        banks = self.config["cluster"]["banking_factor"] * self.n
        if fb.config.cluster.n_banks != banks:
            raise ValueError(
                f"program built {fb.config.cluster.n_banks} banks, config states {banks}")
        return fb

    def execute(self, fb) -> Dict:
        from repro.core.scu.trace import run_traces_jax

        return run_traces_jax(fb.config.programs, n_banks=fb.config.cluster.n_banks,
                              tas_cycles=self.config["cluster"]["tas_cycles"])

    def setup(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        for job in self.jobs:
            self.execute(self.lower(job))

    def window(self, seconds: float, span, traced=contextlib.nullcontext) -> Dict:
        rec = {"n_pes": self.n, "jobs": [], "cycles": [], "lower_s": [], "compile_s": [],
               "results": [], "ends": []}
        rec["t0"] = time.perf_counter()
        with traced():
            self._pass(rec, span)
        rec["traced_cycles"] = sum(rec["cycles"])
        while rec["ends"][-1] - rec["t0"] < seconds:
            self._pass(rec, span)
        rec["t1"] = rec["ends"][-1]
        return rec

    def _pass(self, rec: Dict, span) -> None:
        """Every job of the mix once, in an order drawn from the seed."""
        for j in self.rng.permutation(len(self.jobs)):
            t = time.perf_counter()
            with span("lower"):
                fb = self.lower(self.jobs[j])
            rec["lower_s"].append(time.perf_counter() - t)
            self._compile_s = 0.0
            with span("execute"):
                r = self.execute(fb)
            rec["ends"].append(time.perf_counter())
            rec["compile_s"].append(self._compile_s)
            rec["jobs"].append(int(j))
            rec["cycles"].append(int(r["cycles"]))
            rec["results"].append(r)

    def release(self) -> None:
        pass  # a job's device state is gone when its call returns

    def _mismatched(self, results) -> int:
        refs, failed = {}, 0
        for j, got in results:
            key = self.ref_mod.job_key(self.config, self.jobs[j])
            if key not in refs:
                refs[key] = self.ref_mod.run_job(self.config, self.jobs[j], self.n)
            failed += bool(self.ref_mod.differences(got, refs[key]))
        return failed

    def check(self, rec: Dict) -> Dict:
        """Every job of the window against the reference, bit for bit."""
        failed = self._mismatched(zip(rec["jobs"], rec["results"]))
        return {
            "checks": {"mismatched_jobs": {"value": failed, "limit": 0}},
            "correct": failed == 0,
            "attempted": len(rec["jobs"]),
            "failed": failed,
            "compared": len(rec["jobs"]),
        }

    def control(self, rec: Dict) -> Dict:
        """The control's reading: the reference with two grants per bank per
        cycle, in the executor's place, over the window's jobs."""
        results = [(j, self.ref_mod.run_job(self.config, self.jobs[j], self.n, grants_per_bank=2))
                   for j in sorted(set(rec["jobs"]))]
        return {"mismatched_jobs": self._mismatched(results)}
