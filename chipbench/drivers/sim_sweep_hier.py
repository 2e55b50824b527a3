"""Driver ``sim_sweep_hier``: the ``sim_sweep`` driver on a hierarchical
cluster, the configuration's ``cluster.hierarchy`` (tiles, groups and the
hop latency of each).

A job is lowered by ``repro.core.scu.programs.prep_*_bench(compiled=True,
interconnect=...)`` onto a cluster with the configuration's banking factor
and hierarchy, and run by ``repro.core.scu.trace.run_traces_jax`` with that
hierarchy.  The window, the check against the plain reference and the
spans are ``sim_sweep``'s.  The control is the reference with every hop
answered in one cycle, a flat cluster of the same size.
"""

from __future__ import annotations

from typing import Dict

from chipbench.drivers import sim_sweep


class Driver(sim_sweep.Driver):
    def __init__(self, run):
        super().__init__(run)
        from repro.core.scu.engine import Hierarchy

        h = self.config["cluster"]["hierarchy"]
        lat = h["latency"]
        self.hierarchy = Hierarchy(h["cores_per_tile"], h["tiles_per_group"], h["groups"],
                                   (lat["tile"], lat["group"], lat["remote"]))

    def lower(self, job: Dict):
        from repro.core.scu.primitives import CostModel
        from repro.core.scu.programs import prep_barrier_bench, prep_mutex_bench

        cm = CostModel(**self.config["cost_model"])
        banks = self.config["cluster"]["banking_factor"] * self.n
        ic = {"banking_factor": self.config["cluster"]["banking_factor"],
              "hierarchy": self.hierarchy}
        if job["primitive"] == "barrier":
            fb = prep_barrier_bench(job["policy"], self.n, sfr=job["sfr"], iters=job["iters"],
                                    cost_model=cm, compiled=True, interconnect=ic)
        else:
            fb = prep_mutex_bench(job["policy"], self.n, t_crit=job["t_crit"], sfr=job["sfr"],
                                  iters=job["iters"], cost_model=cm, compiled=True,
                                  interconnect=ic)
        cl = fb.config.cluster
        if cl.n_banks != banks or cl.hierarchy != self.hierarchy:
            raise ValueError(f"program built {cl.n_banks} banks under {cl.hierarchy}, "
                             f"config states {banks} under {self.hierarchy}")
        return fb

    def execute(self, fb) -> Dict:
        from repro.core.scu.trace import run_traces_jax

        return run_traces_jax(fb.config.programs, n_banks=fb.config.cluster.n_banks,
                              tas_cycles=self.config["cluster"]["tas_cycles"],
                              hierarchy=self.hierarchy)

    def control(self, rec: Dict) -> Dict:
        """The control's reading: the reference with every hop answered in
        one cycle, in the executor's place, over the window's jobs."""
        results = [(j, self.ref_mod.run_job(self.config, self.jobs[j], self.n,
                                            latency=self.ref_mod.FLAT))
                   for j in sorted(set(rec["jobs"]))]
        return {"mismatched_jobs": self._mismatched(results)}
