"""The ``sim.mempool-256pe`` cell on the CPU: its plain reference against
the program's lockstep engine at the rehearsal size (16 PEs in 4 tiles and
2 groups), a rehearsal of the cell end to end, its control, its faults
caught, and the reader of its remote accesses."""

import contextlib
import json
import time

import numpy as np
import pytest

from chipbench.harness import Reading, Spec, prepare, run_cell
from chipbench.reference import mempool_cluster as ref_mod

SPEC = Spec()
CELL = "sim.mempool-256pe"
RUN = prepare(SPEC, CELL, seed=2**33 + 19, rehearse=True)
JOBS = RUN.traffic["jobs"]


def _engine(job, n, hierarchy):
    from repro.core.scu.primitives import CostModel
    from repro.core.scu.programs import prep_barrier_bench

    cm = CostModel(**RUN.config["cost_model"])
    ic = {"banking_factor": RUN.config["cluster"]["banking_factor"], "hierarchy": hierarchy}
    fb = prep_barrier_bench(job["policy"], n, sfr=job["sfr"], iters=job["iters"],
                            cost_model=cm, compiled=True, mode="lockstep", interconnect=ic)
    cl = fb.config.cluster
    cl.load(fb.config.programs)
    st = cl.run()
    return {"cycles": st.cycles, "bank_conflicts": st.bank_conflicts,
            "counters": {k: np.array([getattr(c, k) for c in st.cores]) for k in ref_mod.COUNTERS},
            "finished_at": np.array([c.finished_at for c in st.cores]),
            "tcdm": {a: cl.tcdm.get(a, 0) for a in sorted(cl.tcdm)}}


def _driver(run=RUN):
    return SPEC.driver("sim_sweep_hier").Driver(run)


@pytest.mark.parametrize("j", range(len(JOBS)), ids=[j["policy"] for j in JOBS])
def test_reference_equals_the_engine_and_the_control_does_not(j):
    n = RUN.config["n_pes"]
    h = RUN.config["cluster"]["hierarchy"]
    assert (n, h["tiles_per_group"], h["groups"]) == (16, 2, 2)
    got = _engine(JOBS[j], n, _driver().hierarchy)
    assert ref_mod.differences(got, ref_mod.run_job(RUN.config, JOBS[j], n)) == []
    assert ref_mod.differences(got, ref_mod.run_job(RUN.config, JOBS[j], n, latency=ref_mod.FLAT))


def test_configuration_is_mempool_as_published():
    run = prepare(SPEC, CELL, seed=1)
    cfg, h = run.config, run.config["cluster"]["hierarchy"]
    tiles = cfg["n_pes"] // h["cores_per_tile"]
    assert (cfg["n_pes"], cfg["cluster"]["banking_factor"] * cfg["n_pes"]) == (256, 1024)
    assert (tiles, h["tiles_per_group"], h["groups"]) == (64, 16, 4)
    assert h["latency"] == {"tile": 1, "group": 3, "remote": 5}
    assert cfg["reduced"] == [] and _driver(run).hierarchy.n_cores == 256


def test_rehearsal_passes_its_check(capsys):
    from chipbench import run as entry

    rc = entry.main(["--workload", CELL, "--seed", str(2**35 + 7), "--seconds", "0.5",
                     "--rehearse"])
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= len(JOBS)
    assert line["checks"] == {"mismatched_jobs": {"value": 0, "limit": 0}}


def test_control_mismatches_every_job():
    rec = {"jobs": list(range(len(JOBS)))}
    assert _driver().control(rec)["mismatched_jobs"] == len(JOBS)


def _faulty(fault):
    class Faulty(SPEC.driver("sim_sweep_hier").Driver):
        def execute(self, fb):
            if fault == "flat":  # the hierarchy dropped on the way to the executor
                from repro.core.scu.trace import run_traces_jax

                return run_traces_jax(fb.config.programs, n_banks=fb.config.cluster.n_banks,
                                      tas_cycles=self.config["cluster"]["tas_cycles"])
            r = super().execute(fb)
            if fault == "state_unchanged":
                r = dict(r, cycles=0, bank_conflicts=0, tcdm={a: 0 for a in r["tcdm"]},
                         counters={k: np.zeros_like(v) for k, v in r["counters"].items()})
            elif fault == "half_batch":
                n = len(r["finished_at"])
                r = dict(r, counters={k: np.concatenate([v[: n // 2], np.zeros_like(v[n // 2:])])
                                      for k, v in r["counters"].items()})
            elif fault == "altered_token":
                r = dict(r, cycles=r["cycles"] + 1)
            return r

    return Faulty


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "altered_token", "flat"])
def test_fault_is_not_correct(fault):
    run = prepare(SPEC, CELL, 2**36 + 11, rehearse=True)
    out = run_cell(SPEC, run, 0.3, False, time.perf_counter(), driver_factory=_faulty(fault))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1


def test_remote_accesses_reader_counts_a_jobs_grants_outside_the_tile():
    """Over whole passes at the rehearsal size the reader gives the pass's
    remote accesses per job; a window without one count per job reads
    ``None``."""
    from repro import obs

    driver = _driver()
    driver.setup()
    record = driver.window(0.1, lambda name: contextlib.nullcontext())
    read = SPEC.reader("sim.remote_accesses_per_job")
    counts = [e.n for e in obs.events(record["t0"], record["t1"])
              if e.name == "scu.remote_accesses"]
    assert len(counts) == len(record["jobs"]) and min(counts) > 0
    assert len(record["jobs"]) % len(JOBS) == 0
    assert read(Reading(RUN, record, None, None)) == sum(counts) / len(record["jobs"])
    assert read(Reading(RUN, dict(record, t0=0.0, t1=1e-9), None, None)) is None
    # a flat executor's window counts no remote access per job
    flat = SPEC.driver("sim_sweep").Driver(prepare(SPEC, "sim.table1-8pe", 5, rehearse=True))
    flat.jobs = flat.jobs[:1]
    rec = flat.window(0.0, lambda name: contextlib.nullcontext())
    assert read(Reading(RUN, rec, None, None)) == 0.0
