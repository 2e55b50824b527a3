"""The ``sim.table1-scu-8pe`` cell on the CPU: its plain reference against
the program's lockstep engine and its control at the rehearsal size, a
rehearsal of the cell end to end, its faults caught, and the reader of
its SCU transactions."""

import contextlib
import json

import numpy as np
import pytest

from chipbench.harness import Reading, Spec, prepare
from chipbench.reference import scu_cluster_hw as ref_mod
from chipbench.tests.test_chipbench_sim_faults import _run, _sim_fault

SPEC = Spec()
CELL = "sim.table1-scu-8pe"
RUN = prepare(SPEC, CELL, seed=2**33 + 17, rehearse=True)
JOBS = RUN.traffic["jobs"]


def _engine(job, n):
    from repro.core.scu.primitives import CostModel
    from repro.core.scu.programs import prep_barrier_bench, prep_mutex_bench

    cm = CostModel(**RUN.config["cost_model"])
    if job["primitive"] == "barrier":
        fb = prep_barrier_bench(job["policy"], n, sfr=job["sfr"], iters=job["iters"],
                                cost_model=cm, compiled=True, mode="lockstep")
    else:
        fb = prep_mutex_bench(job["policy"], n, t_crit=job["t_crit"], sfr=job["sfr"],
                              iters=job["iters"], cost_model=cm, compiled=True, mode="lockstep")
    cl = fb.config.cluster
    cl.load(fb.config.programs)
    st = cl.run()
    return {"cycles": st.cycles, "bank_conflicts": st.bank_conflicts,
            "counters": {k: np.array([getattr(c, k) for c in st.cores]) for k in ref_mod.COUNTERS},
            "finished_at": np.array([c.finished_at for c in st.cores]),
            "tcdm": {a: cl.tcdm.get(a, 0) for a in sorted(cl.tcdm)}}


@pytest.mark.parametrize("j", range(len(JOBS)),
                         ids=[f"{j['primitive']}-{j['policy']}-{j.get('t_crit', 0)}-{j['sfr']}"
                              for j in JOBS])
def test_reference_equals_the_engine_and_the_control_does_not(j):
    n = RUN.config["n_pes"]
    assert n == 4
    got = _engine(JOBS[j], n)
    assert ref_mod.differences(got, ref_mod.run_job(RUN.config, JOBS[j], n)) == []
    assert ref_mod.differences(got, ref_mod.run_job(RUN.config, JOBS[j], n, grants_per_bank=2))
    assert got["counters"]["scu_accesses"].sum() > 0


def test_rehearsal_passes_its_check(capsys):
    from chipbench import run as entry

    rc = entry.main(["--workload", CELL, "--seed", str(2**35 + 3), "--seconds", "0.5",
                     "--rehearse"])
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= len(JOBS)
    assert line["checks"] == {"mismatched_jobs": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "altered_token"])
def test_fault_is_not_correct(fault):
    out = _run(CELL, 2**36 + 5, _sim_fault(fault))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1


def test_sync_ops_reader_counts_a_jobs_scu_transactions():
    """Over whole passes at the rehearsal size the reader gives the pass's
    SCU transactions per job; a window with no count reads ``None``."""
    driver = SPEC.driver("sim_sweep").Driver(RUN)
    driver.setup()
    record = driver.window(0.1, lambda name: contextlib.nullcontext())
    read = SPEC.reader("sim.sync_ops_per_job")
    want = sum(int(r["counters"]["scu_accesses"].sum()) for r in record["results"])
    assert read(Reading(RUN, record, None, None)) == want / len(record["jobs"])
    assert len(record["jobs"]) % len(JOBS) == 0
    assert read(Reading(RUN, dict(record, t0=0.0, t1=1e-9), None, None)) is None
