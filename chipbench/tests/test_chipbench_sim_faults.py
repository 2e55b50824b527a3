"""Each sim cell's check, with the timed path broken underneath, comes out
not correct; and each control fails the cell's limit.

The runs skip the harness's look for a chip and go through the rest of a
run (set-up, window, release, check) at the rehearsal sizes of the cells'
own configuration and traffic files, with the cells' own limits.  The
faults a cell can have: a step that returns its state unchanged, half of
the batch (or the PEs) left out, and a token or an answer altered where it
is produced.  No cell spans chips, so no exchange between chips can be
left out.
"""

import time

import numpy as np
import pytest

from chipbench.harness import Spec, prepare, run_cell

SPEC = Spec()


def _run(cell, seed, factory=None, seconds=0.3):
    run = prepare(SPEC, cell, seed, rehearse=True)
    run.traffic["jobs"] = run.traffic["jobs"][:3]  # a pass of three jobs keeps it short
    out = run_cell(SPEC, run, seconds, False, time.perf_counter(), driver_factory=factory)
    return out


# --------------------------------------------------------------------------
# sim cells
# --------------------------------------------------------------------------


def _sim_fault(fault):
    base = SPEC.driver("sim_sweep").Driver

    class Faulty(base):
        def execute(self, fb):
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


SIM_CELLS = ["sim.table1-8pe", "sim.fig5-8pe"]


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_sim_sound_run_is_correct(cell):
    out = _run(cell, 2**36 + 5)
    assert out["correct"] and out["failed"] == 0, out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "altered_token"])
@pytest.mark.parametrize("cell", SIM_CELLS)
def test_sim_fault_is_not_correct(cell, fault):
    out = _run(cell, 2**36 + 5, _sim_fault(fault))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_sim_control_fails_the_limit(cell):
    run = prepare(SPEC, cell, 2**36 + 9, rehearse=True)
    run.traffic["jobs"] = run.traffic["jobs"][:3]
    driver = SPEC.driver("sim_sweep").Driver(run)
    rec = {"jobs": list(range(len(run.traffic["jobs"])))}
    assert driver.control(rec)["mismatched_jobs"] == len(rec["jobs"])
