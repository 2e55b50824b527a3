"""The reader of the device loop's iterations, ``sim.cycles_per_iteration``:
on one rehearsal-size window of the sim driver on the CPU, and on a ring
where the program counts no iterations, as a program without the counter
records none."""

import collections
import contextlib

import pytest

from chipbench.harness import Reading, Spec, prepare
from repro import obs
from repro.obs import Event

SPEC = Spec()
READ = SPEC.reader("sim.cycles_per_iteration")


@pytest.mark.parametrize("cell,low", [
    ("sim.fig5-8pe", 10.0),  # compute spans jumped
    ("sim.table1-8pe", 1.0),  # arbitration in most cycles
])
def test_reader_gives_the_windows_cycles_per_loop_iteration(cell, low):
    run = prepare(SPEC, cell, 2**41 + 9, rehearse=True)
    run.traffic["jobs"] = run.traffic["jobs"][:2]
    driver = SPEC.driver("sim_sweep").Driver(run)
    driver.setup()
    record = driver.window(0.1, lambda name: contextlib.nullcontext())
    iters = [e.n for e in obs.events(record["t0"], record["t1"])
             if e.name == "scu.loop_iterations"]
    assert len(iters) == len(record["jobs"])
    got = READ(Reading(run, record, None, None))
    assert got == pytest.approx(sum(record["cycles"]) / sum(iters))
    assert got >= low


def test_a_window_without_iteration_counts_reads_none(monkeypatch):
    ms = 1_000_000
    ring = [Event("scu.run", 1, None, 1, 10_000 * ms, 10_100 * ms),
            Event("scu.sync_ops", 2, 1, 1, 10_050 * ms, 10_050 * ms, 0)]
    monkeypatch.setattr(obs, "_ring", collections.deque(ring, maxlen=obs.MAXLEN))
    record = {"t0": 9.0, "t1": 11.0, "jobs": [0], "cycles": [1_000]}
    assert READ(Reading(None, record, None, None)) is None
    assert READ(Reading(None, dict(record, t0=0.0, t1=1e-9), None, None)) is None
