"""The readers of the executor's own spans and counters (``repro.obs``):
on hand-built rings, and on one rehearsal-size window of the sim driver on
the CPU, where the five phases add up to the driver's ``execute`` time."""

import collections
import contextlib
import sys

import pytest

import repro
from chipbench.harness import Reading, Spec, prepare
from repro import obs
from repro.obs import Event

SPEC = Spec()
SPAN_METRICS = ["sim.pack_ms_per_job", "sim.stage_ms_per_job", "sim.loop_ms_per_job",
                "sim.wait_ns_per_cycle", "sim.readback_ms_per_job"]
METRICS = SPAN_METRICS + ["sim.retraces_per_job"]
MS = 1_000_000  # ns


def _job(ids, t_ms, phases_ms, traces=1):
    """One executor call at ``t_ms``: its root, the five phases of the given
    lengths back to back, and its loop-trace counts."""
    root = next(ids)
    out, t = [], t_ms * MS
    for name, length in zip(["scu.pack", "scu.stage", "scu.loop", "scu.wait", "scu.readback"],
                            phases_ms):
        i = next(ids)
        out.append(Event(name, i, root, root, t, t + length * MS))
        if name == "scu.loop" and traces:
            out.append(Event("scu.loop_traces", next(ids), i, root, t + MS, t + MS, traces))
        t += length * MS
    out.append(Event("scu.run", root, None, root, t_ms * MS, t))
    return out


def _ring(monkeypatch, events):
    monkeypatch.setattr(obs, "_ring", collections.deque(events, maxlen=obs.MAXLEN))


def _read(name, record):
    return SPEC.reader(name)(Reading(None, record, None, None))


def _setup_and_window(jobs_in_window=2):
    ids = iter(range(1, 10_000))
    ev = _job(ids, 1_000, [5, 500, 900, 100, 50], traces=3)  # set-up, before t0
    for k in range(jobs_in_window):
        ev += _job(ids, 10_000 + 1_000 * k, [1, 10, 200, 300, 4])
    record = {"t0": 9.0, "t1": 10.0 + jobs_in_window, "jobs": list(range(jobs_in_window)),
              "cycles": [1_000_000] * jobs_in_window}
    return ev, record


def test_readers_take_the_window_and_leave_out_set_up(monkeypatch):
    ev, record = _setup_and_window()
    _ring(monkeypatch, ev)
    got = {m: _read(m, record) for m in METRICS}
    assert got == {
        "sim.pack_ms_per_job": pytest.approx(1.0),
        "sim.stage_ms_per_job": pytest.approx(10.0),
        "sim.loop_ms_per_job": pytest.approx(200.0),
        # 2 x 300 ms over 2 x 1e6 cycles
        "sim.wait_ns_per_cycle": pytest.approx(300.0),
        "sim.readback_ms_per_job": pytest.approx(4.0),
        "sim.retraces_per_job": pytest.approx(1.0),
    }


def test_a_run_count_that_disagrees_with_the_jobs_reads_none(monkeypatch):
    ev, record = _setup_and_window()
    _ring(monkeypatch, ev)
    record["jobs"].append(2)
    record["cycles"].append(1_000_000)
    assert {m: _read(m, record) for m in METRICS} == dict.fromkeys(METRICS)


def test_an_empty_ring_reads_none(monkeypatch):
    _, record = _setup_and_window()
    _ring(monkeypatch, [])
    assert {m: _read(m, record) for m in METRICS} == dict.fromkeys(METRICS)


def test_a_window_without_runs_reads_none(monkeypatch):
    ev, record = _setup_and_window(jobs_in_window=0)
    _ring(monkeypatch, ev)  # only the set-up call, before t0
    record["jobs"], record["cycles"] = [0], [1_000_000]
    assert {m: _read(m, record) for m in METRICS} == dict.fromkeys(METRICS)


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    _, record = _setup_and_window()
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails, as before it existed
    assert {m: _read(m, record) for m in METRICS} == dict.fromkeys(METRICS)


def test_phases_add_up_to_the_drivers_execute_time():
    run = prepare(SPEC, "sim.table1-8pe", 2**40 + 77, rehearse=True)
    run.traffic["jobs"] = run.traffic["jobs"][:3]
    driver = SPEC.driver("sim_sweep").Driver(run)
    driver.setup()
    rec = driver.window(0.3, lambda name: contextlib.nullcontext())
    got = {m: _read(m, rec) for m in METRICS}
    assert None not in got.values(), got
    assert got["sim.retraces_per_job"] == 1.0
    jobs = len(rec["jobs"])
    phases_s = (sum(got[m] for m in SPAN_METRICS if m.endswith("_ms_per_job")) * 1e-3 * jobs
                + got["sim.wait_ns_per_cycle"] * sum(rec["cycles"]) * 1e-9)
    # the driver's execute span per job: from the end of the job before (the
    # window's start for the first) to the job's end, less its lowering
    prev = [rec["t0"]] + rec["ends"][:-1]
    execute_s = sum(e - p - lo for e, p, lo in zip(rec["ends"], prev, rec["lower_s"]))
    assert phases_s == pytest.approx(execute_s, rel=0.05)
