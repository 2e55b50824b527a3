"""The reduction from a device trace to numbers: on hand-made intervals, and
on a short traced window of ``sim.table1-8pe`` recorded on one TPU v5e
(``data/sim.table1-8pe.trace.json.gz``: three passes of the mix, its
device programs and host spans whole, its operations cut to the first
3,000)."""

from pathlib import Path

import pytest

from chipbench import trace
from chipbench.trace import Event, TraceEvents


def _events():
    # window [0, 100]; device ops [10, 30], [25, 40] (overlap), [60, 70],
    # [95, 120] (cut by the window's end); module runs [10, 40], [60, 70]
    ops = [Event("fusion", 10, 30), Event("copy", 25, 40), Event("fusion", 60, 70),
           Event("fusion", 95, 120)]
    mods = [Event("jit_step(1)", 10, 40), Event("jit_step(1)", 60, 70),
            Event("jit_step(1)", 95, 120)]
    spans = [Event("window", 0, 100), Event("step", 0, 12), Event("readback", 12, 45),
             Event("session_reset", 48, 58), Event("step", 58, 61)]
    return TraceEvents(ops=[ops], modules=[mods], spans=spans)


def test_busy_idle_and_gaps_by_hand():
    s = trace.summarize(_events())
    assert s.window_s == pytest.approx(100e-9)
    # busy: [10, 40] + [60, 70] + [95, 100] = 45
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)
    # gaps [0,10] [40,60] [70,95]: step 0-10; readback 40-45; none 45-48;
    # reset 48-58; step 58-60; none 70-95
    assert s.idle_s["step"] == pytest.approx(12e-9)
    assert s.idle_s["readback"] == pytest.approx(5e-9)
    assert s.idle_s["session_reset"] == pytest.approx(10e-9)
    assert s.idle_s[trace.OTHER] == pytest.approx(28e-9)
    assert sum(s.idle_s.values()) == pytest.approx(55e-9)
    # only runs wholly inside the window count as a step's device time
    assert s.module_runs == {"jit_step(1)": [pytest.approx(30e-9), pytest.approx(10e-9)]}
    assert s.op_s["fusion"] == pytest.approx(35e-9)
    assert s.breakdown(top=1)["device_ops"] == [["fusion", pytest.approx(35e-9)]]


def test_innermost_span_takes_the_gap():
    ev = TraceEvents(ops=[[Event("op", 50, 60)]], modules=[[]],
                     spans=[Event("window", 0, 100), Event("outer", 0, 100),
                            Event("inner", 20, 30)])
    s = trace.summarize(ev)
    assert s.idle_s == {"outer": pytest.approx(80e-9), "inner": pytest.approx(10e-9)}


def test_a_window_with_no_device_work_is_refused():
    ev = TraceEvents(ops=[[Event("op", 200, 300)]], modules=[[]],
                     spans=[Event("window", 0, 100)])
    with pytest.raises(ValueError):
        trace.summarize(ev)


def test_events_round_trip(tmp_path):
    ev = _events()
    trace.dump_events(ev, tmp_path / "ev.json.gz")
    assert trace.load_events(tmp_path / "ev.json.gz") == ev


def test_recorded_tpu_trace_reduces_as_on_the_chip():
    ev = trace.load_events(Path(__file__).parent / "data" / "sim.table1-8pe.trace.json.gz")
    # planes and lines read on the chip: one device, its programs, the spans
    assert len(ev.modules) == 1 and len(ev.ops) == 1
    names = {e.name.split("(")[0] for e in ev.modules[0]}
    assert "jit_while" in names
    assert sorted({e.name for e in ev.spans}) == ["execute", "lower", "window"]
    s = trace.summarize(ev)
    # the numbers the run printed on the chip, from the same events
    assert s.window_s == pytest.approx(7.368822369)
    assert s.busy_s == pytest.approx(1.773825762)
    assert s.idle_s["execute"] == pytest.approx(5.50590766)
    assert s.idle_s["lower"] == pytest.approx(0.088605756)
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)
    # one executor loop per job: three passes of the five jobs
    assert sum(len(v) for k, v in s.module_runs.items() if k.startswith("jit_while(")) == 15
