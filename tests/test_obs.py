"""The in-program span and counter recorder (``repro.obs``)."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro import obs
from repro.compat import HAS_JAX

SRC = Path(__file__).resolve().parent.parent / "src"


def _since(t0):
    return obs.events(t0, time.perf_counter())


def test_spans_nest_under_one_root():
    t0 = time.perf_counter()
    with obs.span("outer") as outer:
        with obs.span("mid") as mid:
            with obs.span("inner") as inner:
                pass
        with obs.span("sibling") as sib:
            pass
    ev = {e.name: e for e in _since(t0)}
    assert [e.name for e in _since(t0)] == ["outer", "mid", "inner", "sibling"]
    assert ev["outer"].parent is None
    assert ev["mid"].parent == ev["sibling"].parent == outer.id
    assert ev["inner"].parent == mid.id
    assert {e.root for e in ev.values()} == {outer.id}
    assert (ev["inner"].id, ev["sibling"].id) == (inner.id, sib.id)
    # each child lies inside its parent
    for child, parent in (("mid", "outer"), ("inner", "mid"), ("sibling", "outer")):
        assert ev[parent].start_ns <= ev[child].start_ns <= ev[child].end_ns <= ev[parent].end_ns
    assert all(e.n is None for e in ev.values())


def test_a_second_root_gets_its_own_id():
    t0 = time.perf_counter()
    with obs.span("a") as a:
        pass
    with obs.span("b") as b:
        pass
    ev = _since(t0)
    assert [(e.name, e.root) for e in ev] == [("a", a.id), ("b", b.id)]
    assert a.id != b.id


def test_counters_attach_to_the_innermost_open_span():
    t0 = time.perf_counter()
    obs.count("loose", 2)
    with obs.span("outer") as outer:
        obs.count("c")
        with obs.span("inner") as inner:
            obs.count("c", 3)
    counts = [e for e in _since(t0) if e.n is not None]
    assert [(e.name, e.n, e.parent, e.root) for e in counts] == [
        ("loose", 2, None, None), ("c", 1, outer.id, outer.id), ("c", 3, inner.id, outer.id)]
    assert all(e.start_ns == e.end_ns for e in counts)


def test_events_filter_by_a_perf_counter_window():
    with obs.span("before"):
        pass
    t0 = time.perf_counter()
    with obs.span("inside"):
        obs.count("tick")
    t1 = time.perf_counter()
    with obs.span("after"):
        pass
    with obs.span("straddles"):
        t_mid = time.perf_counter()
    assert [e.name for e in obs.events(t0, t1)] == ["inside", "tick"]
    assert [e.name for e in obs.events(t1, None)][-2:] == ["after", "straddles"]
    assert "straddles" not in [e.name for e in obs.events(t0, t_mid)]
    assert "before" in [e.name for e in obs.events(None, t0)]


def test_the_ring_stays_at_its_maxlen():
    ring = obs._ring
    assert ring.maxlen == obs.MAXLEN
    t0 = time.perf_counter()
    for i in range(obs.MAXLEN + 10):
        obs.count("fill", i)
    assert len(ring) == obs.MAXLEN
    kept = _since(t0)
    assert len(kept) == obs.MAXLEN
    assert (kept[0].n, kept[-1].n) == (10, obs.MAXLEN + 9)  # the oldest went first


def test_an_exception_still_closes_the_span():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with obs.span("fails"):
            raise ValueError
    with obs.span("next") as nxt:
        pass
    ev = _since(t0)
    assert [e.name for e in ev] == ["fails", "next"]
    assert nxt.parent is None  # the failed span left the stack


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_spans_record_with_the_profiler_off():
    import jax  # noqa: F401  (the annotation is taken where jax is loaded)

    t0 = time.perf_counter()
    with obs.span("quiet"):
        time.sleep(0.002)
    (e,) = _since(t0)
    assert e.name == "quiet" and e.end_ns - e.start_ns >= 2_000_000


def test_spans_record_without_jax():
    code = (
        "import sys, time\n"
        "from repro import obs\n"
        "t0 = time.perf_counter()\n"
        "with obs.span('x'):\n"
        "    obs.count('y')\n"
        "assert [e.name for e in obs.events(t0, time.perf_counter())] == ['x', 'y']\n"
        "assert 'jax' not in sys.modules\n"
    )
    env = dict(os.environ, PYTHONPATH=str(SRC), REPRO_NO_JAX="1")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 0, p.stderr


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_executor_spans_are_host_spans_of_a_profiler_trace():
    """Around a CPU ``run_traces_jax`` call, the profiler's trace holds the
    executor's ``scu.run`` and ``scu.loop`` as host spans, each as long as
    the ring's record of it: the ring and the trace time the same blocks."""
    from chipbench.trace import capture
    from repro.core.scu.programs import prep_barrier_bench
    from repro.core.scu.trace import run_traces_jax

    fb = prep_barrier_bench("sw", 4, sfr=0, iters=2, compiled=True)
    t0 = time.perf_counter()
    with capture(("scu.run", "scu.loop")) as cap:
        run_traces_jax(fb.config.programs, n_banks=fb.config.cluster.n_banks)
    ring = {e.name: e for e in _since(t0) if e.name in ("scu.run", "scu.loop")}
    traced = {e.name: e for e in cap["events"].spans}
    assert sorted(traced) == sorted(ring) == ["scu.loop", "scu.run"]
    assert len(cap["events"].spans) == 2
    run, loop = traced["scu.run"], traced["scu.loop"]
    assert run.start_ns <= loop.start_ns <= loop.end_ns <= run.end_ns
    for name, e in traced.items():
        ring_ns = ring[name].end_ns - ring[name].start_ns
        assert abs((e.end_ns - e.start_ns) - ring_ns) <= max(1e6, 0.02 * ring_ns), name
