"""The program's own spans and counters (``repro.obs``) inside a sim window,
for the per-layer readers of the device executor's phases.

The window is ``[record["t0"], record["t1"]]`` on ``time.perf_counter``'s
clock, the one the driver's record and the program's ring share.  Each job
of the window is one ``run_traces_jax`` call, so one ``scu.run`` span; a
window whose ``scu.run`` spans are missing or do not match its jobs in
number reads ``None``, as does a program that has no recorder.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def _window(record: Dict) -> Optional[List]:
    try:
        from repro import obs
    except ImportError:  # a program older than its recorder
        return None
    ev = obs.events(record["t0"], record["t1"])
    runs = sum(e.name == "scu.run" for e in ev)
    if runs == 0 or runs != len(record["jobs"]):
        return None
    return ev


def span_ns(record: Dict, name: str) -> Optional[float]:
    """Nanoseconds in the window's ``name`` spans, in all."""
    ev = _window(record)
    if ev is None:
        return None
    return float(sum(e.end_ns - e.start_ns for e in ev if e.name == name))


def ms_per_job(record: Dict, name: str) -> Optional[float]:
    """Mean milliseconds per job in ``name`` spans."""
    ns = span_ns(record, name)
    return None if ns is None else ns * 1e-6 / len(record["jobs"])


def count_per_job(record: Dict, name: str) -> Optional[float]:
    """The window's ``name`` counts, per job."""
    ev = _window(record)
    if ev is None:
        return None
    return sum(e.n for e in ev if e.name == name) / len(record["jobs"])
