"""Profiler capture and the reduction of a device trace to numbers.

:func:`capture` wraps the traced window in ``jax.profiler`` (Python tracer
off) and :func:`read_xspace` reads the ``.xplane.pb`` it writes into
:class:`TraceEvents`: per device, the operations and the compiled programs
(modules) that ran, and the benchmark's own host spans.  :func:`summarize`
reduces those to what the per-layer readers use: device busy time inside
the window (the union of the intervals in which a compiled program ran,
averaged over the devices used), the idle gaps attributed to the host span that covered them, time
per device operation name, and every run of each compiled program.

:class:`TraceEvents` round-trips through JSON (:func:`dump_events`,
:func:`load_events`), which is how the tests keep a small recorded trace.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import gzip
import itertools
import json
import os
import shutil
import tempfile
from typing import Dict, Iterator, List, Sequence, Tuple

WINDOW = "window"
OTHER = "(no span)"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class TraceEvents:
    ops: List[List[Event]]  # per device: operations as they ran
    modules: List[List[Event]]  # per device: compiled programs as they ran
    spans: List[Event]  # the benchmark's host spans


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the devices used
    op_s: Dict[str, float]  # device time per operation name, mean over devices
    idle_s: Dict[str, float]  # idle time by the host span that covered it
    module_runs: Dict[str, List[float]]  # seconds of each run, per program name

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [list(kv) for kv in ops], "idle_gaps": [list(kv) for kv in gaps]}


@contextlib.contextmanager
def capture(span_names: Sequence[str]) -> Iterator[Dict]:
    """Trace the enclosed block; afterwards ``out["events"]`` holds its
    :class:`TraceEvents`, with the host spans named ``span_names`` and
    :data:`WINDOW`.  The raw trace lives in a temporary directory (under
    ``TMPDIR``) that is removed once read."""
    import jax

    out: Dict = {}
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        out["events"] = read_xspace(paths[-1], (WINDOW, *span_names))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# Operation events read per device: a while loop that runs for seconds
# makes millions of them.  Busy time comes from the programs (modules), so
# a cut here only shortens the per-operation breakdown.
MAX_OPS = 2_000_000


def read_xspace(path: str, span_names: Sequence[str], max_ops: int = MAX_OPS) -> TraceEvents:
    """Device planes (``/device:...``) give compiled programs (line ``XLA
    Modules``) and operations (line ``XLA Ops``, the first ``max_ops``);
    host planes give the spans named ``span_names``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            mods, opl = lines.get("XLA Modules"), lines.get("XLA Ops")
            if mods is None and opl is None:
                continue
            modules.append([_event(e) for e in mods.events] if mods is not None else [])
            ops.append([_event(e) for e in itertools.islice(opl.events, max_ops)]
                       if opl is not None else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events if e.name in span_names]
    return TraceEvents(ops=ops, modules=modules, spans=spans)


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))


def dump_events(ev: TraceEvents, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(dataclasses.asdict(ev), f)


def load_events(path: str) -> TraceEvents:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return TraceEvents(
        ops=[[Event(**e) for e in dev] for dev in raw["ops"]],
        modules=[[Event(**e) for e in dev] for dev in raw["modules"]],
        spans=[Event(**e) for e in raw["spans"]],
    )


# --------------------------------------------------------------------------
# Interval arithmetic
# --------------------------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The gaps of the (merged, sorted) ``busy`` intervals inside [lo, hi]."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def attribute(gaps: Sequence[Interval], spans: Sequence[Event]) -> Dict[str, float]:
    """Split each gap among the host spans that overlap it (the innermost,
    that is the shortest, span wins where spans nest); the rest is
    :data:`OTHER`.  Returns nanoseconds per span name."""
    out: Dict[str, float] = collections.defaultdict(float)
    spans = sorted(spans, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    longest = max((e.end_ns - e.start_ns for e in spans), default=0.0)
    for gs, ge in gaps:
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_left(starts, ge)
        cover = sorted(
            (e for e in spans[lo:hi] if e.end_ns > gs), key=lambda e: e.end_ns - e.start_ns
        )
        left = [(gs, ge)]
        for e in cover:
            nxt = []
            for s, t in left:
                a, b = max(s, e.start_ns), min(t, e.end_ns)
                if a < b:
                    out[e.name] += b - a
                    if s < a:
                        nxt.append((s, a))
                    if b < t:
                        nxt.append((b, t))
                else:
                    nxt.append((s, t))
            left = nxt
        rest = total(left)
        if rest > 0:
            out[OTHER] += rest
    return dict(out)


def summarize(ev: TraceEvents) -> Summary:
    """Reduce a traced run to a :class:`Summary`.  The window is the host
    span named :data:`WINDOW`; devices that ran nothing in it are not
    counted as used."""
    windows = [e for e in ev.spans if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    inner = [e for e in ev.spans if e.name != WINDOW]
    busy, idle = [], collections.defaultdict(float)
    op_s: Dict[str, float] = collections.defaultdict(float)
    module_runs: Dict[str, List[float]] = collections.defaultdict(list)
    used = 0
    for dev_ops, dev_mods in zip(ev.ops, ev.modules):
        # busy: the union of the programs that ran (of the operations where
        # the trace has no program line)
        merged = union(clip([(e.start_ns, e.end_ns) for e in dev_mods or dev_ops], lo, hi))
        if not merged:
            continue
        used += 1
        busy.append(total(merged))
        for name, ns in attribute(complement(merged, lo, hi), inner).items():
            idle[name] += ns
        for e in dev_ops:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                op_s[e.name] += t - s
        for e in dev_mods:
            if e.start_ns >= lo and e.end_ns <= hi:
                module_runs[e.name].append((e.end_ns - e.start_ns) * 1e-9)
    if not used:
        raise ValueError("no device operation ran inside the window")
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / used * 1e-9,
        op_s={k: v / used * 1e-9 for k, v in op_s.items()},
        idle_s={k: v / used * 1e-9 for k, v in idle.items()},
        module_runs=dict(module_runs),
    )
