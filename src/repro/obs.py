"""In-program spans and counters.

:func:`span` times a block of host code.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so that the block shows
as a host span in any profiler trace taken meanwhile, on the device trace's
clock; and it appends an :class:`Event` on ``time.perf_counter_ns``'s clock
to a bounded in-memory ring.  :func:`count` appends a point event to the
same ring.  :func:`events` reads the ring back over a ``time.perf_counter``
window, which is how the benchmark's readers split a window's host time.

Recording is always on: a span costs a clock read, the annotation and a
``deque`` append, a few microseconds.  Each event names the innermost span
open on its thread when it began (``parent``) and the outermost (``root``),
so the events of one call of an instrumented function share its root's id.
The recorder never imports jax: a profiler trace needs jax loaded, so a
span is annotated only once something else has imported it (never under
``REPRO_NO_JAX``), and records either way.
"""

from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import List, NamedTuple, Optional

__all__ = ["Event", "MAXLEN", "span", "count", "events"]

MAXLEN = 65_536  # events kept; the oldest go first


class Event(NamedTuple):
    name: str
    id: int
    parent: Optional[int]  # innermost span open when the event began
    root: Optional[int]  # outermost open span (a root span's own id)
    start_ns: int
    end_ns: int
    n: Optional[int] = None  # a counter's increment; None for a span


_ring: "collections.deque[Event]" = collections.deque(maxlen=MAXLEN)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List[int]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """``with span(name):`` records the block as one :class:`Event`."""

    __slots__ = ("name", "id", "parent", "root", "start_ns", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        stack = _stack()
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        self.root = stack[0] if stack else self.id
        stack.append(self.id)
        jax = sys.modules.get("jax")
        self._ann = jax.profiler.TraceAnnotation(self.name) if jax is not None else None
        self.start_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        end_ns = time.perf_counter_ns()
        _stack().pop()
        _ring.append(Event(self.name, self.id, self.parent, self.root, self.start_ns, end_ns))


def count(name: str, n: int = 1) -> None:
    """Record ``n`` of ``name`` now, under the innermost open span."""
    stack = _stack()
    t = time.perf_counter_ns()
    _ring.append(Event(name, next(_ids), stack[-1] if stack else None,
                       stack[0] if stack else None, t, t, n))


def events(since_s: Optional[float] = None, until_s: Optional[float] = None) -> List[Event]:
    """The ring's events that lie wholly inside ``[since_s, until_s]``
    (seconds of ``time.perf_counter``; ``None`` leaves that side open), in
    the order they began.  A span still open is not in the ring yet."""
    lo = -1 if since_s is None else since_s * 1e9
    hi = float("inf") if until_s is None else until_s * 1e9
    return sorted((e for e in list(_ring) if e.start_ns >= lo and e.end_ns <= hi),
                  key=lambda e: (e.start_ns, e.id))
