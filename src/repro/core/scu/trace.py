"""Static micro-op trace IR: core programs as data tables, not generators.

PR 5 measured the engine's ceiling: generator advances and spin replay are
per-micro-op *Python*, shared by every dispatch mode.  This module makes the
program side of that boundary static.  A :class:`TraceProgram` is a per-core
table of ``(op_kind, operands..., repeat)`` rows compiled from the existing
``Compute``/``Mem``/``Poll``/``Scu`` generator programs, with bounded loops
re-rolled into explicit ``LOOP`` rows and an explicit "not traceable ->
generator fallback" escape hatch.

Three consumers:

* :class:`_TraceCursor` -- a drop-in generator replacement (``send`` /
  ``__next__`` / ``StopIteration``) interpreting the table, so every
  existing engine tier (lockstep, fast-forward, fleet, ``SlotFleet.admit``)
  executes traces unchanged and bit-exactly.
* :class:`TraceRunMonitor` -- the compiled fast path.  Because a traced
  cluster's *entire* program state is (pc, repeat, loop counters, R), the
  monitor can digest the full cluster state at loop-head crossings, prove a
  whole-cluster period, and collapse all remaining loop iterations into one
  multiply of the per-period stat deltas -- no per-micro-op Python for the
  jumped span.  This is what moves the 8-core spin-heavy sweeps, which sit
  below the vectorization threshold and spin through shared-state phases
  the quiescent/spin tiers cannot jump.
* :func:`run_traces_xp` -- a self-contained batched array executor for
  traces of TCDM and SCU ops (all but the event FIFO): program counters,
  round-robin arbitration, the SCU's event units, ``elw`` sleep and wake,
  its barrier, mutex and notifier extensions, and phase-5 accounting as
  array kernels (numpy, or one ``jax.jit`` program behind
  :mod:`repro.compat`) with no per-micro-op Python in the loop.

Value semantics: a trace tracks one register ``R`` mirroring the engine's
``resume_value`` -- every granted transaction latches into it, exactly like
the value sent into a generator.  ``BR`` branches compare ``R`` against an
immediate; ``sw`` rows may store ``R + delta`` (latched at fetch time, like
a generator computing from the value it received).  Programs whose control
flow depends on values in ways the IR cannot express are detected by the
sentinel tracer (:func:`trace_generator`) and fall back to generators.

Lifecycle: like :class:`repro.core.scu.faults.FaultPlan`, a
:class:`TraceProgram` is **single-use** -- its cursor owns mutable run
state, and the lowering that produced it consumed one build of the (shared,
mutable) policy state.  Re-running a config means re-lowering or
:meth:`TraceProgram.clone`.
"""

from __future__ import annotations

import functools
import os
from typing import (
    Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Sequence, Set, Tuple,
)

import numpy as np

from repro import obs

from .engine import _COUNTERS, Cluster, Compute, Hierarchy, Mem, Poll, Scu
from .scu_unit import EV

__all__ = [
    "T_COMPUTE",
    "T_MEM",
    "T_POLL",
    "T_SCU",
    "T_JMP",
    "T_BR",
    "T_LOOP",
    "T_HALT",
    "Untraceable",
    "TraceBuilder",
    "TraceProgram",
    "TraceRunMonitor",
    "trace_generator",
    "trace_fragments",
    "lower_or_fallback",
    "run_traces_xp",
    "run_traces_jax",
]

# --------------------------------------------------------------------------
# Row encoding: (op, repeat, a0..a6) int tuples.  Control rows cost zero
# cycles and zero instructions -- branch/loop costs are already folded into
# the Compute cycles the generators charge (see primitives.CostModel).
# --------------------------------------------------------------------------

T_COMPUTE = 0  # a0 = cycles
T_MEM = 1  # a0 = kind code, a1 = addr, a2 = data, a3 = 1 if data is R + a2
T_POLL = 2  # a0 = kind, a1 = addr, a2 = until, a3..a6 = hit_c/miss_c/hit_i/miss_i
T_SCU = 3  # a0 = index into the program's scu op pool (packed: see _SCU_OPS)
T_JMP = 4  # a0 = target row
T_BR = 5  # a0 = immediate, a1 = target row; taken when R == a0
T_LOOP = 6  # a0 = target row, a1 = count of back-jumps before falling through
T_HALT = 7

_MK_LW, _MK_SW, _MK_TAS = 0, 1, 2
_MEM_KIND_CODE = {"lw": _MK_LW, "sw": _MK_SW, "tas": _MK_TAS}
_MEM_KIND_NAME = {v: k for k, v in _MEM_KIND_CODE.items()}

_DATA_OPS = (T_COMPUTE, T_MEM, T_POLL, T_SCU)

# Bound on resolved control rows per fetch: a trace whose control flow
# cycles without reaching a data op is malformed (it would hang the engine).
_CONTROL_GUARD = 100_000


class Untraceable(Exception):
    """The program's op stream depends on values the trace IR cannot carry."""


# --------------------------------------------------------------------------
# Sentinel tracer: prove value-independence by poisoning every resume value
# --------------------------------------------------------------------------


class _ValueUsed(Exception):
    pass


def _poison(*_a, **_k):
    raise _ValueUsed


class _Sentinel:
    """Poison resume value: any observation (comparison, arithmetic, truth
    test, hashing, conversion) raises; storing or ignoring it is allowed."""

    __slots__ = ()

    def __repr__(self) -> str:  # repr stays safe for error messages
        return "<trace sentinel>"


for _name in (
    "__eq__", "__ne__", "__lt__", "__le__", "__gt__", "__ge__", "__hash__",
    "__bool__", "__int__", "__index__", "__float__",
    "__add__", "__radd__", "__sub__", "__rsub__", "__mul__", "__rmul__",
    "__floordiv__", "__rfloordiv__", "__mod__", "__rmod__",
    "__and__", "__rand__", "__or__", "__ror__", "__xor__", "__rxor__",
    "__lshift__", "__rlshift__", "__rshift__", "__rrshift__", "__neg__",
    "__invert__", "__getitem__", "__iter__", "__len__", "__format__",
):
    setattr(_Sentinel, _name, _poison)

_SENTINEL = _Sentinel()


def _check_static(value: Any) -> Any:
    if isinstance(value, _Sentinel):
        raise Untraceable("micro-op embeds a value the program received")
    if isinstance(value, tuple):
        for item in value:
            _check_static(item)
    return value


# --------------------------------------------------------------------------
# Builder
# --------------------------------------------------------------------------


class TraceBuilder:
    """Append-only trace assembler with iteration marks and loop re-rolling.

    Emitters call :meth:`mark` at each iteration boundary; :meth:`build`
    re-rolls runs of identical marked segments (period 1..4, e.g. the
    sense-alternating barrier pair) into one segment plus a ``LOOP`` row --
    required for the table to stay small *and* for program counters to
    recur, which is what the period-collapse monitor keys on.  All branch
    targets must stay inside their own segment (asserted at build time).
    """

    def __init__(self) -> None:
        self._rows: List[Tuple[int, ...]] = []
        self._marks: List[int] = []
        self._scu_pool: List[Scu] = []
        self._scu_index: Dict[Tuple[Any, ...], int] = {}
        self._pinned: set = set()  # rows a label points at (no coalescing)

    # ------------------------------------------------------------- emitters
    def label(self) -> int:
        self._pinned.add(len(self._rows))
        return len(self._rows)

    def mark(self) -> None:
        if not self._marks or self._marks[-1] != len(self._rows):
            self._marks.append(len(self._rows))

    def _push(self, row: Tuple[int, ...]) -> int:
        idx = len(self._rows)
        self._rows.append(row)
        return idx

    def compute(self, cycles: int) -> int:
        cycles = int(_check_static(cycles))
        rows = self._rows
        if rows and len(rows) not in self._pinned:
            last = rows[-1]
            if last[0] == T_COMPUTE and last[2] == cycles and (
                not self._marks or self._marks[-1] != len(rows)
            ):
                rows[-1] = (T_COMPUTE, last[1] + 1, cycles, 0, 0, 0, 0, 0, 0)
                return len(rows) - 1
        return self._push((T_COMPUTE, 1, cycles, 0, 0, 0, 0, 0, 0))

    def mem(self, kind: str, addr: int, data: int = 0) -> int:
        code = _MEM_KIND_CODE[kind]
        return self._push((
            T_MEM, 1, code, int(_check_static(addr)), int(_check_static(data)),
            0, 0, 0, 0,
        ))

    def mem_delta(self, kind: str, addr: int, delta: int) -> int:
        """A store whose data is ``R + delta`` (latched at fetch time)."""
        code = _MEM_KIND_CODE[kind]
        return self._push((T_MEM, 1, code, int(addr), int(delta), 1, 0, 0, 0))

    def poll(
        self,
        kind: str,
        addr: int,
        until: int,
        hit_cycles: int,
        miss_cycles: int,
        hit_instr: int = 1,
        miss_instr: int = 2,
    ) -> int:
        code = _MEM_KIND_CODE[kind]
        return self._push((
            T_POLL, 1, code, int(_check_static(addr)),
            int(_check_static(until)), int(_check_static(hit_cycles)),
            int(_check_static(miss_cycles)), int(_check_static(hit_instr)),
            int(_check_static(miss_instr)),
        ))

    def scu(self, kind: str, addr: Any, data: int = 0) -> int:
        _check_static(addr)
        data = int(_check_static(data))
        key = (kind, addr, data)
        pool_idx = self._scu_index.get(key)
        if pool_idx is None:
            pool_idx = len(self._scu_pool)
            self._scu_pool.append(Scu(kind, addr, data))
            self._scu_index[key] = pool_idx
        return self._push((T_SCU, 1, pool_idx, 0, 0, 0, 0, 0, 0))

    def jmp(self, target: int = -1) -> int:
        return self._push((T_JMP, 1, target, 0, 0, 0, 0, 0, 0))

    def br_eq(self, imm: int, target: int = -1) -> int:
        return self._push((T_BR, 1, int(_check_static(imm)), target, 0, 0, 0, 0, 0))

    def set_target(self, row_idx: int, target: int) -> None:
        row = self._rows[row_idx]
        if row[0] == T_JMP:
            self._rows[row_idx] = (T_JMP, 1, target) + row[3:]
        elif row[0] == T_BR:
            self._rows[row_idx] = (T_BR, 1, row[2], target) + row[4:]
        else:  # pragma: no cover - programming error
            raise TypeError(f"row {row_idx} is not a branch")

    def emit_op(self, op: Any) -> None:
        """Record one engine micro-op object (the sentinel tracer's hook)."""
        t = type(op)
        if t is Compute:
            self.compute(op.cycles)
        elif t is Mem:
            self.mem(op.kind, op.addr, op.data)
        elif t is Poll:
            self.poll(
                op.kind, op.addr, op.until, op.hit_cycles, op.miss_cycles,
                op.hit_instr, op.miss_instr,
            )
        elif t is Scu:
            self.scu(op.kind, op.addr, op.data)
        else:
            raise Untraceable(f"not a static micro-op: {op!r}")

    # --------------------------------------------------------------- build
    @staticmethod
    def _target_of(row: Tuple[int, ...]) -> Optional[int]:
        if row[0] == T_JMP:
            return row[2]
        if row[0] == T_BR:
            return row[3]
        return None

    @staticmethod
    def _retarget(row: Tuple[int, ...], target: int) -> Tuple[int, ...]:
        if row[0] == T_JMP:
            return (T_JMP, row[1], target) + row[3:]
        return (T_BR, row[1], row[2], target) + row[4:]

    def _segments(self) -> List[Tuple[int, int]]:
        bounds = sorted({0, len(self._rows), *self._marks})
        return [
            (bounds[i], bounds[i + 1])
            for i in range(len(bounds) - 1)
            if bounds[i] < bounds[i + 1]
        ]

    def build(
        self,
        *,
        fallback: Optional[Callable[..., Any]] = None,
        label: str = "",
        roll: bool = True,
    ) -> "TraceProgram":
        segments = self._segments()
        # Canonical per-segment keys: rows with branch targets rebased to
        # segment-relative offsets, so identical iterations compare equal
        # wherever they land.  Cross-segment targets are a builder error --
        # re-rolling could not preserve them.
        keys: List[Tuple[Tuple[int, ...], ...]] = []
        for start, end in segments:
            seg = []
            for idx in range(start, end):
                row = self._rows[idx]
                tgt = self._target_of(row)
                if tgt is not None:
                    if tgt < 0:
                        raise ValueError(f"unpatched branch target at row {idx}")
                    # ``tgt == end`` is the fall-through target ("skip to the
                    # next iteration"): after re-rolling it lands on the next
                    # segment, the LOOP row, or the final HALT -- all of which
                    # continue the program exactly like falling off the end.
                    if not (start <= tgt <= end):
                        raise ValueError(
                            f"branch at row {idx} targets row {tgt} outside "
                            f"its iteration segment [{start}, {end}]"
                        )
                    row = self._retarget(row, tgt - start)
                seg.append(row)
            keys.append(tuple(seg))

        out: List[Tuple[int, ...]] = []

        def emit_segment(seg: Tuple[Tuple[int, ...], ...]) -> int:
            base = len(out)
            for row in seg:
                tgt = self._target_of(row)
                if tgt is not None:
                    row = self._retarget(row, tgt + base)
                out.append(row)
            return base

        i = 0
        n_seg = len(keys)
        while i < n_seg:
            rolled = False
            if roll:
                for period in (1, 2, 3, 4):
                    if i + 2 * period > n_seg:
                        break
                    group = keys[i:i + period]
                    reps = 0
                    j = i + period
                    while j + period <= n_seg and keys[j:j + period] == group:
                        reps += 1
                        j += period
                    if reps >= 1:
                        base = len(out)
                        for seg in group:
                            emit_segment(seg)
                        out.append((T_LOOP, 1, base, reps, 0, 0, 0, 0, 0))
                        i += period * (reps + 1)
                        rolled = True
                        break
            if not rolled:
                emit_segment(keys[i])
                i += 1
        out.append((T_HALT, 1, 0, 0, 0, 0, 0, 0, 0))
        return TraceProgram(
            rows=tuple(out),
            scu_pool=tuple(self._scu_pool),
            fallback=fallback,
            label=label,
        )


# --------------------------------------------------------------------------
# The program object and its cursor interpreter
# --------------------------------------------------------------------------


class TraceProgram:
    """A compiled per-core micro-op table (or a declared generator fallback).

    Duck-types as a ``Program``: calling it with ``(cluster, cid)`` yields a
    :class:`_TraceCursor`, which the engine drives exactly like a generator.
    Single-use, mirroring :class:`~repro.core.scu.faults.FaultPlan`: the
    second call raises -- :meth:`clone` (or re-lowering) produces a fresh
    usable instance for retries.
    """

    __slots__ = ("rows", "scu_pool", "fallback", "label", "_consumed", "_ops")

    def __init__(
        self,
        rows: Optional[Tuple[Tuple[int, ...], ...]] = None,
        scu_pool: Tuple[Scu, ...] = (),
        fallback: Optional[Callable[..., Any]] = None,
        label: str = "",
    ):
        if rows is None and fallback is None:
            raise ValueError("TraceProgram needs a row table or a fallback")
        self.rows = rows
        self.scu_pool = scu_pool
        self.fallback = fallback
        self.label = label
        self._consumed = False
        self._ops: Optional[List[Optional[Any]]] = None

    @property
    def is_traced(self) -> bool:
        """True when a static table exists (False: generator fallback)."""
        return self.rows is not None

    @property
    def consumed(self) -> bool:
        return self._consumed

    def clone(self) -> "TraceProgram":
        """A fresh, un-consumed program sharing the immutable tables."""
        return TraceProgram(
            rows=self.rows, scu_pool=self.scu_pool,
            fallback=self.fallback, label=self.label,
        )

    def addresses(self) -> Set[int]:
        """Union of the static TCDM addresses the table touches."""
        addrs: Set[int] = set()
        if self.rows:
            for row in self.rows:
                if row[0] in (T_MEM, T_POLL):
                    addrs.add(row[3])
        return addrs

    def n_data_rows(self) -> int:
        return sum(1 for r in self.rows or () if r[0] in _DATA_OPS)

    def __call__(self, cluster, cid: int):
        if self._consumed:
            raise RuntimeError(
                f"TraceProgram {self.label or cid!r} already consumed: trace "
                "cursors are single-use (like FaultPlan) -- re-lower the "
                "program or clone() a fresh instance for a retried run"
            )
        self._consumed = True
        if self.rows is None:
            return self.fallback(cluster, cid)
        return _TraceCursor(self, cluster, cid)

    def _op_cache(self) -> List[Optional[Any]]:
        """Per-row immutable micro-op objects (delta stores stay None --
        their data depends on R and is built fresh at fetch time)."""
        if self._ops is None:
            ops: List[Optional[Any]] = []
            for row in self.rows:
                kind = row[0]
                if kind == T_COMPUTE:
                    ops.append(Compute(row[2]))
                elif kind == T_MEM:
                    if row[5]:
                        ops.append(None)  # R + delta store
                    else:
                        ops.append(Mem(_MEM_KIND_NAME[row[2]], row[3], row[4]))
                elif kind == T_POLL:
                    ops.append(Poll(
                        _MEM_KIND_NAME[row[2]], row[3], row[4], row[5],
                        row[6], row[7], row[8],
                    ))
                elif kind == T_SCU:
                    ops.append(self.scu_pool[row[2]])
                else:
                    ops.append(None)
            self._ops = ops
        return self._ops


class _TraceCursor:
    """Generator-protocol interpreter over a :class:`TraceProgram` table.

    The engine's ``_advance`` drives it via ``__next__``/``send`` and sees
    only ``Compute``/``Mem``/``Poll``/``Scu`` objects -- control rows are
    resolved internally at zero cycles and zero instructions, so a traced
    program is bit-indistinguishable from the generator it was lowered
    from.  ``R`` mirrors the engine's ``resume_value``; ``crossed`` flags
    backward control transfers for the period-collapse monitor.
    """

    _is_trace_cursor = True

    __slots__ = ("prog", "cid", "pc", "R", "ctrs", "crossed", "_rep", "_ops")

    def __init__(self, prog: TraceProgram, cluster, cid: int):
        self.prog = prog
        self.cid = cid
        self.pc = 0
        self.R: Any = 0
        # armed LOOP rows: row index -> remaining back-jumps
        self.ctrs: Dict[int, int] = {}
        self.crossed = False
        self._rep = 0
        self._ops = prog._op_cache()

    def __iter__(self):
        return self

    def __next__(self):
        return self._fetch()

    def send(self, value):
        self.R = value
        return self._fetch()

    def _fetch(self):
        rows = self.prog.rows
        n = len(rows)
        pc = self.pc
        guard = 0
        while True:
            if pc >= n:
                self.pc = pc
                raise StopIteration
            row = rows[pc]
            kind = row[0]
            if kind <= T_SCU:  # data op
                rep = self._rep if self._rep else row[1]
                rep -= 1
                if rep == 0:
                    self.pc = pc + 1
                    self._rep = 0
                else:
                    self.pc = pc
                    self._rep = rep
                op = self._ops[pc]
                if op is None:  # R + delta store, latched now (fetch time)
                    row_t = rows[pc]
                    op = Mem(_MEM_KIND_NAME[row_t[2]], row_t[3], self.R + row_t[4])
                return op
            if kind == T_JMP:
                tgt = row[2]
                if tgt <= pc:
                    self.crossed = True
                pc = tgt
            elif kind == T_BR:
                if self.R == row[2]:
                    tgt = row[3]
                    if tgt <= pc:
                        self.crossed = True
                    pc = tgt
                else:
                    pc += 1
            elif kind == T_LOOP:
                rem = self.ctrs.get(pc)
                if rem is None:
                    rem = row[3]
                if rem > 0:
                    self.ctrs[pc] = rem - 1
                    self.crossed = True
                    pc = row[2]
                else:
                    self.ctrs.pop(pc, None)
                    pc += 1
            else:  # T_HALT
                self.pc = n
                raise StopIteration
            guard += 1
            if guard > _CONTROL_GUARD:  # pragma: no cover - malformed table
                raise RuntimeError(
                    f"trace {self.prog.label!r}: control flow cycled "
                    f"{_CONTROL_GUARD} rows without reaching a micro-op"
                )


# --------------------------------------------------------------------------
# Lowering helpers: sentinel-trace generators into tables
# --------------------------------------------------------------------------


def trace_generator(tb: TraceBuilder, gen, max_ops: int = 200_000) -> int:
    """Drain ``gen`` into ``tb``, feeding a poisoned sentinel as every
    resume value.  Completing without observing a value *proves* the op
    stream is value-independent, so the linear recording is exact for any
    engine schedule.  Raises :class:`Untraceable` otherwise."""
    n = 0
    try:
        op = next(gen)
    except StopIteration:
        return 0
    except _ValueUsed:
        raise Untraceable("program observed a resume value") from None
    while True:
        n += 1
        if n > max_ops:
            gen.close()
            raise Untraceable(
                f"program exceeded {max_ops} recorded micro-ops (unbounded "
                "or data-dependent loop)"
            )
        tb.emit_op(op)
        try:
            op = gen.send(_SENTINEL)
        except StopIteration:
            return n
        except _ValueUsed:
            raise Untraceable("program observed a resume value") from None


def trace_fragments(
    tb: TraceBuilder,
    fragments: Iterable[Callable[[], Any]],
    max_ops: int = 200_000,
) -> int:
    """Sentinel-trace a sequence of per-iteration generator factories,
    marking each boundary so :meth:`TraceBuilder.build` can re-roll the
    repeated iterations into ``LOOP`` rows."""
    total = 0
    for make in fragments:
        tb.mark()
        total += trace_generator(tb, make(), max_ops=max_ops)
        if total > max_ops:
            raise Untraceable(f"program exceeded {max_ops} recorded micro-ops")
    return total


def lower_or_fallback(
    program: Callable[..., Any],
    cluster,
    cid: int,
    *,
    fragments: Optional[Callable[[], Iterable[Callable[[], Any]]]] = None,
    emit: Optional[Callable[[TraceBuilder], None]] = None,
    label: str = "",
) -> TraceProgram:
    """Compile one core's program into a :class:`TraceProgram`.

    Strategy order: an explicit ``emit`` hook (policy-provided BR-based
    emitter for value-dependent fragments), then ``fragments`` (marked
    per-iteration sentinel tracing), then whole-program sentinel tracing of
    ``program(cluster, cid)``.  An :class:`Untraceable` program becomes a
    declared generator fallback carrying ``program`` -- the escape hatch,
    still a valid ``TraceProgram`` for every dispatch layer."""
    tb = TraceBuilder()
    try:
        if emit is not None:
            emit(tb)
        elif fragments is not None:
            trace_fragments(tb, fragments())
        else:
            trace_generator(tb, program(cluster, cid))
    except Untraceable:
        return TraceProgram(fallback=program, label=label or f"fallback:{cid}")
    return tb.build(label=label or f"trace:{cid}")


# --------------------------------------------------------------------------
# The compiled fast path: whole-cluster period collapse over trace state
# --------------------------------------------------------------------------


def _pending_key(op) -> Optional[Tuple[Any, ...]]:
    if op is None:
        return None
    t = type(op)
    if t is Mem:
        return ("m", op.kind, op.addr, op.data)
    if t is Poll:
        return (
            "p", op.kind, op.addr, op.until, op.hit_cycles, op.miss_cycles,
            op.hit_instr, op.miss_instr,
        )
    if t is Scu:
        return ("s", op.kind, op.addr, op.data)
    return ("c", op.cycles)


class TraceRunMonitor:
    """Collapse repeated whole-cluster periods of a fully-traced run.

    Activated by :meth:`Cluster.load` when every core runs a pure (table,
    no-fallback) :class:`_TraceCursor`, no fault plan is attached and no
    watchdog is armed.  At the top of the fast-forward scheduler loop,
    whenever some cursor crossed a loop head, the monitor digests the
    complete cluster state -- per-core scheduler fields, cursor positions
    and armed loop-counter keys (values excluded: they are the induction
    variables), the TCDM words at every statically-addressed location, all
    round-robin pointers and the SCU's :meth:`state_key`.  A recurring
    digest proves the cluster is periodic; every mechanism between the two
    digests (full steps, quiescent jumps, spin resolution) is deterministic
    given that state, so the remaining iterations collapse into one multiply
    of the per-period cycle/counter deltas, bounded so at least one full
    period of real execution remains before every loop counter expires and
    before ``max_cycles``.
    """

    __slots__ = ("cl", "cursors", "addrs", "seen")

    # runaway guard: aperiodic digests stop accumulating past this
    _SEEN_LIMIT = 4096

    def __init__(self, cluster, cursors: Sequence[_TraceCursor]):
        self.cl = cluster
        self.cursors = list(cursors)
        addrs: Set[int] = set()
        for cur in self.cursors:
            addrs |= cur.prog.addresses()
        self.addrs = sorted(addrs)
        self.seen: Dict[Any, Any] = {}

    def poll(self) -> None:
        crossed = False
        for cur in self.cursors:
            if cur.crossed:
                crossed = True
                cur.crossed = False
        if not crossed:
            return
        key = self._digest()
        prev = self.seen.get(key)
        snap = self._snapshot()
        if prev is None:
            if len(self.seen) >= self._SEEN_LIMIT:
                self.seen.clear()
            self.seen[key] = snap
        elif not self._jump(prev, snap):
            self.seen[key] = snap  # measure the next period from here

    # ------------------------------------------------------------ internals
    def _digest(self) -> Tuple[Any, ...]:
        cl = self.cl
        lanes = []
        for core, cur in zip(cl.cores, self.cursors):
            lanes.append((
                core.state.value, core.busy, core.wake_countdown,
                core.sleep_entry, core.elw_issued, core.resume_value,
                cur.pc, cur._rep, frozenset(cur.ctrs),
                _pending_key(core.pending),
            ))
        tcdm = cl.tcdm
        mem = tuple(tcdm.get(a, 0) for a in self.addrs)
        scu = cl.scu
        return (
            tuple(lanes), mem, cl._rr.tobytes(),
            scu.state_key() if scu is not None else None,
        )

    def _snapshot(self):
        cl = self.cl
        if cl._vec is not None:
            counters = cl._vec.counter_block.copy()
        else:
            counters = np.array(
                [[getattr(c, name) for c in cl.cores] for name in _COUNTERS],
                dtype=np.int64,
            )
        return (
            cl.cycle, counters, cl.stats.bank_conflicts, cl.stats.scu_events,
            [dict(cur.ctrs) for cur in self.cursors],
        )

    def _jump(self, prev, snap) -> bool:
        cl = self.cl
        cyc0, ctr0, bc0, ev0, loops0 = prev
        cyc1, ctr1, bc1, ev1, loops1 = snap
        period = cyc1 - cyc0
        if period <= 0:
            return False
        k: Optional[int] = None
        deltas: List[List[Tuple[int, int, int]]] = []
        for l0, l1 in zip(loops0, loops1):
            lane: List[Tuple[int, int, int]] = []
            for row, rem in l1.items():
                d = l0.get(row, rem) - rem
                if d <= 0:
                    continue  # inner loop, re-armed within the period
                kk = (rem - d) // d
                if kk <= 0:
                    return False
                k = kk if k is None else min(k, kk)
                lane.append((row, rem, d))
            deltas.append(lane)
        cap = (cl.max_cycles - cl.cycle) // period - 2
        k = cap if k is None else min(k, cap)
        if k <= 0:
            return False
        dC = ctr1 - ctr0
        if cl._vec is not None:
            cl._vec.counter_block += k * dC
        else:
            for i, name in enumerate(_COUNTERS):
                for j, core in enumerate(cl.cores):
                    setattr(core, name, getattr(core, name) + k * int(dC[i, j]))
        cl.stats.bank_conflicts += k * (bc1 - bc0)
        cl.stats.scu_events += k * (ev1 - ev0)
        cl.cycle += k * period
        for cur, lane in zip(self.cursors, deltas):
            for row, rem, d in lane:
                cur.ctrs[row] = rem - k * d
        cl.trace_jumps += 1
        cl.trace_jump_cycles += k * period
        self.seen.clear()
        return True


# --------------------------------------------------------------------------
# Batched array executor for traces (numpy, and jax.jit via compat)
# --------------------------------------------------------------------------

# lane states: the engine's CoreState, with STALL_MEM as _X_STALL
_X_ACTIVE, _X_STALL, _X_DONE, _X_SCU, _X_SLEEP, _X_WAKE = 0, 1, 2, 3, 4, 5
_I32_MAX = int(np.iinfo(np.int32).max)

# SCU ops the executor runs: a packed T_SCU row is (T_SCU, 1, op code,
# instance, data).  The key is (kind, address tag, address role); a read's
# role is any.  The event FIFO and every other op stay on the engine.
(_S_BAR_WAIT, _S_MTX_LOCK, _S_NTF_WAIT, _S_EV_WAIT, _S_MTX_UNLOCK, _S_NTF_TRIG,
 _S_MASK, _S_CLEAR, _S_RD_BUF, _S_RD_BAR, _S_RD_MTX) = range(11)
_SCU_OPS = {
    ("elw", "barrier", "wait_all"): _S_BAR_WAIT,
    ("elw", "mutex", "lock"): _S_MTX_LOCK,
    ("elw", "notifier", "wait"): _S_NTF_WAIT,
    ("elw", "event", "wait_any"): _S_EV_WAIT,
    ("write", "mutex", "unlock"): _S_MTX_UNLOCK,
    ("write", "notifier", "trigger"): _S_NTF_TRIG,
    ("write", "mask", "event"): _S_MASK,
    ("write", "buffer", "clear"): _S_CLEAR,
    ("read", "buffer", None): _S_RD_BUF,
    ("read", "barrier", None): _S_RD_BAR,
    ("read", "mutex", None): _S_RD_MTX,
}
_INDEXED = ("barrier", "mutex", "notifier")  # tags whose addr[1] is an instance
_EV_BARRIER_BIT, _EV_MUTEX_BIT = np.int32(1 << EV.BARRIER), np.int32(1 << EV.MUTEX)


def _encode_scu(op: Scu, n: int) -> Tuple[int, int]:
    """``op``'s (code, instance), or a ValueError that names the op."""
    addr = op.addr
    tag = addr[0] if isinstance(addr, tuple) and addr else None
    inst = addr[1] if tag in _INDEXED and len(addr) > 1 else 0
    role = None if op.kind == "read" else (addr[-1] if tag is not None else None)
    code = _SCU_OPS.get((op.kind, tag, role))
    why = None
    if tag == "fifo":
        why = "the event FIFO runs on the engine only"
    elif code is None or not isinstance(inst, int):
        why = "the array executor does not encode it"
    elif inst < 0 or (tag == "notifier" and inst >= 8):
        why = "no such extension instance"
    elif code == _S_RD_BAR and n > 31:
        why = "a barrier status word holds at most 31 cores in int32"
    if why is not None:
        raise ValueError(f"array executor cannot run SCU op {op!r}: {why}")
    return code, inst


def _pack_tables(programs: Sequence[TraceProgram], hierarchy: Optional[Hierarchy] = None,
                 n_banks: int = 0):
    """Flatten trace tables into padded per-lane numpy arrays.  Also returns
    the SCU's shape, ``(barriers, mutexes)`` the tables address, or ``None``
    when no table has an SCU row.  Under a ``hierarchy`` of ``n_banks``
    banks the table gains a tenth column: each memory row's hop class for
    its lane (:meth:`Hierarchy.hop_class`; 0 on every other row)."""
    for p in programs:
        if not p.is_traced:
            raise ValueError("array executor needs pure traced programs")
    n = len(programs)
    length = max(len(p.rows) for p in programs)
    addrs = sorted(set().union(*(p.addresses() for p in programs)))
    addr_idx = {a: i for i, a in enumerate(addrs)}
    tab = np.zeros((n, length, 9), dtype=np.int64)
    tab[:, :, 0] = T_HALT
    n_bar = n_mtx = 0
    has_scu = False
    for lane, p in enumerate(programs):
        for r, row in enumerate(p.rows):
            tab[lane, r] = row
            if row[0] in (T_MEM, T_POLL):
                tab[lane, r, 3] = addr_idx[row[3]]
            elif row[0] == T_SCU:
                op = p.scu_pool[row[2]]
                code, inst = _encode_scu(op, n)
                tab[lane, r, 2:] = (code, inst, op.data, 0, 0, 0, 0)
                has_scu = True
                if code in (_S_BAR_WAIT, _S_RD_BAR):
                    n_bar = max(n_bar, inst + 1)
                elif code in (_S_MTX_LOCK, _S_MTX_UNLOCK, _S_RD_MTX):
                    n_mtx = max(n_mtx, inst + 1)
    scu = (max(n_bar, 1), max(n_mtx, 1)) if has_scu else None
    addrs = np.array(addrs, dtype=np.int64)
    if hierarchy is not None:
        mem = (tab[:, :, 0] == T_MEM) | (tab[:, :, 0] == T_POLL)
        bank = (addrs[np.where(mem, tab[:, :, 3], 0)] >> 2) % n_banks if len(addrs) else 0
        cls = hierarchy.hop_class(np.arange(n)[:, None], bank, n_banks)
        tab = np.concatenate([tab, np.where(mem, cls, 0)[:, :, None]], axis=2)
    return tab, addrs, scu


class _Tables(NamedTuple):
    """The executor's read-only inputs, passed to every phase: the packed
    table once, column-major as ``(10, lanes, rows)`` (the nine packed
    columns, then the TCDM bank of a memory row's address; under a
    hierarchy an eleventh, the row's hop class), the row and lane indices,
    the cycle cap and the static sizes.
    ``xp`` is the array namespace they live in.  ``scu`` is ``(barriers,
    mutexes)``, or ``None`` for tables without SCU rows, which then carry
    no SCU state and run no SCU phase.  ``hop`` is the hierarchy's
    latencies (tile, group, remote), or ``None`` for the flat cluster,
    whose tables carry no response state and run no ``scu.hop`` step."""

    xp: Any
    tab: Any
    rows: Any
    lanes: Any
    max_cycles: Any
    n: int
    n_banks: int
    tas_cycles: int
    scu: Optional[Tuple[int, int]]
    hop: Optional[Tuple[int, int, int]]


def _scoped(name: str):
    """Decorate an executor phase ``fn(t, s)`` to run under
    ``jax.named_scope(name)`` on the jax path: a label on the HLO it
    traces, and nothing else."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(t, s, *args):
            if t.xp is np:
                return fn(t, s, *args)
            import jax

            with jax.named_scope(name):
                return fn(t, s, *args)

        return scoped

    return wrap


# The loop body reads and writes by these one-hot selects, never by an
# indexed gather or scatter (see run_traces_xp).

def _onehot(t, idx, size: int):
    """``(size, lanes)`` bool: row ``i`` marks the lanes whose ``idx`` is ``i``."""
    return idx[None, :] == t.xp.arange(size)[:, None]


def _pick(t, arr, hot):
    """Each lane's ``arr[idx]``, given ``hot = _onehot(t, idx, len(arr))``:
    the lane's one marked entry of the first axis, by a masked reduction."""
    xp = t.xp
    hot = hot.reshape(hot.shape + (1,) * (arr.ndim - 1))
    if arr.dtype == bool:
        return (hot & arr[:, None]).any(axis=0)
    return xp.where(hot, arr[:, None], 0).sum(axis=0, dtype=arr.dtype)


def _row(t, idx):
    """Each lane's table row ``idx``, as ``(10, lanes)``: row ``c`` holds
    column ``c`` (op kind, repeat count, ``a0``..``a6``, bank, and under a
    hierarchy the hop class).  One masked
    sum over the rows fetches every column; ``idx`` must lie in the table."""
    hot = idx[:, None] == t.rows[None, :]
    return t.xp.where(hot[None], t.tab, 0).sum(axis=2, dtype=t.tab.dtype)


def _set(t, arr, hot, old, val, mask):
    """``arr[idx] = val`` for the lanes in ``mask``, given ``hot =
    _onehot(t, idx, len(arr))`` and ``old = _pick(t, arr, hot)``: each entry
    gains the sum of ``val - old`` over the masked lanes that mark it.  That
    sum is the one writer's change, as at most one masked lane marks an
    entry: the writers are the cycle's grants, at most one per bank, and a
    word lies in one bank.  (int32 wraps, so ``old + (val - old)`` is
    ``val`` exactly.)"""
    change = t.xp.where(hot & mask[None, :], val - old, 0)
    return arr + change.sum(axis=1, dtype=arr.dtype)


def _add(t, cnt, row: int, val, mask):
    """Counter row ``row`` += ``val`` for the lanes in ``mask``, as a dense
    ``(counters, lanes)`` increment (``cnt.at[row].add`` is a scatter-add
    on the device even with a static row)."""
    at = mask[None, :] & (np.arange(cnt.shape[0]) == row)[:, None]
    return cnt + t.xp.where(at, val, t.xp.int32(0))


@_scoped("scu.decode")
def _decode_step(t, s, row):
    """Resolve one control row for every lane that needs a fetch.  ``row``
    is what :func:`_issue_data` fetched before it: a lane that it moved on
    fetches no more, so each lane resolved here is still on that row."""
    xp = t.xp
    pc, R, st = s["pc"], s["R"], s["st"]
    row_k, _, r0, r1 = row[:4]
    fetching = s["fetch"] & (st == _X_ACTIVE)
    is_ctrl = fetching & (row_k >= T_JMP)
    # JMP
    jmp = is_ctrl & (row_k == T_JMP)
    new_pc = xp.where(jmp, r0, pc)
    # BR: taken when R == imm
    br = is_ctrl & (row_k == T_BR)
    new_pc = xp.where(br, xp.where(R == r0, r1, pc + 1), new_pc)
    # LOOP: per-(lane, row) counters; -1 = not armed yet
    lp = is_ctrl & (row_k == T_LOOP)
    ctr = s["ctr"]
    at_pc = pc[:, None] == t.rows[None, :]
    cur = xp.where(at_pc, ctr, 0).sum(axis=1, dtype=ctr.dtype)
    cur = xp.where(cur < 0, r1, cur)
    take = lp & (cur > 0)
    new_pc = xp.where(lp, xp.where(cur > 0, r0, pc + 1), new_pc)
    new_ctr_val = xp.where(take, cur - 1, -1)
    ctr = xp.where(at_pc & lp[:, None], new_ctr_val[:, None], ctr)
    # HALT
    halt = is_ctrl & (row_k == T_HALT)
    st = xp.where(halt, _X_DONE, st)
    fin = xp.where(halt & (s["fin"] < 0), s["cycle"], s["fin"])
    s = dict(s)
    s.update(pc=new_pc, st=st, fin=fin, ctr=ctr)
    s["fetch"] = fetching & is_ctrl & ~halt
    return s


@_scoped("scu.issue")
def _issue_data(t, s, row):
    """Lanes whose pc sits on a data row: issue it (instr, busy/stall).
    ``row`` is each lane's row at ``pc``, :func:`_row`."""
    xp = t.xp
    pc, rep = s["pc"], s["rep"]
    fetch = s["fetch"]
    row_k, rn, c0, _, d_imm, d_flag = row[:6]
    data = fetch & (row_k <= T_SCU)
    r = xp.where(rep > 0, rep, rn) - 1
    new_pc = xp.where(data & (r == 0), pc + 1, pc)
    new_rep = xp.where(data, r, rep)
    cnt = _add(t, s["cnt"], 5, 1, data)  # instructions
    # COMPUTE: busy = max(0, c - 1), stay ACTIVE
    comp = data & (row_k == T_COMPUTE)
    busy = xp.where(comp, xp.maximum(c0 - 1, 0), s["busy"])
    # MEM / POLL: pend at the issuing row, STALL; delta stores latch now
    memp = data & ((row_k == T_MEM) | (row_k == T_POLL))
    st = xp.where(memp, _X_STALL, s["st"])
    pend = xp.where(memp, pc, s["pend"])
    if t.scu:
        # SCU: pend at the issuing row, STALL_SCU until the link services it
        scu = data & (row_k == T_SCU)
        st = xp.where(scu, _X_SCU, st)
        pend = xp.where(scu, pc, pend)
    pdata = xp.where(
        data & (row_k == T_MEM),
        xp.where(d_flag == 1, s["R"] + d_imm, d_imm),
        s["pdata"],
    )
    s = dict(s)
    s.update(pc=new_pc, rep=new_rep, busy=busy, st=st, pend=pend,
             pdata=pdata, cnt=cnt)
    s["fetch"] = s["fetch"] & ~data
    return s


@_scoped("scu.grant")
def _grant(t, s):
    """Per-bank round-robin arbitration + transaction effects."""
    xp, lanes, n, tas_cycles = t.xp, t.lanes, t.n, t.tas_cycles
    st, pend, rr = s["st"], s["pend"], s["rr"]
    req = st == _X_STALL
    if t.hop:
        req = req & (s["resp"] == 0)  # not a lane awaiting its response
    row = _row(t, xp.where(req, pend, 0))  # T_MEM / T_POLL rows
    r_kind, _, m_kind, aidx, until, hit_c, miss_c, hit_i, miss_i, bank = row[:10]
    # each bank grants its request of least key, the lanes counted from
    # the bank's round-robin pointer; keys differ, so one lane per bank
    big = n + 1
    kmat = xp.where(req[None, :] & _onehot(t, bank, t.n_banks),
                    (lanes[None, :] - rr[:, None]) % n, big)
    least = kmat.min(axis=1)
    has = least < big
    win = (has[:, None] & (kmat == least[:, None])).any(axis=0)
    conflicts = s["conflicts"] + (req & ~win)  # each lane's lost requests
    rr = xp.where(has, (rr + least + 1) % n, rr)  # past the granted lane
    # effects
    cnt = _add(t, s["cnt"], 6, 1, win)  # tcdm
    at_addr = _onehot(t, aidx, s["tcdm"].shape[0])
    val = _pick(t, s["tcdm"], at_addr)
    is_poll = win & (r_kind == T_POLL)
    is_tas = win & (m_kind == _MK_TAS)
    cnt = _add(t, cnt, 7, 1, is_tas)  # tas
    # tas (Mem or Poll) pays the 3-cycle latency
    base = xp.where(is_tas, tas_cycles - 1, 0)
    # Poll: hit vs miss
    hit = is_poll & (val == until)
    miss = is_poll & (val != until)
    busy = s["busy"]
    busy = xp.where(hit, base + hit_c, busy)
    busy = xp.where(miss, base + miss_c, busy)
    cnt = _add(t, cnt, 5, xp.where(hit, hit_i, miss_i), is_poll)
    R = xp.where(hit, val, s["R"])
    # plain Mem
    is_lw = win & (r_kind == T_MEM) & (m_kind == _MK_LW)
    is_sw = win & (r_kind == T_MEM) & (m_kind == _MK_SW)
    is_mtas = win & (r_kind == T_MEM) & (m_kind == _MK_TAS)
    R = xp.where(is_lw | is_mtas, val, R)
    R = xp.where(is_sw, 0, R)
    # tas (Mem or Poll) writes -1, a store its latched data
    tcdm = _set(t, s["tcdm"], at_addr, val, xp.where(is_tas, -1, s["pdata"]),
                is_tas | is_sw)
    busy = xp.where(is_mtas, tas_cycles - 1, busy)
    # resolution: winners go ACTIVE; polls stay armed on a miss
    done_req = win & ~miss
    pend = xp.where(done_req, -1, pend)
    new_st = xp.where(win, _X_ACTIVE, st)
    s = dict(s)
    s.update(st=new_st, pend=pend, busy=busy, R=R, tcdm=tcdm, rr=rr,
             cnt=cnt, conflicts=conflicts)
    if t.hop:
        s = _hop_grant(t, s, win, is_sw, row[10])
    return s


@_scoped("scu.hop")
def _hop_grant(t, s, win, posted, cls):
    """A hierarchical grant: each granted load, poll or TAS waits for its
    response ``h - 1`` more cycles in ``STALL`` (``resp`` counts them down,
    :func:`_await_response`), ``h`` the hop latency of its class; a posted
    store goes on at once.  Grants outside the lane's tile count in
    ``remote``."""
    xp, (tile, group, remote) = t.xp, t.hop
    lat = xp.where(cls == 0, np.int32(tile), xp.where(cls == 1, np.int32(group), np.int32(remote)))
    held = win & ~posted & (lat > 1)
    s = dict(s)
    s["st"] = xp.where(held, _X_STALL, s["st"])
    s["resp"] = xp.where(held, lat - 1, s["resp"])
    s["remote"] = s["remote"] + (win & (cls != 0))
    return s


@_scoped("scu.hop")
def _await_response(t, s):
    """Phase 1 of a lane awaiting its response: count down; it turns ACTIVE
    as the response arrives and goes on from there next cycle."""
    xp = t.xp
    waiting = s["resp"] > 0
    resp = xp.where(waiting, s["resp"] - 1, s["resp"])
    s = dict(s)
    s["resp"] = resp
    s["st"] = xp.where(waiting & (resp == 0), _X_ACTIVE, s["st"])
    return s


@_scoped("scu.account")
def _account(t, s):
    """Phase 5: the cycle's accounting, and the same for each of the
    ``s["jump"]`` quiet cycles that :func:`_quiet_jump` found after it, in
    which no lane changes state."""
    xp, st = t.xp, s["st"]
    clocked = st != _X_DONE
    act = st == _X_ACTIVE
    stall = st == _X_STALL
    wait = stall
    gated = xp.zeros_like(act)
    if t.scu:
        # a sleeping lane is clock gated; STALL_SCU and WAKE wait clocked
        gated = st == _X_SLEEP
        clocked = clocked & ~gated
        wait = clocked & ~act
    cycles = 1 + s["jump"]
    cnt = s["cnt"]
    # counter rows 0-4: active, comp, wait, gated, stall
    for row, mask in enumerate((clocked, act, wait, gated, stall)):
        cnt = _add(t, cnt, row, cycles, mask)
    s = dict(s)
    del s["jump"]
    s["cnt"] = cnt
    s["cycle"] = s["cycle"] + cycles
    return s


def _or_over(t, x, axis: int):
    """Bitwise OR of ``x`` along ``axis``."""
    if t.xp is np:
        return np.bitwise_or.reduce(x, axis=axis)
    import jax

    return jax.lax.reduce(x, np.int32(0), jax.lax.bitwise_or, (axis,))


def _comparators(s):
    """Which extensions fire this cycle: each barrier every lane arrived at,
    the lanes waiting on each mutex, and each free mutex with a waiter."""
    waiting = s["stamp"] >= 0
    return s["bar"].all(axis=1), waiting, (s["owner"] < 0) & waiting.any(axis=1)


@_scoped("scu.sync")
def _scu_evaluate(t, s):
    """Phase 0: the extension comparators (the engine's ``SCU.evaluate``),
    so events of the previous cycle's triggers are buffered now.  A barrier
    every lane arrived at sends the barrier event to every lane and clears;
    a free mutex with waiters elects the earliest arrival (lowest stamp,
    then lowest lane, as the engine's queue orders them) and sends it the
    mutex event."""
    xp, lanes = t.xp, t.lanes
    bar, stamp, owner = s["bar"], s["stamp"], s["owner"]
    fire, waiting, elect = _comparators(s)
    el = xp.argmin(xp.where(waiting, stamp, _I32_MAX), axis=1).astype(xp.int32)
    chosen = elect[:, None] & (lanes[None, :] == el[:, None])
    s = dict(s)
    s["bar"] = bar & ~fire[:, None]
    s["owner"] = xp.where(elect, el, owner)
    s["stamp"] = xp.where(chosen, -1, stamp)
    s["ev_buf"] = (s["ev_buf"] | xp.where(fire.any(), _EV_BARRIER_BIT, 0)
                   | xp.where(chosen.any(axis=0), _EV_MUTEX_BIT, 0))
    return s


@_scoped("scu.sync")
def _scu_countdown(t, s):
    """Phase 1 of a lane on an ``elw`` (the engine's ``Cluster._issue``): an
    issued one counts down its sleep entry, then its clock is gated (Fig. 4
    left); a granted one counts down its wake, then turns ACTIVE and
    fetches with the rest (its ``busy`` is 0 since it issued the op)."""
    xp = t.xp
    st = s["st"]
    entering = (st == _X_SCU) & s["elw"]
    waking = st == _X_WAKE
    entry = xp.where(entering, s["sleep_entry"] - 1, s["sleep_entry"])
    wake = xp.where(waking, s["wake"] - 1, s["wake"])
    st = xp.where(entering & (entry <= 0), _X_SLEEP, st)
    st = xp.where(waking & (wake <= 0), _X_ACTIVE, st)
    s = dict(s)
    s.update(st=st, sleep_entry=entry, wake=wake)
    return s


@_scoped("scu.sync")
def _scu_service(t, s):
    """Phase 3: every lane's fresh transaction on its private SCU link,
    with the effects the engine's ``Cluster._service_one`` gives them when
    it takes the lanes in order.  A write or read completes (the lane
    resumes next cycle with the read's value, 0 after a write); an ``elw``
    triggers its extension once, arms its sleep entry and waits."""
    xp, lanes = t.xp, t.lanes
    n_bar, n_mtx = t.scu
    st, pend, buf = s["st"], s["pend"], s["ev_buf"]
    fresh = (st == _X_SCU) & ~s["elw"]
    code, inst, data = _row(t, xp.where(fresh, pend, 0))[2:5]
    code = xp.where(fresh, code, -1)
    earlier = lanes[:, None] < lanes[None, :]  # [sender, lane]: sender first
    # notifier triggers: event ``inst`` to the lanes set in ``data`` (0:
    # all).  A lane clearing or reading its own buffer sees the triggers of
    # the lanes before it and not those after it.
    trig = code == _S_NTF_TRIG
    reach = (data[:, None] == 0) | (((data[:, None] >> xp.minimum(lanes, 31)[None, :]) & 1) == 1)
    sent = xp.where(trig[:, None] & reach, (1 << xp.where(trig, inst, 0))[:, None], 0)
    before = _or_over(t, xp.where(earlier, sent, 0), 0)
    after = _or_over(t, xp.where(earlier, 0, sent), 0)
    seen = buf | before
    buf = xp.where(code == _S_CLEAR, (seen & ~data) | after, seen | after)
    # barrier arrivals; a status read sees the arrivals of earlier lanes
    arrive = _onehot(t, inst, n_bar) & (code == _S_BAR_WAIT)[None, :]
    at_bar = _onehot(t, xp.where(code == _S_RD_BAR, inst, 0), n_bar)
    status = _pick(t, s["bar"], at_bar) | (_pick(t, arrive, at_bar) & earlier.T)  # [lane, core]
    # (barrier reads are refused above 31 lanes, so no shift passes 30)
    bar_word = xp.where(status, 1 << xp.minimum(lanes, 30)[None, :], 0).sum(axis=1, dtype=xp.int32)
    # mutexes: a lock joins the queue stamped with this cycle unless the
    # lane waits or owns already; the owner's unlock frees it with its
    # message; a read sees the mutex held unless its owner, an earlier
    # lane, unlocked it
    owner, stamp = s["owner"], s["stamp"]
    on_mtx = _onehot(t, inst, n_mtx)
    join = on_mtx & (code == _S_MTX_LOCK)[None, :] & (stamp < 0) & (owner[:, None] != lanes[None, :])
    rel = on_mtx & (code == _S_MTX_UNLOCK)[None, :] & (owner[:, None] == lanes[None, :])
    freed = rel.any(axis=1)
    at_mtx = _onehot(t, xp.where(code == _S_RD_MTX, inst, 0), n_mtx)
    owned_by = _pick(t, owner, at_mtx)
    held = (owned_by >= 0) & ~(_pick(t, freed, at_mtx) & (owned_by < lanes))
    value = xp.where(code == _S_RD_BUF, seen, 0)
    value = xp.where(code == _S_RD_BAR, bar_word, value)
    value = xp.where(code == _S_RD_MTX, held.astype(xp.int32), value)
    is_elw = fresh & (code <= _S_EV_WAIT)
    done = fresh & ~is_elw
    s = dict(s)
    s.update(
        ev_buf=buf,
        ev_mask=xp.where(code == _S_MASK, data, s["ev_mask"]),
        bar=s["bar"] | arrive,
        stamp=xp.where(join, s["cycle"], stamp),
        owner=xp.where(freed, -1, owner),
        msg=xp.where(freed, xp.where(rel, data[None, :], 0).sum(axis=1, dtype=xp.int32),
                     s["msg"]),
        R=xp.where(done, value, s["R"]),
        st=xp.where(done, _X_ACTIVE, st),
        pend=xp.where(done, -1, pend),
        elw=s["elw"] | is_elw,
        sleep_entry=xp.where(is_elw, Cluster.SLEEP_ENTRY_CYCLES, s["sleep_entry"]),
        cnt=_add(t, s["cnt"], 8, 1, fresh),  # scu
    )
    return s


@_scoped("scu.sync")
def _scu_wake(t, s):
    """Phase 4: every issued ``elw`` polled against its lane's event buffer
    (the engine's ``Cluster._wake_one``).  A lane waits on the event lines
    of its ``elw`` (the engine's ``SCU.elw_would_grant``); a hit clears
    them and answers with the mutex's message for a mutex, else with the
    buffer; the lane wakes in ``WAKE_CYCLES``, one fewer if it never
    slept.  The lines waited on are left in ``s["elw_wait"]`` for
    :func:`_quiet_jump`: a lane still asleep waits on the same ones."""
    xp = t.xp
    st, buf, elw, mask = s["st"], s["ev_buf"], s["elw"], s["ev_mask"]
    code, inst = _row(t, xp.where(elw, s["pend"], 0))[2:4]
    code = xp.where(elw, code, -1)
    wait = xp.where(code == _S_EV_WAIT, xp.where(mask != 0, mask, -1),
                    1 << (EV.NOTIFIER0 + xp.where(code == _S_NTF_WAIT, inst, 0)))
    wait = xp.where(code == _S_BAR_WAIT, _EV_BARRIER_BIT, wait)
    wait = xp.where(code == _S_MTX_LOCK, _EV_MUTEX_BIT, wait)
    hit = elw & ((buf & wait) != 0)
    is_mtx = code == _S_MTX_LOCK
    msg = _pick(t, s["msg"], _onehot(t, xp.where(is_mtx, inst, 0), t.scu[1]))
    value = xp.where(is_mtx, msg, buf)
    woken = Cluster.WAKE_CYCLES - (st == _X_SCU).astype(xp.int32)
    s = dict(s)
    s.update(
        ev_buf=xp.where(hit, buf & ~wait, buf),
        R=xp.where(hit, value, s["R"]),
        pend=xp.where(hit, -1, s["pend"]),
        wake=xp.where(hit, woken, s["wake"]),
        st=xp.where(hit, _X_WAKE, st),
        elw=elw & ~hit,
        elw_wait=wait,
    )
    return s


def _cycle_step(t, s):
    xp = t.xp
    if t.scu:
        # Phase 0: comparators; then phase 1 of the lanes on an elw
        s = _scu_evaluate(t, s)
        s = _scu_countdown(t, s)
    # Phase 1: issue.  busy countdown; armed polls re-enter the queue
    # (one instruction, like the engine's re-issue); everyone else
    # fetches through the table until a data op lands.
    st, busy, pend = s["st"], s["busy"], s["pend"]
    act = st == _X_ACTIVE
    counting = act & (busy > 0)
    advancing = act & (busy <= 0)
    s = dict(s)
    s["busy"] = xp.where(counting, busy - 1, busy)
    reissue = advancing & (pend >= 0)
    s["st"] = xp.where(reissue, _X_STALL, st)
    s["cnt"] = _add(t, s["cnt"], 5, 1, reissue)
    s["fetch"] = advancing & (pend < 0)
    # decode until every fetching lane reached a data op or halted; one
    # row fetch serves an issue and the decode after it
    if xp is np:
        while bool(np.any(s["fetch"])):
            row = _row(t, s["pc"])
            s = _issue_data(t, s, row)
            if not bool(np.any(s["fetch"])):
                break
            s = _decode_step(t, s, row)
    else:
        import jax

        def body(ss):
            row = _row(t, ss["pc"])
            return _decode_step(t, _issue_data(t, ss, row), row)

        s = jax.lax.while_loop(
            lambda ss: ss["fetch"].any(), body, s,
        )
        s = _issue_data(t, s, _row(t, s["pc"]))
    s.pop("fetch", None)
    if t.hop:
        # the lanes awaiting a response: no other phase-1 step touches them
        s = _await_response(t, s)
    # Phase 2: arbitration + grants.  Phases 3 and 4: the SCU's links and
    # elw grants.  Then the jump over the quiet cycles that follow, and
    # phase 5: accounting, for this cycle and those.
    s = _grant(t, s)
    if t.scu:
        s = _scu_service(t, s)
        s = _scu_wake(t, s)
    return _account(t, _quiet_jump(t, s))


@_scoped("scu.jump")
def _quiet_jump(t, s):
    """Find the cycles after this one in which no lane can act (the
    engine's ``Cluster.next_event_bound``), and count down their phase-1
    ``busy`` and ``wake`` in one update (its ``fast_forward``); phase 5
    accounts them as ``s["jump"]``.  A lane stays quiet while it is ACTIVE
    and counts down ``busy``, WAKE and counts down ``wake`` past 1, or
    asleep on an ``elw`` that nothing grants, or awaits a response (under
    a hierarchy) and counts down ``resp`` past 1; a DONE lane bounds
    nothing.
    Any other lane, an extension about to fire, or no bounded lane at all
    gives 0, and the jump never passes the cycle cap.  A sleeper waits on
    the event lines :func:`_scu_wake` left in ``s["elw_wait"]``.  The same
    lane-min sets ``s["live"]``, whether some lane has not halted, which
    the loop condition reads."""
    xp = t.xp
    s = dict(s)
    st = s["st"]
    act = st == _X_ACTIVE
    # unbounded: _I32_MAX for a halted lane, one less for a live sleeper
    bound = xp.where(st == _X_DONE, _I32_MAX, xp.where(act, s["busy"], 0))
    if t.scu:
        waking = st == _X_WAKE
        bound = xp.where(waking, s["wake"] - 1, bound)
        wait = s.pop("elw_wait")
        bound = xp.where((st == _X_SLEEP) & ((s["ev_buf"] & wait) == 0), _I32_MAX - 1, bound)
    if t.hop:
        waiting = s["resp"] > 0
        bound = xp.where(waiting, s["resp"] - 1, bound)
    least = bound.min()
    k = xp.where(least >= _I32_MAX - 1, 0, least)
    if t.scu:
        fire, _, elect = _comparators(s)
        k = xp.where(fire.any() | elect.any(), 0, k)
    k = xp.maximum(xp.minimum(k, t.max_cycles - 1 - s["cycle"]), 0)
    s["jump"] = k
    s["live"] = least < _I32_MAX
    s["busy"] = xp.where(act, s["busy"] - k, s["busy"])
    if t.scu:
        s["wake"] = xp.where(waking, s["wake"] - k, s["wake"])
    if t.hop:
        s["resp"] = xp.where(waiting, s["resp"] - k, s["resp"])
    return s


def _execute(xp, tab, addr_bank, max_cycles, *, n_banks: int, tas_cycles: int,
             scu: Optional[Tuple[int, int]], hop: Optional[Tuple[int, int, int]] = None):
    """Run the packed int32 table ``(lanes, rows, 9)`` from a cleared
    cluster until every lane halts or ``max_cycles`` cycles pass; the final
    state.  ``addr_bank`` is each TCDM address's bank, looked up once here
    for every memory row, so the loop reads it with the row; ``scu`` is
    :func:`_pack_tables`'s SCU shape.  Under a hierarchy ``hop`` is its
    latencies and the table has a tenth column, the hop classes.  Under jax
    this is the body of :func:`_jitted_execute`, so the tables are its
    inputs.  The final state also holds ``out``, the host's whole result
    packed after the loop (:func:`_pack_result`)."""
    n, length, _ = tab.shape
    mem = (tab[:, :, 0] == T_MEM) | (tab[:, :, 0] == T_POLL)
    row_bank = addr_bank[xp.where(mem, tab[:, :, 3], 0)]
    if hop is None:
        cols = [xp.transpose(tab, (2, 0, 1)), row_bank[None]]
    else:
        cols = [xp.transpose(tab[:, :, :9], (2, 0, 1)), row_bank[None], tab[None, :, :, 9]]
    t = _Tables(
        xp, xp.concatenate(cols),
        xp.arange(length), xp.arange(n), max_cycles, n, n_banks, tas_cycles, scu, hop,
    )
    state = {
        "pc": xp.zeros(n, dtype=xp.int32),
        "rep": xp.zeros(n, dtype=xp.int32),
        "R": xp.zeros(n, dtype=xp.int32),
        "st": xp.zeros(n, dtype=xp.int32),
        "busy": xp.zeros(n, dtype=xp.int32),
        "pend": xp.full((n,), -1, dtype=xp.int32),  # row idx of pending op
        "pdata": xp.zeros(n, dtype=xp.int32),  # latched store data
        "tcdm": xp.zeros(addr_bank.shape[0], dtype=xp.int32),
        "rr": xp.zeros(n_banks, dtype=xp.int32),
        "cnt": xp.zeros((len(_COUNTERS), n), dtype=xp.int32),
        "conflicts": xp.zeros(n, dtype=xp.int32),
        "fin": xp.full((n,), -1, dtype=xp.int32),
        "cycle": xp.zeros((), dtype=xp.int32),
        "iters": xp.zeros((), dtype=xp.int32),  # loop iterations
        "live": xp.ones((), dtype=bool),  # some lane has not halted
        "ctr": xp.full((n, length), -1, dtype=xp.int32),
    }
    if scu:
        # the base units' registers, each lane's elw sequencing, and the
        # extensions: barrier arrivals, mutex owner (-1: free), unlock
        # message and each lane's queue stamp (-1: not waiting)
        n_bar, n_mtx = scu
        state.update(
            ev_buf=xp.zeros(n, dtype=xp.int32),
            ev_mask=xp.zeros(n, dtype=xp.int32),
            elw=xp.zeros(n, dtype=bool),
            sleep_entry=xp.zeros(n, dtype=xp.int32),
            wake=xp.zeros(n, dtype=xp.int32),
            bar=xp.zeros((n_bar, n), dtype=bool),
            owner=xp.full((n_mtx,), -1, dtype=xp.int32),
            msg=xp.zeros(n_mtx, dtype=xp.int32),
            stamp=xp.full((n_mtx, n), -1, dtype=xp.int32),
        )
    if hop:
        # each lane's response countdown and its grants outside its tile
        state.update(resp=xp.zeros(n, dtype=xp.int32), remote=xp.zeros(n, dtype=xp.int32))
    if xp is np:
        while state["live"] and state["cycle"] < max_cycles:
            state["fetch"] = np.zeros(n, dtype=bool)
            state = _cycle_step(t, state)
            state["iters"] = state["iters"] + 1
        return dict(state, out=_pack_result(np, state))
    import jax

    def cond(s):
        return s["live"] & (s["cycle"] < max_cycles)

    def body(s):
        obs.count("scu.loop_traces")  # runs only while jax traces the body
        s = dict(s)
        s["fetch"] = xp.zeros(n, dtype=bool)
        s = _cycle_step(t, s)
        s["iters"] = s["iters"] + 1
        return s

    state = jax.lax.while_loop(cond, body, state)
    return dict(state, out=_pack_result(xp, state))


def _pack_result(xp, s):
    """The final state's result as one int32 vector, so the host reads it
    in one transfer: ``cycle``, ``iters`` and ``live`` (0 or 1), the
    counters row by row, then per lane ``conflicts`` and ``fin``, the TCDM
    words, and under a hierarchy per lane ``remote``.  :func:`_unpack_result`
    slices it back."""
    head = xp.stack([xp.asarray(s[k], dtype=xp.int32) for k in ("cycle", "iters", "live")])
    parts = [head, s["cnt"].reshape(-1), s["conflicts"], s["fin"], s["tcdm"]]
    if "remote" in s:
        parts.append(s["remote"])
    # numpy widens some rows to int64; the int32 bound of run_traces_xp
    # holds every count, so both namespaces return the same int32 rows
    return xp.concatenate(parts).astype(xp.int32)


def _unpack_result(out: np.ndarray, n: int, words: int):
    """Slice :func:`_pack_result`'s host copy for ``n`` lanes and ``words``
    TCDM words, in its order: ``cycle``, ``iters`` and ``live`` as ints,
    the counter rows, the conflicts and retire cycles, the TCDM words and
    the remote grants (empty on a flat cluster)."""
    rows = len(_COUNTERS) * n
    cnt, conflicts, fin, tcdm, remote = np.split(out[3:], np.cumsum([rows, n, n, words]))
    return (*map(int, out[:3]), cnt.reshape(len(_COUNTERS), n), conflicts, fin, tcdm, remote)


@functools.cache
def _jitted_execute():
    """:func:`_execute` on ``jax.numpy`` as one ``jax.jit`` program, built
    on first use (jax is optional).  jax keeps one executable per table
    shape, ``n_banks``, ``tas_cycles``, SCU shape and hop latencies;
    ``max_cycles`` is an input."""
    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(_execute, jnp),
                   static_argnames=("n_banks", "tas_cycles", "scu", "hop"))


def run_traces_xp(
    programs: Sequence[TraceProgram],
    *,
    n_banks: int,
    tas_cycles: int = 3,
    max_cycles: int = 10_000_000,
    xp=np,
    hierarchy: Optional[Hierarchy] = None,
):
    """Execute traces as one batched array computation.

    A from-scratch implementation of the engine's cycle semantics (issue,
    per-bank round-robin arbitration, Poll retry shadows, the SCU's
    comparators, private links and elw sleep and wake, phase-5 accounting)
    where every phase is an array kernel over all lanes -- no per-micro-op
    Python in the loop.  SCU rows are the ops of ``_SCU_OPS``; any other,
    the event FIFO's first, raises a ValueError that names it.  ``xp`` selects the array namespace:
    ``numpy`` (default; the no-jax CI path) runs the cycle loop in Python;
    ``jax.numpy`` (what :func:`run_traces_jax` passes) runs it as
    :func:`_jitted_execute`, one compiled program per table shape that jax
    keeps in memory, so a call with a shape seen before neither traces nor
    compiles.  Both run the same phase functions.  Returns a dict with
    ``cycles``, the nine counter rows, ``bank_conflicts``, ``finished_at``
    and the final tcdm contents; parity vs the generator engine is enforced
    by ``tests/test_trace.py``.

    ``hierarchy`` (a :class:`~repro.core.scu.engine.Hierarchy` of one core
    per program) runs the programs on a MemPool-style interconnect, with
    the lockstep engine's semantics: a granted load, poll or TAS answers
    after its hop latency, a store is posted.  Each memory row's hop class
    is packed with the table; the grant starts each held lane's response
    countdown, and phase 1 counts it down (named scope ``scu.hop``).  A
    flat table (``None``) runs the program it ran before hierarchies.

    Consumes the programs (single-use), mirroring the cursor path.

    Each call records host spans (:mod:`repro.obs`): ``scu.run`` around it
    all, and in turn ``scu.pack`` (host tables), ``scu.stage`` (the int32
    table and the address banks; under jax their upload to the device),
    ``scu.loop`` (the loop from a cleared state; under jax the compiled
    call: on a new shape its trace, lowering and compile or cache read,
    then the enqueue), ``scu.wait`` (the host waiting on the device) and
    ``scu.readback``.  The jax loop body counts ``scu.loop_traces`` each
    time it is traced, so once per new shape, and its issue, decode, grant
    and account phases carry the named scopes ``scu.issue``,
    ``scu.decode``, ``scu.grant`` and ``scu.account``, the SCU phase of a
    table with SCU rows ``scu.sync``, and the jump ``scu.jump``.  The
    readback fetches the whole result in one transfer: the compiled
    program packs it after its loop into one int32 vector
    (:func:`_pack_result`), and the host slices its copy by offsets it
    knows from the lane count, the TCDM words and the hierarchy, and sums
    over lanes there.  It counts the job's SCU transactions as
    ``scu.sync_ops``, its loop iterations as ``scu.loop_iterations`` and
    its granted TCDM accesses to banks outside the lane's tile as
    ``scu.remote_accesses`` (0 on a flat cluster).

    One loop iteration is one simulated cycle and a jump (:func:`_quiet_jump`)
    over the quiet cycles after it: while no lane can act -- every live
    lane counts down a compute span or a wake, or sleeps on an ``elw``
    nothing grants, and no extension can fire -- it advances the least of
    those countdowns in one update, as the engine's ``fast_forward`` does,
    and phase 5 accounts them with the cycle.  So a compute span costs one
    iteration, not one per cycle; every count is the same.

    The loop body holds no gather and no scatter: each is a device kernel
    of its own, where a compare-and-select over these small arrays fuses
    with the arithmetic around it.  A phase fetches its lanes' table rows
    with one one-hot select over the rows (:func:`_row`), looks up a word,
    bank pointer or extension by a one-hot reduction (:func:`_pick`), and
    writes the TCDM and the counters densely (:func:`_set`, :func:`_add`).
    A dense write has no conflicts: at most one lane writes a TCDM word in
    a cycle, since each bank grants one lane and a word lies in one bank.
    Its cost grows as lanes x rows a cycle, which is small for the paper's
    clusters.
    """
    with obs.span("scu.run"):
        with obs.span("scu.pack"):
            for p in programs:
                if p._consumed:
                    raise RuntimeError("TraceProgram already consumed (single-use)")
                p._consumed = True
            if hierarchy is not None and hierarchy.n_cores != len(programs):
                raise ValueError(f"{hierarchy} does not describe {len(programs)} lanes")
            tab_np, addrs_np, scu = _pack_tables(programs, hierarchy, n_banks)
            hop = None if hierarchy is None else tuple(hierarchy.latency)
            is_np = xp is np
            # All state is int32 under both namespaces (jax without x64 has no
            # int64).  A counter grows by at most 2 + the largest poll instruction
            # count per cycle, so bounding that by the cycle cap bounds every count.
            per_cycle = 2 + int(tab_np[:, :, 7:9].max(initial=0))
            if max_cycles * per_cycle > _I32_MAX or np.abs(tab_np).max() > _I32_MAX:
                raise ValueError(
                    f"max_cycles={max_cycles} (or a table operand) overflows the "
                    "executor's int32 state"
                )
        with obs.span("scu.stage"):
            tab = tab_np.astype(np.int32)
            addr_bank = ((addrs_np >> 2) % n_banks).astype(np.int32)
            if not len(addr_bank):  # no TCDM word: the grant phase still selects one
                addr_bank = np.zeros(1, dtype=np.int32)
            if not is_np:
                import jax

                tab, addr_bank = jax.device_put((tab, addr_bank))
        with obs.span("scu.loop"):
            run = functools.partial(_execute, np) if is_np else _jitted_execute()
            state = run(tab, addr_bank, np.int32(max_cycles), n_banks=n_banks,
                        tas_cycles=tas_cycles, scu=scu, hop=hop)
        with obs.span("scu.wait"):
            if not is_np:
                jax.block_until_ready(state)
        with obs.span("scu.readback"):
            out = state["out"] if is_np else jax.device_get(state["out"])
            cycles, iters, live, cnt, conflicts, fin, tcdm, remote = _unpack_result(
                out, tab.shape[0], len(addr_bank))
            obs.count("scu.loop_iterations", iters)
            obs.count("scu.remote_accesses", int(remote.sum()))
            if live:
                raise RuntimeError(f"traced run did not finish within {max_cycles} cycles")

            counters = dict(zip(_COUNTERS, cnt))
            obs.count("scu.sync_ops", int(counters["scu_accesses"].sum()))
            return {
                "cycles": cycles,
                "counters": counters,
                "bank_conflicts": int(conflicts.sum()),
                "finished_at": fin,
                "tcdm": dict(zip(addrs_np.tolist(), tcdm.tolist())),
            }


def run_traces_jax(
    programs: Sequence[TraceProgram],
    *,
    n_banks: int,
    tas_cycles: int = 3,
    max_cycles: int = 10_000_000,
    hierarchy: Optional[Hierarchy] = None,
):
    """:func:`run_traces_xp` on ``jax.numpy``: the cycle loop runs as one
    ``jax.jit`` program (an XLA while loop) kept in memory per table shape.
    Requires jax; gate callers on :data:`repro.compat.HAS_JAX`."""
    from repro.compat import HAS_JAX

    if not HAS_JAX:
        raise RuntimeError(
            "jax is unavailable (REPRO_NO_JAX or import failure); "
            "use run_traces_xp with numpy"
        )
    import jax.numpy as jnp

    return run_traces_xp(
        programs, n_banks=n_banks, tas_cycles=tas_cycles,
        max_cycles=max_cycles, xp=jnp, hierarchy=hierarchy,
    )
