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
  pure-TCDM traces: program counters, round-robin arbitration and phase-5
  accounting as array kernels (numpy, or one ``jax.jit`` program behind
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

import os
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro import obs

from .engine import _COUNTERS, Compute, Mem, Poll, Scu

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
T_SCU = 3  # a0 = index into the program's scu op pool
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
# Batched array executor for pure-TCDM traces (numpy, and jax.jit via compat)
# --------------------------------------------------------------------------

_X_ACTIVE, _X_STALL, _X_DONE = 0, 1, 2
_I32_MAX = int(np.iinfo(np.int32).max)


def _pack_tables(programs: Sequence[TraceProgram]):
    """Flatten trace tables into padded per-lane numpy arrays."""
    for p in programs:
        if not p.is_traced:
            raise ValueError("array executor needs pure traced programs")
        for row in p.rows:
            if row[0] == T_SCU:
                raise ValueError(
                    "array executor supports pure-TCDM traces only "
                    "(SCU rows need the full engine)"
                )
    n = len(programs)
    length = max(len(p.rows) for p in programs)
    addrs = sorted(set().union(*(p.addresses() for p in programs)))
    addr_idx = {a: i for i, a in enumerate(addrs)}
    tab = np.zeros((n, length, 9), dtype=np.int64)
    tab[:, :, 0] = T_HALT
    for lane, p in enumerate(programs):
        for r, row in enumerate(p.rows):
            tab[lane, r] = row
            if row[0] in (T_MEM, T_POLL):
                tab[lane, r, 3] = addr_idx[row[3]]
    return tab, np.array(addrs, dtype=np.int64)


def _scoped(name: str, on: bool):
    """Decorate an executor phase to run under ``jax.named_scope(name)``
    when ``on``: a label on the HLO it traces, and nothing else."""

    def wrap(fn):
        if not on:
            return fn
        import jax

        def scoped(s):
            with jax.named_scope(name):
                return fn(s)

        return scoped

    return wrap


def run_traces_xp(
    programs: Sequence[TraceProgram],
    *,
    n_banks: int,
    tas_cycles: int = 3,
    max_cycles: int = 10_000_000,
    xp=np,
):
    """Execute pure-TCDM traces as one batched array computation.

    A from-scratch implementation of the engine's TCDM semantics (issue,
    per-bank round-robin arbitration, Poll retry shadows, phase-5
    accounting) where every phase is an array kernel over all lanes -- no
    per-micro-op Python in the loop.  ``xp`` selects the array namespace:
    ``numpy`` (default; the no-jax CI path) or ``jax.numpy`` inside
    :func:`run_traces_jax`.  Returns a dict with ``cycles``, the nine
    counter rows, ``bank_conflicts``, ``finished_at`` and the final tcdm
    contents; parity vs the generator engine is enforced by
    ``tests/test_trace.py``.

    Consumes the programs (single-use), mirroring the cursor path.

    Each call records host spans (:mod:`repro.obs`): ``scu.run`` around it
    all, and in turn ``scu.pack`` (host tables), ``scu.stage`` (tables and
    initial state in ``xp``), ``scu.loop`` (the loop; under jax its trace,
    lowering, compile or cache read and enqueue), ``scu.wait`` (the host
    waiting on the device) and ``scu.readback``.  The jax loop body counts
    ``scu.loop_traces`` each time it is traced, and its issue, decode, grant
    and account phases carry the named scopes ``scu.issue``, ``scu.decode``,
    ``scu.grant`` and ``scu.account``.
    """
    with obs.span("scu.run"):
        with obs.span("scu.pack"):
            for p in programs:
                if p._consumed:
                    raise RuntimeError("TraceProgram already consumed (single-use)")
                p._consumed = True
            tab_np, addrs_np = _pack_tables(programs)
            n, length, _ = tab_np.shape
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
            tab = xp.asarray(tab_np.astype(np.int32))
            op_k = tab[:, :, 0]
            rep_n = tab[:, :, 1]
            a0, a1, a2 = tab[:, :, 2], tab[:, :, 3], tab[:, :, 4]
            a3, a4, a5, a6 = tab[:, :, 5], tab[:, :, 6], tab[:, :, 7], tab[:, :, 8]
            addr_bank = xp.asarray(((addrs_np >> 2) % n_banks).astype(np.int32))
            lanes = xp.arange(n)

            state = {
                "pc": xp.zeros(n, dtype=xp.int32),
                "rep": xp.zeros(n, dtype=xp.int32),
                "R": xp.zeros(n, dtype=xp.int32),
                "st": xp.zeros(n, dtype=xp.int32),
                "busy": xp.zeros(n, dtype=xp.int32),
                "pend": xp.full((n,), -1, dtype=xp.int32),  # row idx of pending op
                "pdata": xp.zeros(n, dtype=xp.int32),  # latched store data
                "tcdm": xp.zeros(len(addrs_np), dtype=xp.int32),
                "rr": xp.zeros(n_banks, dtype=xp.int32),
                "cnt": xp.zeros((len(_COUNTERS), n), dtype=xp.int32),
                "conflicts": xp.zeros((), dtype=xp.int32),
                "fin": xp.full((n,), -1, dtype=xp.int32),
                "cycle": xp.zeros((), dtype=xp.int32),
            }
            state["ctr"] = xp.full((n, length), -1, dtype=xp.int32)
        with obs.span("scu.loop"):
            def _set(arr, idx, val, mask):
                # masked scatter: only the masked lanes write.  Unmasked lanes may
                # share an index with a writer (an idle lane's pending row defaults
                # to row 0), so they must not write back the old value -- with
                # duplicate indices that could clobber the real write.
                if is_np:
                    out = arr.copy()
                    out[idx[mask]] = np.broadcast_to(val, idx.shape)[mask]
                    return out
                return arr.at[xp.where(mask, idx, arr.shape[0])].set(val, mode="drop")

            def _add(arr, idx, val, mask):
                # per-lane counter bump: arr[idx[lane], lane] += val[lane] where mask
                if is_np:
                    out = arr.copy()
                    v = val if np.isscalar(val) else val[mask]
                    np.add.at(out, (idx[mask], np.asarray(lanes)[mask]), v)
                    return out
                return arr.at[idx, lanes].add(xp.where(mask, val, 0))

            @_scoped("scu.decode", not is_np)
            def decode_step(s):
                """Resolve one control row for every lane that needs a fetch."""
                pc, rep, R, st = s["pc"], s["rep"], s["R"], s["st"]
                row_k = xp.take_along_axis(op_k, pc[:, None], axis=1)[:, 0]
                fetching = s["fetch"] & (st == _X_ACTIVE)
                is_ctrl = fetching & (row_k >= T_JMP)
                r0 = xp.take_along_axis(a0, pc[:, None], axis=1)[:, 0]
                r1 = xp.take_along_axis(a1, pc[:, None], axis=1)[:, 0]
                # JMP
                jmp = is_ctrl & (row_k == T_JMP)
                new_pc = xp.where(jmp, r0, pc)
                # BR: taken when R == imm
                br = is_ctrl & (row_k == T_BR)
                new_pc = xp.where(br, xp.where(R == r0, r1, pc + 1), new_pc)
                # LOOP: per-(lane, row) counters; -1 = not armed yet
                lp = is_ctrl & (row_k == T_LOOP)
                ctr = s["ctr"]
                cur = xp.take_along_axis(ctr, pc[:, None], axis=1)[:, 0]
                cur = xp.where(cur < 0, r1, cur)
                take = lp & (cur > 0)
                new_pc = xp.where(lp, xp.where(cur > 0, r0, pc + 1), new_pc)
                new_ctr_val = xp.where(take, cur - 1, -1)
                if is_np:
                    ctr = ctr.copy()
                    ctr[lanes[lp], pc[lp]] = new_ctr_val[lp]
                else:
                    ctr = ctr.at[lanes, pc].set(
                        xp.where(lp, new_ctr_val, ctr[lanes, pc])
                    )
                # HALT
                halt = is_ctrl & (row_k == T_HALT)
                st = xp.where(halt, _X_DONE, st)
                fin = xp.where(halt & (s["fin"] < 0), s["cycle"], s["fin"])
                s = dict(s)
                s.update(pc=new_pc, st=st, fin=fin, ctr=ctr)
                s["fetch"] = fetching & is_ctrl & ~halt
                return s

            @_scoped("scu.issue", not is_np)
            def issue_data(s):
                """Lanes whose pc sits on a data row: issue it (instr, busy/stall)."""
                pc, rep = s["pc"], s["rep"]
                fetch = s["fetch"]
                row_k = xp.take_along_axis(op_k, pc[:, None], axis=1)[:, 0]
                data = fetch & (row_k <= T_SCU)
                rn = xp.take_along_axis(rep_n, pc[:, None], axis=1)[:, 0]
                r = xp.where(rep > 0, rep, rn) - 1
                new_pc = xp.where(data & (r == 0), pc + 1, pc)
                new_rep = xp.where(data, r, rep)
                cnt = s["cnt"]
                cnt = _add(cnt, 5 * xp.ones(n, dtype=xp.int32), 1, data)  # instructions
                # COMPUTE: busy = max(0, c - 1), stay ACTIVE
                c0 = xp.take_along_axis(a0, pc[:, None], axis=1)[:, 0]
                comp = data & (row_k == T_COMPUTE)
                busy = xp.where(comp, xp.maximum(c0 - 1, 0), s["busy"])
                # MEM / POLL: pend at the issuing row, STALL; delta stores latch now
                memp = data & ((row_k == T_MEM) | (row_k == T_POLL))
                st = xp.where(memp, _X_STALL, s["st"])
                pend = xp.where(memp, pc, s["pend"])
                d_imm = xp.take_along_axis(a2, pc[:, None], axis=1)[:, 0]
                d_flag = xp.take_along_axis(a3, pc[:, None], axis=1)[:, 0]
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

            @_scoped("scu.grant", not is_np)
            def grant(s):
                """Per-bank round-robin arbitration + transaction effects."""
                st, pend = s["st"], s["pend"]
                req = st == _X_STALL
                p_row = xp.where(req, pend, 0)
                r_kind = op_k[lanes, p_row]  # T_MEM / T_POLL
                m_kind = a0[lanes, p_row]
                aidx = a1[lanes, p_row]
                bank = addr_bank[aidx]
                key = (lanes - s["rr"][bank]) % n
                big = n + 1
                kmat = xp.where(
                    req[None, :] & (bank[None, :] == xp.arange(n_banks)[:, None]),
                    key[None, :], big,
                )
                wlane = xp.argmin(kmat, axis=1)
                has = kmat[xp.arange(n_banks), wlane] < big
                win = xp.zeros(n, dtype=bool)
                if is_np:
                    win = win.copy()
                    win[wlane[has]] = True
                else:
                    # scatter-add, not set: banks with no requester still argmin to
                    # lane 0 with has=False, and a duplicate-index set could let
                    # that clobber lane 0's real grant
                    win = xp.zeros(n, dtype=xp.int32).at[wlane].add(
                        has.astype(xp.int32)
                    ) > 0
                conflicts = s["conflicts"] + req.sum() - has.sum()
                rr = _set(s["rr"], xp.arange(n_banks), (wlane + 1) % n, has)
                # effects
                cnt = s["cnt"]
                cnt = _add(cnt, 6 * xp.ones(n, dtype=xp.int32), 1, win)  # tcdm
                val = s["tcdm"][aidx]
                is_poll = win & (r_kind == T_POLL)
                is_tas = win & (m_kind == _MK_TAS)
                cnt = _add(cnt, 7 * xp.ones(n, dtype=xp.int32), 1, is_tas)  # tas
                # tas (Mem or Poll) writes -1 and pays the 3-cycle latency
                tcdm = _set(s["tcdm"], aidx, -1, is_tas)
                base = xp.where(is_tas, tas_cycles - 1, 0)
                # Poll: hit vs miss
                until = a2[lanes, p_row]
                hit_c, miss_c = a3[lanes, p_row], a4[lanes, p_row]
                hit_i, miss_i = a5[lanes, p_row], a6[lanes, p_row]
                hit = is_poll & (val == until)
                miss = is_poll & (val != until)
                busy = s["busy"]
                busy = xp.where(hit, base + hit_c, busy)
                busy = xp.where(miss, base + miss_c, busy)
                cnt = _add(cnt, 5 * xp.ones(n, dtype=xp.int32),
                           xp.where(hit, hit_i, miss_i), is_poll)
                R = xp.where(hit, val, s["R"])
                # plain Mem
                is_lw = win & (r_kind == T_MEM) & (m_kind == _MK_LW)
                is_sw = win & (r_kind == T_MEM) & (m_kind == _MK_SW)
                is_mtas = win & (r_kind == T_MEM) & (m_kind == _MK_TAS)
                R = xp.where(is_lw | is_mtas, val, R)
                R = xp.where(is_sw, 0, R)
                tcdm = _set(tcdm, aidx, s["pdata"], is_sw)
                busy = xp.where(is_mtas, tas_cycles - 1, busy)
                busy = xp.where(is_lw | is_sw, busy, busy)
                # resolution: winners go ACTIVE; polls stay armed on a miss
                done_req = win & ~miss
                pend = xp.where(done_req, -1, pend)
                new_st = xp.where(win, _X_ACTIVE, st)
                s = dict(s)
                s.update(st=new_st, pend=pend, busy=busy, R=R, tcdm=tcdm, rr=rr,
                         cnt=cnt, conflicts=conflicts)
                return s

            @_scoped("scu.account", not is_np)
            def account(s):
                st = s["st"]
                clocked = st != _X_DONE
                act = st == _X_ACTIVE
                stall = st == _X_STALL
                cnt = s["cnt"]
                inc = xp.stack([
                    clocked.astype(xp.int32),  # active
                    act.astype(xp.int32),  # comp
                    stall.astype(xp.int32),  # wait
                    xp.zeros(n, dtype=xp.int32),  # gated
                    stall.astype(xp.int32),  # stall
                ])
                if is_np:
                    cnt = cnt.copy()
                    cnt[:5] += inc
                else:
                    cnt = cnt.at[:5].add(inc)
                s = dict(s)
                s["cnt"] = cnt
                s["cycle"] = s["cycle"] + 1
                return s

            def cycle_step(s):
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
                s["cnt"] = _add(s["cnt"], 5 * xp.ones(n, dtype=xp.int32), 1, reissue)
                s["fetch"] = advancing & (pend < 0)
                # decode until every fetching lane reached a data op or halted
                if is_np:
                    while bool(np.any(s["fetch"])):
                        s = issue_data(s)
                        if not bool(np.any(s["fetch"])):
                            break
                        s = decode_step(s)
                else:
                    import jax

                    def body(ss):
                        ss = issue_data(ss)
                        return decode_step(ss)

                    s = jax.lax.while_loop(
                        lambda ss: ss["fetch"].any(), body, s,
                    )
                    s = issue_data(s)
                s.pop("fetch", None)
                # Phase 2: arbitration + grants.  Phase 5: accounting.
                s = grant(s)
                s = account(s)
                return s

            if is_np:
                while not np.all(state["st"] == _X_DONE) and state["cycle"] < max_cycles:
                    state["fetch"] = np.zeros(n, dtype=bool)
                    state = cycle_step(state)
            else:
                import jax

                def cond(s):
                    return (~(s["st"] == _X_DONE).all()) & (s["cycle"] < max_cycles)

                def body(s):
                    obs.count("scu.loop_traces")  # runs only while jax traces the body
                    s = dict(s)
                    s["fetch"] = xp.zeros(n, dtype=bool)
                    return cycle_step(s)

                state = jax.lax.while_loop(cond, body, state)
        with obs.span("scu.wait"):
            if not is_np:
                jax.block_until_ready(state)
        with obs.span("scu.readback"):
            if not bool((state["st"] == _X_DONE).all()):
                raise RuntimeError(f"traced run did not finish within {max_cycles} cycles")

            counters = {
                name: np.asarray(state["cnt"][i])
                for i, name in enumerate(_COUNTERS)
            }
            return {
                "cycles": int(state["cycle"]),
                "counters": counters,
                "bank_conflicts": int(state["conflicts"]),
                "finished_at": np.asarray(state["fin"]),
                "tcdm": dict(zip(addrs_np.tolist(), np.asarray(state["tcdm"]).tolist())),
            }


def run_traces_jax(
    programs: Sequence[TraceProgram],
    *,
    n_banks: int,
    tas_cycles: int = 3,
    max_cycles: int = 10_000_000,
):
    """The same batched executor as one ``jax.jit`` program (XLA while
    loop).  Requires jax; gate callers on :data:`repro.compat.HAS_JAX`."""
    from repro.compat import HAS_JAX

    if not HAS_JAX:
        raise RuntimeError(
            "jax is unavailable (REPRO_NO_JAX or import failure); "
            "use run_traces_xp with numpy"
        )
    import jax.numpy as jnp

    return run_traces_xp(
        programs, n_banks=n_banks, tas_cycles=tas_cycles,
        max_cycles=max_cycles, xp=jnp,
    )
