"""Plain reference of the shared-L1 cluster with its synchronization and
communication unit (SCU), running Table 1's SCU primitives and the
idle-wait baselines (arXiv 2004.06662, Sec. 4-5 and 6.3).

Imports nothing of the system under test; from ``scu_cluster.py`` beside
it, only the spin mutex, the variable layout, the counter names, the job
key and the comparison.  The cluster is the one the configuration file
states: ``n`` PEs, ``banking_factor * n`` word-interleaved TCDM banks, one
grant per bank per cycle by round robin, test-and-set taking
``tas_cycles``; and the SCU of its ``scu`` block: per PE an event buffer
over 32 event lines (notifier events 0-7, the barrier event 8, the mutex
event 9), one barrier and one mutex, a private link that takes one
transaction per PE per cycle, and the Fig. 4 timing of an ``elw`` (an
event-load-word: the SCU withholds its answer, gating the PE's clock,
until an event the PE waits for is buffered).

Cycle semantics, in order within a cycle:

0. comparators -- a barrier every PE has arrived at sends the barrier
   event to every PE and clears; a free mutex with a queue elects the
   head of the queue (arrival order) and sends it the mutex event;
1. issue -- as in ``scu_cluster.py``; an SCU operation holds the PE on its
   link.  A PE whose ``elw`` was taken counts down ``sleep_entry_cycles``,
   then its clock is gated; a waking PE counts down, then fetches;
2. grant -- as in ``scu_cluster.py``;
3. link -- PEs in order, each holding a fresh transaction: a notifier
   trigger sets its event in the buffers of the PEs in its mask (all for
   0); an unlock by the owner frees the mutex and leaves its message; both
   complete (the PE goes on next cycle).  An ``elw`` arrives at the
   barrier, joins the mutex's queue (unless queued or owner), or does
   nothing for a notifier wait, once, and waits;
4. answer -- each waiting ``elw`` whose event is buffered clears it and
   answers (the mutex's message, else the buffer); the PE wakes in
   ``wake_cycles``, or ``wake_cycles_never_slept`` if its clock was never
   gated;
5. accounting -- per PE not retired: gated while its clock is, else active,
   and computing or waiting (stalled on a bank as well).

The result is what the executor reports (``scu_cluster.py``'s fields).
The control, ``run_job(..., grants_per_bank=2)``, grants two requests per
bank per cycle and wakes a PE one cycle sooner: the pure-SCU jobs never
touch the TCDM, so the grants alone would leave them unchanged.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

# job_key and differences are part of this module's API (drivers/sim_sweep.py)
from chipbench.reference.scu_cluster import (  # noqa: F401
    COUNTERS, Layout, Program, differences, job_key, spin_mutex,
)

_ACT, _COMP, _WAIT, _GATED, _STALL, _INSTR, _TCDM, _TAS, _SCU = range(9)
ACTIVE, STALLED, LINK, SLEEP, WAKE, RETIRED = range(6)
_LINE = {"barrier": 8, "mutex": 9}  # notifier e: line e

# SCU operations: ("elw", "barrier" | "mutex" | "notifier", instance)
# | ("trigger", event, pe mask) | ("unlock", mutex, message)


# --------------------------------------------------------------------------
# Programs
# --------------------------------------------------------------------------


def scu_barrier() -> Program:
    """Address set-up and one elw on the barrier (Sec. 5)."""
    yield ("compute", 1)
    yield ("elw", "barrier", 0)


def scu_mutex(t_crit: int) -> Program:
    """elw on the mutex (the SCU elects one PE), the work, one unlock."""
    yield ("compute", 1)
    yield ("elw", "mutex", 0)
    if t_crit > 0:
        yield ("compute", t_crit)
    yield ("unlock", 0, 0)


def central_idle_barrier(lay: Layout, n: int, cid: int, sense: List[int]) -> Program:
    """Sense-reversal barrier on a counter guarded by a test-and-set lock;
    a waiter sleeps on notifier event 0, which the last arrival sends to
    all, and tests the sense again when woken."""
    c = lay.cost
    sense[cid] ^= 1
    s = sense[cid]
    yield ("compute", c["call"] + c["sense_setup"])
    yield ("poll", "tas", lay.bar_lock, 0, 1, 1 + c["branch_taken"], 1, 1)
    if c["crit_extra"] > 0:
        yield ("compute", c["crit_extra"])
    count = yield ("lw", lay.bar_count)
    yield ("compute", 1 + c["load_use"])
    yield ("compute", 1)
    if count + 1 == n:
        yield ("sw", lay.bar_count, 0)
        yield ("sw", lay.bar_sense, s)
        yield ("sw", lay.bar_lock, 0)
        yield ("trigger", 0, 0)
    else:
        yield ("sw", lay.bar_count, count + 1)
        yield ("sw", lay.bar_lock, 0)
        while True:
            word = yield ("lw", lay.bar_sense)
            yield ("compute", 1 + c["load_use"])
            if word == s:
                break
            yield ("compute", c["mask_setup"])
            yield ("elw", "notifier", 0)
            yield ("compute", 1 + c["branch_taken"])
    yield ("compute", c["ret"])


def tas_idle_mutex(lay: Layout, t_crit: int) -> Program:
    """Test-and-set entry; a loser sleeps on notifier event 1 and, woken,
    loads the lock word before trying the test-and-set again; the exit
    stores 0 and sends event 1 to all."""
    c = lay.cost
    word = yield ("tas", lay.mutex)
    first = True
    while word != 0:
        if first:
            yield ("compute", 1 + c["branch_taken"])
            first = False
        yield ("compute", c["mask_setup"])
        yield ("elw", "notifier", 1)
        again = yield ("lw", lay.mutex)
        yield ("compute", 1 + c["load_use"])
        if again != 0:
            yield ("compute", c["branch_taken"])
            continue
        word = yield ("tas", lay.mutex)
    yield ("compute", 1)
    if t_crit > 0:
        yield ("compute", t_crit)
    yield ("sw", lay.mutex, 0)
    yield ("trigger", 1, 0)


def tree_notifier_barrier(lay: Layout, n: int, cid: int, sense: List[int], radix: int) -> Program:
    """Tournament barrier (``scu_cluster.tree_barrier``) whose release is
    one notifier event 0 from PE 0 to every other PE, which sleep on it."""
    c = lay.cost
    sense[cid] ^= 1
    s = sense[cid]
    spin = (1 + c["load_use"], 1 + c["load_use"] + c["branch_taken"], 1, 2)
    yield ("compute", c["call"] + c["sense_setup"])
    stride, champion = 1, True
    while stride < n:
        if (cid // stride) % radix:
            yield ("compute", 1)
            yield ("sw", lay.tree_flag_base + 4 * cid, s)
            champion = False
            break
        for m in range(1, radix):
            partner = cid + m * stride
            if partner >= n:
                break
            yield ("poll", "lw", lay.tree_flag_base + 4 * partner, s, *spin)
        stride *= radix
    if champion:
        yield ("trigger", 0, ((1 << n) - 1) & ~1)
    else:
        yield ("compute", c["mask_setup"])
        yield ("elw", "notifier", 0)
    yield ("compute", c["ret"])


def job_programs(config: Dict, job: Dict, n: int) -> List[Program]:
    """One program per PE: ``iters`` times (a compute of ``sfr`` cycles if
    any, then the job's primitive under the job's policy)."""
    lay = Layout(config)
    pol = config["policies"][job["policy"]]
    sense = [0] * n

    def primitive(cid):
        if job["primitive"] == "mutex":
            impl = pol["mutex"]
            if impl == "scu_mutex":
                return scu_mutex(job["t_crit"])
            if impl == "tas_idle_wait":
                return tas_idle_mutex(lay, job["t_crit"])
            if impl == "spin":
                return spin_mutex(lay, job["t_crit"])
            raise ValueError(f"no reference for mutex {impl!r}")
        impl = pol["barrier"]
        if impl == "scu_barrier":
            return scu_barrier()
        if impl == "central_idle_wait":
            return central_idle_barrier(lay, n, cid, sense)
        if impl == "tree_notifier_release":
            return tree_notifier_barrier(lay, n, cid, sense, pol["radix"])
        raise ValueError(f"no reference for barrier {impl!r}")

    def program(cid):
        for _ in range(job["iters"]):
            if job.get("sfr", 0) > 0:
                yield ("compute", job["sfr"])
            yield from primitive(cid)

    return [program(cid) for cid in range(n)]


# --------------------------------------------------------------------------
# The cluster
# --------------------------------------------------------------------------


def simulate(
    programs: List[Program], *, banks: int, tas_cycles: int, sleep_entry: int, wake: int,
    wake_never_slept: int, grants_per_bank: int = 1, max_cycles: int = 10_000_000,
) -> Dict:
    """Run ``programs`` (one per PE) to completion."""
    n = len(programs)
    full = (1 << n) - 1
    st = [ACTIVE] * n
    busy = [0] * n
    pend: List = [None] * n  # the operation a PE waits on (a missed poll stays)
    value = [None] * n
    sent = [False] * n  # the elw in flight was taken by the link
    count = [0] * n  # sleep-entry or wake countdown
    cnt = [[0] * n for _ in COUNTERS]
    fin = [-1] * n
    rr = [0] * banks
    mem: Dict[int, int] = {}
    touched = set()
    buf = [0] * n
    arrived = set()
    owner, message, queue = None, 0, []
    conflicts = 0
    cycle = 0
    live = n

    def fetch(i):
        nonlocal live
        try:
            op = programs[i].send(value[i])
        except StopIteration:
            st[i] = RETIRED
            fin[i] = cycle
            live -= 1
            return
        cnt[_INSTR][i] += 1
        if op[0] == "compute":
            busy[i] = max(op[1] - 1, 0)
        elif op[0] in ("elw", "trigger", "unlock"):
            st[i] = LINK
            pend[i] = op
        else:
            st[i] = STALLED
            pend[i] = op
            touched.add(op[2] if op[0] == "poll" else op[1])

    while live:
        if cycle >= max_cycles:
            raise RuntimeError(f"programs did not finish within {max_cycles} cycles")
        # skip a span in which every live PE only counts down a compute
        if all(s == RETIRED or (s == ACTIVE and busy[i] > 0) for i, s in enumerate(st)) \
                and len(arrived) < n and (owner is not None or not queue):
            k = min(busy[i] for i in range(n) if st[i] == ACTIVE)
            for i in range(n):
                if st[i] == ACTIVE:
                    busy[i] -= k
                    cnt[_ACT][i] += k
                    cnt[_COMP][i] += k
            cycle += k
            continue
        # 0. comparators
        if len(arrived) == n:
            buf = [b | 1 << _LINE["barrier"] for b in buf]
            arrived = set()
        if owner is None and queue:
            owner = queue.pop(0)
            buf[owner] |= 1 << _LINE["mutex"]
        # 1. issue
        for i in range(n):
            if st[i] == ACTIVE:
                if busy[i] > 0:
                    busy[i] -= 1
                elif pend[i] is not None:  # a missed poll goes again
                    st[i] = STALLED
                    cnt[_INSTR][i] += 1
                else:
                    fetch(i)
            elif st[i] == WAKE:
                count[i] -= 1
                if count[i] <= 0:
                    st[i] = ACTIVE
                    fetch(i)
            elif st[i] == LINK and sent[i]:
                count[i] -= 1
                if count[i] <= 0:
                    st[i] = SLEEP
        # 2. grant
        by_bank: Dict[int, List[int]] = {}
        for i in range(n):
            if st[i] == STALLED:
                op = pend[i]
                addr = op[2] if op[0] == "poll" else op[1]
                by_bank.setdefault((addr >> 2) % banks, []).append(i)
        for b, reqs in by_bank.items():
            order = sorted(reqs, key=lambda i: (i - rr[b]) % n)
            won = order[:grants_per_bank]
            conflicts += len(reqs) - len(won)
            rr[b] = (won[0] + 1) % n
            for i in won:
                op = pend[i]
                cnt[_TCDM][i] += 1
                st[i] = ACTIVE
                if op[0] == "poll":
                    _, kind, addr, until, hit_c, miss_c, hit_i, miss_i = op
                    word = mem.get(addr, 0)
                    base = 0
                    if kind == "tas":
                        cnt[_TAS][i] += 1
                        mem[addr] = -1
                        base = tas_cycles - 1
                    if word == until:
                        busy[i] = base + hit_c
                        cnt[_INSTR][i] += hit_i
                        value[i] = word
                        pend[i] = None
                    else:
                        busy[i] = base + miss_c
                        cnt[_INSTR][i] += miss_i
                    continue
                pend[i] = None
                if op[0] == "lw":
                    value[i] = mem.get(op[1], 0)
                elif op[0] == "tas":
                    cnt[_TAS][i] += 1
                    value[i] = mem.get(op[1], 0)
                    mem[op[1]] = -1
                    busy[i] = tas_cycles - 1
                else:  # sw
                    mem[op[1]] = op[2]
                    value[i] = 0
        # 3. link
        for i in range(n):
            if st[i] != LINK or sent[i]:
                continue
            op = pend[i]
            cnt[_SCU][i] += 1
            if op[0] == "trigger":
                targets = op[2] or full
                for j in range(n):
                    if targets >> j & 1:
                        buf[j] |= 1 << op[1]
            elif op[0] == "unlock":
                if owner == i:
                    owner, message = None, op[2]
            else:  # elw
                if op[1] == "barrier":
                    arrived.add(i)
                elif op[1] == "mutex" and i not in queue and owner != i:
                    queue.append(i)
                sent[i] = True
                count[i] = sleep_entry
                continue
            st[i] = ACTIVE
            pend[i] = None
            value[i] = 0
        # 4. answer
        for i in range(n):
            if not sent[i]:
                continue
            _, ext, inst = pend[i]
            bit = 1 << _LINE.get(ext, inst)
            if buf[i] & bit:
                value[i] = message if ext == "mutex" else buf[i]
                buf[i] &= ~bit
                sent[i] = False
                pend[i] = None
                count[i] = wake_never_slept if st[i] == LINK else wake
                st[i] = WAKE
        # 5. accounting
        for i in range(n):
            if st[i] == RETIRED:
                continue
            if st[i] == SLEEP:
                cnt[_GATED][i] += 1
                continue
            cnt[_ACT][i] += 1
            if st[i] == ACTIVE:
                cnt[_COMP][i] += 1
            else:
                cnt[_WAIT][i] += 1
                if st[i] == STALLED:
                    cnt[_STALL][i] += 1
        cycle += 1
    return {
        "cycles": cycle,
        "counters": {name: np.array(cnt[k]) for k, name in enumerate(COUNTERS)},
        "bank_conflicts": conflicts,
        "finished_at": np.array(fin),
        "tcdm": {a: mem.get(a, 0) for a in sorted(touched)},
    }


def run_job(config: Dict, job: Dict, n: int, grants_per_bank: int = 1) -> Dict:
    """The job's result; ``grants_per_bank`` above 1 is the control (see
    the module's docstring), which also wakes a PE one cycle sooner."""
    cl, scu = config["cluster"], config["scu"]
    shorter = 1 if grants_per_bank > 1 else 0
    return simulate(
        job_programs(config, job, n), banks=cl["banking_factor"] * n,
        tas_cycles=cl["tas_cycles"], sleep_entry=scu["sleep_entry_cycles"],
        wake=scu["wake_cycles"] - shorter,
        wake_never_slept=scu["wake_cycles_never_slept"] - shorter, grants_per_bank=grants_per_bank,
    )
