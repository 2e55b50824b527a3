"""Plain reference of the shared-L1 cluster running the software
synchronization microbenchmarks (arXiv 2004.06662, Sec. 6.3).

Imports nothing of the system under test.  The cluster is the one the
configuration file states: ``n`` PEs, ``banking_factor * n`` word-
interleaved TCDM banks (bank = (address >> 2) mod banks), one grant per
bank per cycle by round robin, test-and-set taking ``tas_cycles``.  The
programs are written here from the paper's description of each primitive
and the configuration's cost model and variable layout.

Cycle semantics, in order within a cycle:

1. issue -- a PE counting down a compute stays busy; a PE whose poll
   missed re-issues it (one instruction); any other PE fetches its next
   operation (one instruction): a compute of ``c`` cycles keeps it busy for
   ``c - 1`` more cycles, a load, store, test-and-set or poll stalls it on
   its bank; a finished program retires the PE;
2. grant -- each bank grants the stalled requester nearest after its
   round-robin pointer, which then points past the winner; a
   test-and-set (also as a poll) reads the word, writes -1 and keeps the PE
   busy ``tas_cycles - 1`` cycles; a poll compares the word with its
   target: a hit adds its hit cycles and instructions and returns the
   word, a miss adds its miss cycles and instructions and re-arms; a load
   returns the word, a store writes it and returns 0;
3. accounting -- per PE not retired: active; computing (not stalled) or
   waiting and stalled (stalled).

The result is what the executor reports: cycles, the nine per-lane
counters, bank conflicts (requests minus grants, summed over cycles), the
cycle at which each PE retired, and the final value of every word the
programs touch.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

COUNTERS = (
    "active_cycles", "comp_cycles", "wait_cycles", "gated_cycles", "stall_cycles",
    "instructions", "tcdm_accesses", "tas_accesses", "scu_accesses",
)
_ACT, _COMP, _WAIT, _GATED, _STALL, _INSTR, _TCDM, _TAS, _SCU = range(9)
ACTIVE, STALLED, RETIRED = 0, 1, 2

# an operation: ("compute", cycles) | ("lw", addr) | ("sw", addr, data)
# | ("tas", addr) | ("poll", kind, addr, until, hit_c, miss_c, hit_i, miss_i)
Op = Tuple
Program = Iterator[Op]


# --------------------------------------------------------------------------
# Programs
# --------------------------------------------------------------------------


class Layout:
    def __init__(self, config: Dict):
        a = config["layout"]
        self.bar_lock, self.bar_count = a["bar_lock"], a["bar_count"]
        self.bar_sense = a["bar_sense"]
        self.mutex = a["mutex"]
        self.tree_release, self.tree_flag_base = a["tree_release"], a["tree_flag_base"]
        self.cost = config["cost_model"]


def central_barrier(lay: Layout, n: int, cid: int, sense: List[int]) -> Program:
    """Sense-reversal barrier on a counter guarded by a test-and-set lock."""
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
    if count + 1 == n:  # last to arrive: reset, flip the shared sense, unlock
        yield ("sw", lay.bar_count, 0)
        yield ("sw", lay.bar_sense, s)
        yield ("sw", lay.bar_lock, 0)
    else:
        yield ("sw", lay.bar_count, count + 1)
        yield ("sw", lay.bar_lock, 0)
        yield ("poll", "lw", lay.bar_sense, s, 1 + c["load_use"],
               1 + c["load_use"] + c["branch_taken"], 1, 2)
    yield ("compute", c["ret"])


def tree_barrier(lay: Layout, n: int, cid: int, sense: List[int], radix: int) -> Program:
    """Tournament barrier: a PE publishes its arrival in its own flag word at
    the first level where its base-``radix`` digit is not zero; block
    leaders spin on their partners' flags; PE 0 flips the release word."""
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
        yield ("sw", lay.tree_release, s)
    else:
        yield ("poll", "lw", lay.tree_release, s, *spin)
    yield ("compute", c["ret"])


def spin_mutex(lay: Layout, t_crit: int) -> Program:
    """Test-and-set spin lock, ``t_crit`` cycles of work, one store out."""
    c = lay.cost
    yield ("poll", "tas", lay.mutex, 0, 1, 1 + c["branch_taken"], 1, 1)
    if t_crit > 0:
        yield ("compute", t_crit)
    yield ("sw", lay.mutex, 0)


def job_programs(config: Dict, job: Dict, n: int) -> List[Program]:
    """One program per PE: ``iters`` times (a compute of ``sfr`` cycles if
    any, then the job's primitive under the job's policy)."""
    lay = Layout(config)
    pol = config["policies"][job["policy"]]
    sense = [0] * n

    def primitive(cid):
        if job["primitive"] == "mutex":
            if pol["mutex"] != "spin":
                raise ValueError(f"no reference for mutex {pol['mutex']!r}")
            return spin_mutex(lay, job["t_crit"])
        if pol["barrier"] == "central":
            return central_barrier(lay, n, cid, sense)
        if pol["barrier"] == "tree":
            return tree_barrier(lay, n, cid, sense, pol["radix"])
        raise ValueError(f"no reference for barrier {pol['barrier']!r}")

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
    programs: List[Program], *, banks: int, tas_cycles: int, max_cycles: int = 10_000_000,
    grants_per_bank: int = 1,
) -> Dict:
    """Run ``programs`` (one per PE) to completion.  ``grants_per_bank``
    above 1 breaks the one-grant-per-bank guarantee (the control)."""
    n = len(programs)
    st = [ACTIVE] * n
    busy = [0] * n
    pend: List = [None] * n  # the stalled or re-armed memory operation
    value = [None] * n  # what the PE's last operation returned
    cnt = [[0] * n for _ in COUNTERS]
    fin = [-1] * n
    rr = [0] * banks
    mem: Dict[int, int] = {}
    touched = set()
    conflicts = 0
    cycle = 0
    live = n
    while live:
        if cycle >= max_cycles:
            raise RuntimeError(f"programs did not finish within {max_cycles} cycles")
        # skip a span in which every live PE only counts down a compute
        if STALLED not in st and all(s == RETIRED or busy[i] > 0 for i, s in enumerate(st)):
            k = min(busy[i] for i in range(n) if st[i] == ACTIVE)
            for i in range(n):
                if st[i] == ACTIVE:
                    busy[i] -= k
                    cnt[_ACT][i] += k
                    cnt[_COMP][i] += k
            cycle += k
            continue
        # 1. issue
        for i in range(n):
            if st[i] != ACTIVE:
                continue
            if busy[i] > 0:
                busy[i] -= 1
                continue
            if pend[i] is not None:  # a missed poll goes again
                st[i] = STALLED
                cnt[_INSTR][i] += 1
                continue
            try:
                op = programs[i].send(value[i])
            except StopIteration:
                st[i] = RETIRED
                fin[i] = cycle
                live -= 1
                continue
            cnt[_INSTR][i] += 1
            if op[0] == "compute":
                busy[i] = max(op[1] - 1, 0)
            else:
                st[i] = STALLED
                pend[i] = op
                touched.add(op[2] if op[0] == "poll" else op[1])
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
        # 3. accounting
        for i in range(n):
            if st[i] == RETIRED:
                continue
            cnt[_ACT][i] += 1
            if st[i] == ACTIVE:
                cnt[_COMP][i] += 1
            else:
                cnt[_WAIT][i] += 1
                cnt[_STALL][i] += 1
        cycle += 1
    return {
        "cycles": cycle,
        "counters": {name: np.array(cnt[k]) for k, name in enumerate(COUNTERS)},
        "bank_conflicts": conflicts,
        "finished_at": np.array(fin),
        "tcdm": {a: mem.get(a, 0) for a in sorted(touched)},
    }


def job_key(config: Dict, job: Dict) -> Tuple:
    """What determines a job's programs: jobs with equal keys (policies
    that share a primitive's implementation) give equal results."""
    pol = config["policies"][job["policy"]]
    impl = pol["mutex"] if job["primitive"] == "mutex" else (pol["barrier"], pol.get("radix"))
    return (job["primitive"], impl, job.get("t_crit", 0), job.get("sfr", 0), job["iters"])


def run_job(config: Dict, job: Dict, n: int, grants_per_bank: int = 1) -> Dict:
    cl = config["cluster"]
    return simulate(
        job_programs(config, job, n), banks=cl["banking_factor"] * n,
        tas_cycles=cl["tas_cycles"], grants_per_bank=grants_per_bank,
    )


def differences(got: Dict, ref: Dict) -> List[str]:
    """The fields in which an executor's result differs from the
    reference's (an empty list when they agree bit for bit)."""
    out = []
    for key in ("cycles", "bank_conflicts"):
        if int(got[key]) != int(ref[key]):
            out.append(key)
    for name in COUNTERS:
        if not np.array_equal(np.asarray(got["counters"][name]), ref["counters"][name]):
            out.append(name)
    if not np.array_equal(np.asarray(got["finished_at"]), ref["finished_at"]):
        out.append("finished_at")
    if dict(got["tcdm"]) != ref["tcdm"]:
        out.append("tcdm")
    return out

