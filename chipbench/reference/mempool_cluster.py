"""Plain reference of a MemPool-scale shared-L1 cluster (Riedel et al.,
arXiv 2303.17742) running the software barriers of Table 1 (arXiv
2004.06662, Sec. 6.3).

Imports nothing of the system under test; from ``scu_cluster.py`` beside
it, only the programs (``job_programs``), the counter names, the job key
and the comparison.  The cluster is the one the configuration file
states: ``n`` PEs in tiles of ``cores_per_tile``, ``tiles_per_group``
tiles a group; ``banking_factor * n`` word-interleaved TCDM banks (bank =
(address >> 2) mod banks), the ``banking_factor * cores_per_tile``
consecutive banks of a tile; one grant per bank per cycle by round robin;
test-and-set taking ``tas_cycles``.  The hop latency of PE ``p`` to bank
``b`` is the configuration's ``latency.tile`` when ``b`` lies in ``p``'s
tile, ``latency.group`` when it lies in ``p``'s group, else
``latency.remote``.

Cycle semantics, in order within a cycle:

1. issue -- as in ``scu_cluster.py``; a PE awaiting a response counts
   down, and goes on once it has counted to 0;
2. grant -- as in ``scu_cluster.py`` (a PE awaiting a response does not
   request); then a PE granted a load, a test-and-set or a poll awaits the
   response for its hop latency - 1 more cycles, after which it goes on
   with what the grant gave it (the busy cycles of a poll's hit or miss,
   or the test-and-set's); a granted store is posted and goes on at once;
3. accounting -- per PE not retired: active; computing, or waiting and
   stalled (stalled on a bank or awaiting a response).

The result is what the executor reports (``scu_cluster.py``'s fields).
The control, ``run_job(..., latency=FLAT)``, answers every access in one
cycle: a flat MemPool.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

# job_key and differences are part of this module's API (drivers/sim_sweep.py)
from chipbench.reference.scu_cluster import (  # noqa: F401
    COUNTERS, Program, differences, job_key, job_programs,
)

_ACT, _COMP, _WAIT, _GATED, _STALL, _INSTR, _TCDM, _TAS, _SCU = range(9)
ACTIVE, STALLED, RESPONDING, RETIRED = range(4)
FLAT = {"tile": 1, "group": 1, "remote": 1}


def hop_latency(hierarchy: Dict, banks: int, latency: Dict, pe: int, bank: int) -> int:
    """Cycles from the grant of ``pe``'s access to ``bank`` to its answer."""
    tile = pe // hierarchy["cores_per_tile"]
    bank_tile = bank // (banks // (hierarchy["tiles_per_group"] * hierarchy["groups"]))
    if tile == bank_tile:
        return latency["tile"]
    if tile // hierarchy["tiles_per_group"] == bank_tile // hierarchy["tiles_per_group"]:
        return latency["group"]
    return latency["remote"]


def simulate(
    programs: List[Program], *, banks: int, tas_cycles: int, hierarchy: Dict,
    latency: Dict, max_cycles: int = 10_000_000,
) -> Dict:
    """Run ``programs`` (one per PE) to completion on the hierarchy."""
    n = len(programs)
    st = [ACTIVE] * n
    busy = [0] * n
    wait = [0] * n  # response cycles left
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
        # skip a span in which every live PE only counts down a compute or
        # a response that arrives after the span
        quiet = [busy[i] if st[i] == ACTIVE else wait[i] - 1
                 for i in range(n) if st[i] in (ACTIVE, RESPONDING)]
        if STALLED not in st and quiet and min(quiet) > 0:
            k = min(quiet)
            for i in range(n):
                if st[i] == ACTIVE:
                    busy[i] -= k
                    cnt[_ACT][i] += k
                    cnt[_COMP][i] += k
                elif st[i] == RESPONDING:
                    wait[i] -= k
                    cnt[_ACT][i] += k
                    cnt[_WAIT][i] += k
                    cnt[_STALL][i] += k
            cycle += k
            continue
        # 1. issue
        for i in range(n):
            if st[i] == RESPONDING:
                wait[i] -= 1
                if wait[i] == 0:
                    st[i] = ACTIVE
                continue
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
            i = min(reqs, key=lambda j: (j - rr[b]) % n)
            conflicts += len(reqs) - 1
            rr[b] = (i + 1) % n
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
            else:
                pend[i] = None
                if op[0] == "lw":
                    value[i] = mem.get(op[1], 0)
                elif op[0] == "tas":
                    cnt[_TAS][i] += 1
                    value[i] = mem.get(op[1], 0)
                    mem[op[1]] = -1
                    busy[i] = tas_cycles - 1
                else:  # sw: posted
                    mem[op[1]] = op[2]
                    value[i] = 0
                    continue
            h = hop_latency(hierarchy, banks, latency, i, b)
            if h > 1:
                st[i] = RESPONDING
                wait[i] = h - 1
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


def run_job(config: Dict, job: Dict, n: int, latency: Optional[Dict] = None) -> Dict:
    """``job`` on the configuration's cluster; ``latency`` replaces its hop
    latencies (the control passes ``FLAT``)."""
    cl = config["cluster"]
    hier = cl["hierarchy"]
    return simulate(
        job_programs(config, job, n), banks=cl["banking_factor"] * n,
        tas_cycles=cl["tas_cycles"], hierarchy=hier, latency=latency or hier["latency"],
    )
