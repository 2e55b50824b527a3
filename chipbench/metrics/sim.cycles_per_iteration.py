"""sim.cycles_per_iteration: the window's simulated cycles over the device
loop's iterations, the ``scu.loop_iterations`` counts the executor records
at readback, from the program's own counters.  1.0 is one iteration a
cycle; more is the cycles its quiescent jumps skip.  A program that counts
no iterations in the window reads ``None``."""

from chipbench import program_spans


def read(r):
    per_job = program_spans.count_per_job(r.record, "scu.loop_iterations")
    if not per_job:
        return None
    return sum(r.record["cycles"]) / (per_job * len(r.record["jobs"]))
