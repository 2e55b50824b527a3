"""sim.remote_accesses_per_job: ``scu.remote_accesses`` counts per job, each
one job's granted TCDM accesses to a bank outside the PE's tile, counted by
the executor at readback, from the program's own counters.  A window
without one count per job (a program that counts none) reads ``None``."""

from chipbench import program_spans


def read(r):
    try:
        from repro import obs
    except ImportError:  # a program older than its recorder
        return None
    counts = [e for e in obs.events(r.record["t0"], r.record["t1"])
              if e.name == "scu.remote_accesses"]
    if len(counts) != len(r.record["jobs"]):
        return None
    return program_spans.count_per_job(r.record, "scu.remote_accesses")
