"""sim.sync_ops_per_job: ``scu.sync_ops`` counts per job, each one job's
SCU transactions (its lanes' summed ``scu_accesses``) counted by the
executor at readback, from the program's own counters.  A program that
counts none in the window reads ``None``."""

from chipbench import program_spans


def read(r):
    try:
        from repro import obs
    except ImportError:  # a program older than its recorder
        return None
    if not any(e.name == "scu.sync_ops" for e in obs.events(r.record["t0"], r.record["t1"])):
        return None
    return program_spans.count_per_job(r.record, "scu.sync_ops")
