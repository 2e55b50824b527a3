"""sim.pack_ms_per_job: mean host time per job in the executor's
``scu.pack`` span (the single-use check, packing the trace tables on the
host, the int32 overflow check), from the program's own spans."""

from chipbench import program_spans


def read(r):
    return program_spans.ms_per_job(r.record, "scu.pack")
