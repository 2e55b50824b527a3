"""sim.readback_ms_per_job: mean host time per job in the executor's
``scu.readback`` span (the finish check and the result's copy to the
host), from the program's own spans."""

from chipbench import program_spans


def read(r):
    return program_spans.ms_per_job(r.record, "scu.readback")
