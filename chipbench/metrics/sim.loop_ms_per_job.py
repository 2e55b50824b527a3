"""sim.loop_ms_per_job: mean host time per job in the executor's
``scu.loop`` span (the ``while_loop`` call: its trace, lowering, compile
or cache read, and enqueue), from the program's own spans."""

from chipbench import program_spans


def read(r):
    return program_spans.ms_per_job(r.record, "scu.loop")
