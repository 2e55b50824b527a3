"""sim.stage_ms_per_job: mean host time per job in the executor's
``scu.stage`` span (the tables' upload, their column slices, the bank map,
the initial state: eager programs on the device), from the program's own
spans."""

from chipbench import program_spans


def read(r):
    return program_spans.ms_per_job(r.record, "scu.stage")
