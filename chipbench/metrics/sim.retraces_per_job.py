"""sim.retraces_per_job: ``scu.loop_traces`` counts per job, each a trace
of the executor's loop body by jax (a call that a compiled program in
jax's own cache serves traces none), from the program's own counters."""

from chipbench import program_spans


def read(r):
    return program_spans.count_per_job(r.record, "scu.loop_traces")
