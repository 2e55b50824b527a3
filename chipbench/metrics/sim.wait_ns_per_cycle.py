"""sim.wait_ns_per_cycle: host time in the executor's ``scu.wait`` span
(waiting for the device loop after its enqueue) over the window's
simulated cycles, from the program's own spans; comparable with
``sim.device_ns_per_cycle``."""

from chipbench import program_spans


def read(r):
    ns = program_spans.span_ns(r.record, "scu.wait")
    return None if ns is None else ns / sum(r.record["cycles"])
