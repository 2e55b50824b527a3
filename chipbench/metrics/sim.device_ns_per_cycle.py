"""sim.device_ns_per_cycle: device busy time inside the traced window (the
window's first pass over the mix) over the simulated cycles of its jobs."""


def read(r):
    if r.trace is None:
        return None
    return r.trace.busy_s * 1e9 / r.record["traced_cycles"]
