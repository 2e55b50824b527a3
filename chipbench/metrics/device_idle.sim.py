"""device_idle.sim: share of the traced sweep window (its first pass) in which no operation
ran on the device (1 - busy / window, from the device trace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
