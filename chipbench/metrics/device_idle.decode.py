"""device_idle.decode: share of the traced decode window in which no
operation ran on the device (1 - busy / window, from the device trace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
