"""decode.hbm_share: the bytes a decode step needs (chipbench.work) over the
step's device time from the trace (its compiled program's runs), as a
share of the device's peak HBM bandwidth."""

import numpy as np

from chipbench import work


def read(r):
    if r.trace is None:
        return None
    rec = r.record
    name = rec["step_module"]
    runs = [
        t for mod, ts in r.trace.module_runs.items()
        if mod == name or mod.startswith((name + "(", name + "."))
        for t in ts
    ]
    if not runs:
        return None
    hf, b = rec["config"], rec["batch"]
    step_bytes = np.mean([work.decode_step_bytes(hf, [int(p)] * b) for p in rec["positions"]])
    return 100.0 * step_bytes / np.mean(runs) / r.peaks["hbm_bytes_per_s"]
