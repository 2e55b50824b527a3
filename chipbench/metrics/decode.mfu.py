"""decode.mfu: the FLOPs the window's tokens need (chipbench.work), over the
window's host-clock length, as a share of the device's peak bf16 rate."""

from chipbench import work


def read(r):
    rec = r.record
    flops = sum(work.decode_step_flops(rec["config"], [int(p)] * rec["batch"])
                for p in rec["positions"])
    return 100.0 * flops / (rec["t1"] - rec["t0"]) / r.peaks["bf16_flops_per_s"]
