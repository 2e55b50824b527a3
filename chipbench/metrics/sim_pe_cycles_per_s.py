"""sim_pe_cycles_per_s: simulated cycles times PEs, summed over the window's
jobs, over the time from the window's start to the end of its last job
(host clock; the job in flight at the deadline is finished and counted)."""


def read(r):
    rec = r.record
    return sum(rec["cycles"]) * rec["n_pes"] / (rec["t1"] - rec["t0"])
