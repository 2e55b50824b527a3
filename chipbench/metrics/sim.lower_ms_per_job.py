"""sim.lower_ms_per_job: mean host time to lower a job to static traces
(``prep_*_bench(compiled=True)``), host clock."""


def read(r):
    s = r.record["lower_s"]
    return 1e3 * sum(s) / len(s)
