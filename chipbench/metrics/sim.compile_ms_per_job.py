"""sim.compile_ms_per_job: mean time per job that JAX reports, through
``jax.monitoring``, for tracing, lowering to MLIR and compiling (or reading
the compiled program from the persistent cache) inside ``run_traces_jax``."""


def read(r):
    s = r.record["compile_s"]
    return 1e3 * sum(s) / len(s)
