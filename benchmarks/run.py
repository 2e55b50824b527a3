"""Benchmark harness: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json PATH]
                                            [--only SECTION[,SECTION...]]

Every section is a :class:`BenchSpec` in the ``BENCHES`` registry: a name
(the ``--only`` handle), a banner, the artifact keys it contributes to the
``--json`` output, and a runner.  The registry is the single source of
truth for the benchmark front-end -- ``--only`` validation, run order and
the JSON schema all derive from it, so a new section registers once and
cannot drift from ``scripts/bench_compare.py`` / ``tests/test_bench_schema``
(which introspect ``artifact_keys()``).

Order: Tier-1 paper reproduction (Table 1, Fig. 5, Table 2), the pipelined
producer-consumer chain and multi-producer work-queue microbenchmarks (SCU
event FIFO), the scaling sweeps (16/32/64/128/256-core clusters; --fast
samples 16/64/128/256), the engine-throughput benchmark (quiescent,
contended, fleet-dispatch and compiled-trace sweeps), the sweep-service
traffic benchmark (continuous batching vs drain baseline on the
slot-recycling fleet), the resilience sweep (deterministic fault
injection x recovery mode: retry, degradation, watchdog release), the
fault-domain chaos sweep (domain fault rate x routing policy on the
multi-fleet pool) and the checkpoint/restore benchmark (live migration vs
restart-reroute, preemptive priority scheduling), then the Tier-2
roofline read-out from the dry-run artifacts.  The
Table-1/Fig-5/chain/work-queue sweeps and their scaling variants dispatch
through the batched fleet engine
(``repro.core.scu.engine.simulate_fleet``); per-config numbers are
bit-exact against sequential runs.  The chip-level barrier timing
benchmark measures 8 host-CPU devices by design, so it runs as a child
process with ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the device count is
locked at jax init); on a TPU host the child therefore never competes for
the chip, and this parent refuses to spawn it once it has touched a JAX
backend itself.  The child's failure propagates to this process's exit
code so CI actually gates on it.

``--only`` restricts the run to a comma-separated subset of sections (see
``BENCHES``; unknown names exit nonzero) for CI and local iteration.
Note a filtered ``--json`` artifact is partial and will not satisfy the
full schema gate in ``scripts/bench_compare.py``.

``--json`` writes the machine-readable key numbers (Table-1/Fig-5 rows,
scaling rows, engine throughput per mode) -- the seed of the performance
trajectory tracked across PRs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
from typing import Callable, Dict, Tuple


def _jsonable(obj):
    """Recursively convert benchmark results to strict-JSON-safe values."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, (int, str, bool)) or obj is None:
        return obj
    return str(obj)


def _table1_json(rows):
    return [
        {
            "primitive": prim,
            "policy": policy,
            "cycles": meas_c,
            "paper_cycles": list(pc) if pc else None,
            "energy_nj": meas_e,
            "paper_energy_nj": list(pe) if pe else None,
        }
        for prim, policy, meas_c, pc, meas_e, pe in rows
    ]


def _table1_scaling_json(rows):
    return [
        {
            "primitive": prim,
            "policy": policy,
            "core_counts": counts,
            "cycles": meas_c,
            "energy_nj": meas_e,
        }
        for prim, policy, counts, meas_c, meas_e in rows
    ]


def _fig5_json(result):
    return {
        variant: {
            "min_sfr_cycles_10pct": r["min_sfr_cycles_10pct"],
            "min_sfr_energy_10pct": r["min_sfr_energy_10pct"],
            "paper_min_sfr_energy": r["paper_min_sfr_energy"],
        }
        for variant, r in result.items()
    }


# --------------------------------------------------------------------------
# The bench registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BenchSpec:
    """One registered benchmark section.

    ``run(args)`` returns ``(artifact_fragment, rc)``: the fragment is
    merged into the ``--json`` artifact; a nonzero rc propagates to the
    process exit code (the section already printed why).
    """

    name: str
    title: str
    json_keys: Tuple[str, ...]
    run: Callable[[argparse.Namespace], Tuple[Dict, int]]


BENCHES: Dict[str, BenchSpec] = {}


def register_bench(name: str, title: str, json_keys: Tuple[str, ...] = ()):
    """Register a section; insertion order is run order."""

    def deco(fn):
        BENCHES[name] = BenchSpec(name=name, title=title, json_keys=json_keys, run=fn)
        return fn

    return deco


def artifact_keys() -> Dict[str, Tuple[str, ...]]:
    """Section name -> the top-level ``--json`` keys it contributes (the
    contract the schema gate checks against)."""
    return {name: spec.json_keys for name, spec in BENCHES.items()}


@register_bench(
    "table1",
    "Tier 1 -- Table 1: primitive costs (cycle-accurate simulator)",
    ("table1",),
)
def _run_table1(args):
    from benchmarks import table1_primitives

    return {"table1": _table1_json(table1_primitives.run())}, 0


@register_bench(
    "fig5",
    "Tier 1 -- Fig. 5: synchronization overhead vs SFR",
    ("fig5",),
)
def _run_fig5(args):
    from benchmarks import fig5_overhead

    return {"fig5": _fig5_json(fig5_overhead.run(dense=not args.fast))}, 0


@register_bench(
    "table2",
    "Tier 1 -- Table 2: application kernels",
    ("table2",),
)
def _run_table2(args):
    from benchmarks import table2_apps

    return {"table2": table2_apps.run(include_slow=not args.fast)}, 0


@register_bench(
    "chain",
    "Tier 1 -- pipelined producer-consumer chains (SCU event FIFO)",
    ("chain",),
)
def _run_chain(args):
    from benchmarks import chain_pipeline

    return {"chain": chain_pipeline.run()}, 0


@register_bench(
    "work_queue",
    "Tier 1 -- multi-producer work queues (mutex vs SCU event FIFO)",
    ("work_queue",),
)
def _run_work_queue(args):
    from benchmarks import work_queue

    return {"work_queue": work_queue.run()}, 0


@register_bench(
    "scaling",
    "Tier 1 -- scaling sweeps (vectorized engine: 16..256 cores)",
    ("table1_scaling", "fig5_scaling", "chain_scaling", "work_queue_scaling"),
)
def _run_scaling(args):
    from benchmarks import chain_pipeline, fig5_overhead, table1_primitives, work_queue

    # --fast (the CI smoke) samples the decades; the full run is dense.
    # The 128/256-core rows are affordable because the contended path
    # runs on the vectorized structure-of-arrays engine core.
    scale_counts = (16, 64, 128, 256) if args.fast else (16, 32, 64, 128, 256)
    frag = {
        "table1_scaling": _table1_scaling_json(
            table1_primitives.run_scaling(core_counts=scale_counts)
        ),
        "fig5_scaling": {
            n: _fig5_json(r)
            for n, r in fig5_overhead.run_scaling(core_counts=scale_counts).items()
        },
        "chain_scaling": chain_pipeline.run_scaling(core_counts=scale_counts),
        "work_queue_scaling": work_queue.run_scaling(core_counts=scale_counts),
    }
    return frag, 0


@register_bench(
    "engine_perf",
    "Engine throughput -- lockstep vs fast-forward vs fleet vs compiled",
    ("engine_perf",),
)
def _run_engine_perf(args):
    from benchmarks import engine_perf

    # reduced sweep under --fast: the lockstep side is the slow half, and
    # the dedicated CI perf-smoke job already runs the full benchmark
    perf = (
        engine_perf.run(sfrs=(1000, 2500), iters=4)
        if args.fast
        else engine_perf.run()
    )
    contended = engine_perf.run_contended(
        core_counts=(8, 64) if args.fast else engine_perf.CONTENDED_CORES
    )
    fleet = engine_perf.run_fleet()
    compiled = engine_perf.run_compiled()
    frag = {
        "engine_perf": {
            "cycles_per_sec": perf["cycles_per_sec"],
            "speedup": perf["speedup"],
            "n_cores": perf["n_cores"],
            "sfrs": perf["sfrs"],
            "contended": {
                "cycles_per_sec": contended["cycles_per_sec"],
                "speedup": contended["speedup"],
                "core_counts": contended["core_counts"],
                "sfrs": contended["sfrs"],
            },
            "fleet": {
                "configs": fleet["configs"],
                "configs_8core": fleet["configs_8core"],
                "wall_s": fleet["wall_s"],
                "speedup": fleet["speedup"],
                "speedup_8core": fleet["speedup_8core"],
            },
            "compiled": {
                "configs": compiled["configs"],
                "iters": compiled["iters"],
                "wall_s": compiled["wall_s"],
                "lower_s": compiled["lower_s"],
                "trace_jumps": compiled["trace_jumps"],
                "trace_jump_cycles": compiled["trace_jump_cycles"],
                "speedup": compiled["speedup"],
                "speedup_incl_lowering": compiled["speedup_incl_lowering"],
            },
        }
    }
    return frag, 0


@register_bench(
    "traffic",
    "Sweep-service traffic -- continuous batching vs drain baseline",
    ("traffic",),
)
def _run_traffic(args):
    from benchmarks import traffic

    # one fixed size under --fast and full: the round-count metrics are
    # deterministic and hard-gated, so the artifact must not vary
    return {"traffic": traffic.run()}, 0


@register_bench(
    "resilience",
    "Resilience -- fault injection x recovery mode on the sweep service",
    ("resilience",),
)
def _run_resilience(args):
    from benchmarks import resilience

    # fixed size under --fast and full: every metric is cycle- or
    # round-counted on a seeded deterministic run and hard-gated
    return {"resilience": resilience.run()}, 0


@register_bench(
    "fault_domains",
    "Fault domains -- chaos sweep x routing policy on the fleet pool",
    ("fault_domains",),
)
def _run_fault_domains(args):
    from benchmarks import fault_domains

    # fixed size under --fast and full: every metric is cycle- or
    # round-counted on a seeded deterministic run and hard-gated
    return {"fault_domains": fault_domains.run()}, 0


@register_bench(
    "preemption",
    "Checkpoint/restore -- live migration + preemptive priority scheduling",
    ("preemption",),
)
def _run_preemption(args):
    from benchmarks import preemption

    # fixed size under --fast and full: every metric is cycle- or
    # round-counted on a seeded deterministic run and hard-gated
    return {"preemption": preemption.run()}, 0


@register_bench(
    "jax_barriers",
    "Tier 2 -- chip-level barrier disciplines (8 host devices)",
    ("jax_barriers_ok",),
)
def _run_jax_barriers(args):
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "benchmarks.run initialized a JAX backend before spawning "
                "jax_barriers; the parent must leave every device to the child"
            )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.jax_barriers"],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    print(r.stdout)
    if r.returncode != 0:
        print("[jax_barriers] failed:", r.stderr[-2000:])
    return {"jax_barriers_ok": r.returncode == 0}, (1 if r.returncode != 0 else 0)


@register_bench(
    "roofline",
    "Tier 2 -- roofline from the multi-pod dry-run artifacts",
    (),
)
def _run_roofline(args):
    from benchmarks import roofline

    roofline.run()
    return {}, 0


# legacy alias: the ordered section-name tuple some callers/tests enumerate
SECTIONS = tuple(BENCHES)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the slow PCA app")
    ap.add_argument(
        "--json", metavar="PATH",
        help="write Table-1/Fig-5/scaling/engine-perf key numbers as JSON",
    )
    ap.add_argument(
        "--only", metavar="SECTION[,SECTION...]",
        help=f"run only the given sections (of: {', '.join(BENCHES)}); "
        "a filtered --json artifact is partial and fails the full schema gate",
    )
    args = ap.parse_args()

    only = None
    if args.only:
        only = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = only - set(BENCHES)
        if unknown or not only:
            print(
                f"[run] unknown section(s): {', '.join(sorted(unknown)) or '(none given)'}; "
                f"valid sections: {', '.join(BENCHES)}",
                file=sys.stderr,
            )
            return 2

    results: Dict = {}
    rc = 0
    for spec in BENCHES.values():
        if only is not None and spec.name not in only:
            continue
        print("\n" + "#" * 72)
        print(f"# {spec.title}")
        print("#" * 72)
        frag, section_rc = spec.run(args)
        results.update(frag)
        rc = rc or section_rc

    if args.json:
        with open(args.json, "w") as f:
            json.dump(_jsonable(results), f, indent=2)
        print(f"\nwrote {args.json}")

    if rc:
        print("\nbenchmarks FAILED (see section output above)", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
