"""Readings for a cell's correctness limit: the program's number on many
seeds and the control's on a few, in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 1-12 \
        --control-seeds 1-3 --seconds <run_seconds> [--out readings.jsonl]

Each seed runs the cell's set-up and window as a benchmark run does (at
the cell's own sizes and load), frees the program's state and takes the
check's numbers (and the window's end-to-end metrics, set-up aside); on a
control seed it also takes the control's numbers on the same window
(``Driver.control``).  One JSON line per seed goes to
standard output and to ``--out``.  A limit lies above the largest program
reading and below the smallest control reading (``PERF.md`` gives both).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def _seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += range(int(a), int(b) + 1)
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from chipbench.harness import Reading, Spec, prepare
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("[calibrate] no TPU: refusing to run", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    spec = Spec()
    controls = set(_seeds(args.control_seeds))
    out = open(args.out, "a") if args.out else None
    for seed in _seeds(args.seeds):
        run = prepare(spec, args.workload, seed)
        driver = spec.driver(run.config["kind"]).Driver(run)
        t = time.perf_counter()
        driver.setup()
        rec = driver.window(args.seconds, lambda name: contextlib.nullcontext())
        driver.release()
        t_check = time.perf_counter()
        verdict = driver.check(rec)
        reading = Reading(run, rec, None, jax.devices()[0].device_kind)
        window = {m["name"]: spec.reader(m["name"])(reading)
                  for m in spec.metrics(run.cell, trace=False) if m["name"] != "setup_s"}
        line = {"workload": args.workload, "seed": seed, "attempted": verdict["attempted"],
                "compared": verdict.get("compared"),
                "program": {k: v["value"] for k, v in verdict["checks"].items()},
                "window_metrics": window,
                "check_s": time.perf_counter() - t_check, "run_s": t_check - t}
        if seed in controls:
            line["control"] = driver.control(rec)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del driver, rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
