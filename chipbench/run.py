"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python -m chipbench.run ...            (the same, as a module)

One process holds the chip.  It refuses any platform but ``tpu`` and fewer
devices than the cell asks for, exiting 2 with no result.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``: each number compared with its limit, which are also
the last lines of standard error).

``--rehearse`` runs the cell at the small sizes its configuration and
traffic files give under ``rehearsal``, on any platform, and prints counts
and checks but no metric: it tries the paths, not the speed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    from chipbench.harness import Spec, prepare, print_checks, run_cell

    spec = Spec()
    run = prepare(spec, args.workload, args.seed, rehearse=args.rehearse)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[chipbench] {args.workload}: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr)
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"[chipbench] no TPU (platform {dev.platform!r}): refusing to run",
                  file=sys.stderr)
            return 2
        if len(devices) < run.cell["chips"]:
            print(f"[chipbench] {args.workload} needs {run.cell['chips']} chips, "
                  f"found {len(devices)}", file=sys.stderr)
            return 2
        from repro.launch.compile_cache import enable_compile_cache

        # every program of the cell goes into the cache, however fast it compiled
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        print(f"[chipbench] compilation cache: {enable_compile_cache()}", file=sys.stderr)

    out = run_cell(spec, run, args.seconds, bool(args.trace), T_START)
    print(json.dumps(out))
    print_checks(out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
