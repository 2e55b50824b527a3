"""The benchmark's harness: finds a cell's files by name and runs it once.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json`` (the path ``BENCHMARK.json`` names):
  the configuration as run; its ``kind`` names the driver and its
  ``reference`` the plain reference in ``chipbench/reference/``;
* ``chipbench/traffic/<traffic>.json``: one traffic mix's parameters, with
  the cell's correctness limits under ``limits``;
* ``chipbench/drivers/<kind>.py``: a ``Driver(run)`` with ``setup()``,
  ``window(seconds, span, traced) -> record`` (``traced()`` wraps the part
  of the window that a traced run traces), ``release()`` and
  ``check(record) -> {"correct", "attempted", "failed", "checks"}``, and
  ``SPANS``, the host span names its window writes;
* ``chipbench/metrics/<metric>.py``: ``read(reading) -> float | None`` for
  every metric but ``setup_s``, which the harness takes itself.

A run: set-up (timed from process start), the window (traced when
``trace`` is on), the peak device memory, the program's state freed, then
the check.  :func:`run_cell` returns the result line's object.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

__all__ = ["Spec", "CellRun", "Reading", "run_cell", "load_module"]


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (metric names hold dots, so they are not
    module names)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name or f"chipbench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir)
        self.data = _read_json(self.root / "BENCHMARK.json")
        self.cells = {c["name"]: c for c in self.data["workloads"]}
        self.configs = {c["name"]: c for c in self.data["configs"]}

    def cell(self, name: str) -> Dict:
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: Dict) -> Dict:
        return _read_json(self.root / self.configs[cell["config"]]["file"])

    def traffic(self, cell: Dict) -> Dict:
        return _read_json(self.bench_dir / "traffic" / f"{cell['traffic']}.json")

    def driver(self, kind: str):
        return load_module(self.bench_dir / "drivers" / f"{kind}.py", f"chipbench_driver_{kind}")

    def metrics(self, cell: Dict, trace: bool) -> List[Dict]:
        """The cell's metrics for this kind of run: end-to-end ones with
        ``trace`` off, per-layer ones with it on."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str) -> Callable:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read


@dataclasses.dataclass
class CellRun:
    name: str
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    rehearse: bool = False


@dataclasses.dataclass
class Reading:
    """What a metric's reader gets."""

    run: CellRun
    record: Dict  # the driver's window record
    trace: Any  # chipbench.trace.Summary, or None with trace off
    device_kind: Optional[str]

    @property
    def peaks(self) -> Dict:
        """This device's row of ``peaks.json``, read when a reader needs it."""
        return peaks_for(self.device_kind)


def peaks_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict:
    table = _read_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def _rehearsal(doc: Dict) -> Dict:
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    out.update(doc.get("rehearsal", {}))
    return out


def make_run(cell: Dict, config: Dict, traffic: Dict, seed: int, rehearse: bool = False) -> CellRun:
    if rehearse:
        config, traffic = _rehearsal(config), _rehearsal(traffic)
    return CellRun(cell["name"], cell, config, traffic, seed, rehearse)


def prepare(spec: Spec, workload: str, seed: int, rehearse: bool = False) -> CellRun:
    cell = spec.cell(workload)
    return make_run(cell, spec.config(cell), spec.traffic(cell), seed, rehearse)


def run_cell(
    spec: Spec,
    run: CellRun,
    seconds: float,
    trace: bool,
    t_start: float,
    driver_factory: Optional[Callable] = None,
) -> Dict:
    """Run one cell once and return the result line's object.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock;
    ``driver_factory`` replaces the configuration's driver (tests plant
    faults through it)."""
    import jax

    from chipbench.trace import WINDOW, capture, summarize

    devices = jax.devices()
    dev = devices[0]
    if driver_factory is None:
        driver_factory = spec.driver(run.config["kind"]).Driver
    driver = driver_factory(run)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    def span(name):
        return jax.profiler.TraceAnnotation(name)

    summary = None
    if trace and not run.rehearse:
        cap: Dict = {}

        @contextlib.contextmanager
        def traced():
            with capture(driver.SPANS) as c:
                with span(WINDOW):
                    yield
            cap.update(c)

        record = driver.window(seconds, span, traced)
        summary = summarize(cap["events"])
    else:
        record = driver.window(seconds, span)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    driver.release()
    verdict = driver.check(record)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {
        "correct": bool(verdict["correct"]),
        "attempted": int(verdict["attempted"]),
        "failed": int(verdict["failed"]),
    }
    if run.rehearse:
        # no timing leaves a rehearsal: counts only, under no metric's name
        out["rehearsal"] = {"steps_or_jobs": int(verdict["attempted"]),
                            "compared": verdict.get("compared")}
    else:
        reading = Reading(run, record, summary, dev.device_kind)
        metrics = {}
        if not trace:
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        for m in spec.metrics(run.cell, trace):
            if m["name"] == "setup_s":
                continue
            value = spec.reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        out["metrics"] = metrics
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["device"] = device
    out["checks"] = verdict["checks"]
    return out


def print_checks(checks: Dict, stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=stream)
