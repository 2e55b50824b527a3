"""The harness on the CPU: discovery of new files, refusal without a TPU,
the result line's keys, and a checkout that holds only the benchmark."""

import contextlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness
from chipbench.harness import BENCH_DIR, ROOT, Reading, Spec, prepare, run_cell


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_new_config_traffic_and_metric_are_found_as_new_files(tmp_path):
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    config = json.loads((bench / "configs" / "scu-cluster-8pe.json").read_text())
    config["name"] = "scu-tiny"
    config["n_pes"] = 4
    (bench / "configs" / "scu-tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny-4pe.json").write_text(json.dumps({
        "jobs": [{"primitive": "barrier", "policy": "tree", "sfr": 16, "iters": 2},
                 {"primitive": "mutex", "policy": "sw", "t_crit": 3, "sfr": 0, "iters": 2}],
    }))
    (bench / "metrics" / "sim.jobs_in_window.py").write_text(
        "def read(r):\n    return len(r.record['jobs'])\n")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "scu-tiny", "source": "https://arxiv.org/abs/2004.06662",
                           "file": "chipbench/configs/scu-tiny.json", "reduced": [],
                           "why": "a tiny cluster"})
    doc["workloads"].append({"name": "scu-tiny.tiny-4pe", "config": "scu-tiny",
                             "traffic": "tiny-4pe", "chips": 1, "why": "a tiny sweep"})
    doc["per_layer"].append({"name": "sim.jobs_in_window", "unit": "jobs", "better": "higher",
                             "source": "program_counter", "layer": "sweep driver (host)",
                             "moves": "sim_pe_cycles_per_s", "workloads": ["scu-tiny.tiny-4pe"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = Spec(tmp_path, bench)
    run = prepare(spec, "scu-tiny.tiny-4pe", seed=2**40 + 3)
    assert run.config["name"] == "scu-tiny" and run.config["n_pes"] == 4
    driver = spec.driver(run.config["kind"]).Driver(run)
    driver.setup()
    record = driver.window(0.1, lambda name: contextlib.nullcontext())
    assert driver.check(record)["correct"]
    names = [m["name"] for m in spec.metrics(run.cell, trace=True)]
    assert names == ["sim.jobs_in_window"]
    value = spec.reader("sim.jobs_in_window")(Reading(run, record, None, None))
    assert value == len(record["jobs"]) >= 1
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed


def test_refuses_a_platform_that_is_not_tpu(capsys):
    from chipbench import run as entry

    rc = entry.main(["--workload", "sim.fig5-8pe", "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no TPU" in err


def test_a_checkout_of_only_the_benchmark_exits_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    for extra in ([], ["--rehearse"]):  # refused; the program is missing
        p = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload", "sim.fig5-8pe", "--seed", "1",
             "--seconds", "1", *extra],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        assert p.stdout == ""


def test_result_line_keys():
    spec = Spec()
    run = prepare(spec, "sim.fig5-8pe", seed=9, rehearse=True)
    run.traffic["jobs"] = run.traffic["jobs"][:2]
    run.rehearse = False  # a real run's line, at a rehearsal's size
    out = run_cell(spec, run, seconds=0.2, trace=False, t_start=time.perf_counter())
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "sim_pe_cycles_per_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert out["checks"] == {"mismatched_jobs": {"value": 0, "limit": 0}}
    json.dumps(out)
