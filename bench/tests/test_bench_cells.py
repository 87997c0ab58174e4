"""Every cell's glue driven through ``GangExecutor`` on the CPU at the
reduced float32 size, the result line it prints, a cell added from data
alone, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
BM = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BM["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "checks"}
CPU_PEAK = {"bf16_flops": 1e12}      # CPU runs read no chip's peak


def small_run(cell, trace=False, **kw):
    return harness.run_cell(cell, 2 ** 40 + 17, 1.0, trace,
                            require_tpu=False, cache=False, small=True,
                            peak=CPU_PEAK, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_through_the_executor(cell):
    r = small_run(cell)
    assert set(r) == LINE_KEYS
    assert list(r)[-1] == "checks"
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert set(r["metrics"]) == e2e
    assert r["attempted"] >= 10
    assert r["metrics"]["be_quanta_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert "kind" in r["device"] and "memory_peak_bytes" in r["device"]
    for name, c in r["checks"].items():
        assert set(c) == {"value", "limit", "op"}, name
    # a one-second window serves too few frames to reach the sample size
    # of the chip's window; every other number passes
    sizes = {"frames_checked"}
    assert all(harness.passes(c["value"], c["limit"], c["op"])
               for n, c in r["checks"].items() if n not in sizes), r["checks"]
    json.dumps(r)


def test_traced_run_reads_every_per_layer_metric():
    cell = "dave2.multicam-hbm-be"
    r = small_run(cell, trace=True)
    assert set(r) == LINE_KEYS | {"breakdown"}
    want = {m["name"] for m in BM["per_layer"] if cell in m["workloads"]}
    assert set(r["metrics"]) == want
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert 0 <= r["metrics"]["rt_step_mfu"]["value"] <= 100
    assert r["breakdown"]["device_ops"]


@pytest.mark.parametrize("config,mix,source", [
    ("dave2", "dummy-be", "testdata"),
])
def test_a_cell_from_data_alone(tmp_path, config, mix, source):
    """A traffic mix and a new BENCHMARK.json entry are all a cell needs:
    no file that is there changes."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  ".trace"))
    shutil.copy(os.path.join(harness.BENCH, source, mix + ".json"),
                bench / "traffic" / (mix + ".json"))
    bm = json.loads(json.dumps(BM))
    name = f"{config}.{mix}"
    assert name not in CELLS
    bm["workloads"].append({"name": name, "config": config, "traffic": mix,
                            "chips": 1, "why": "test"})
    r = harness.run_cell(name, 5, 1.0, False, bm=bm, bench=str(bench),
                         require_tpu=False, cache=False, small=True,
                         peak=CPU_PEAK)
    assert r["attempted"] >= 5 and r["metrics"]["be_quanta_per_s"]["value"] > 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""
