"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and when the control stands in the
program's place. Runs on the CPU at the reduced size, with the harness's look
for a chip skipped."""
import os

import pytest

from bench import harness

BM = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CPU_PEAK = {"bf16_flops": 1e12}


def run(cell, **kw):
    return harness.run_cell(cell, 2 ** 33 + 5, 1.0, False, require_tpu=False,
                            cache=False, small=True, peak=CPU_PEAK, **kw)


def failed(r, name):
    c = r["checks"][name]
    return not harness.passes(c["value"], c["limit"], c["op"])


@pytest.mark.parametrize("cell,fault,check", [
    ("dave2.multicam-hbm-be", "answer", "steer_err_max"),
    ("dave2.multicam-hbm-be", "budget", "be_over_budget_windows"),
])
def test_fault_makes_the_run_incorrect(cell, fault, check):
    r = run(cell, fault=fault)
    assert r["correct"] is False
    assert failed(r, check), r["checks"]


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_the_control_in_the_programs_place_is_incorrect(cell):
    r = run(cell, control=True)
    assert r["correct"] is False
    assert list(r)[-1] == "checks"
    for name in ("steer_err_max", "steer_err_rms"):
        assert not failed(r["program"], name), r["program"]["checks"]
        assert failed(r, name), r["checks"]
