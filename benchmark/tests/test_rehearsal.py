"""CPU rehearsal: each cell's data -> server -> client -> window ->
reference check -> contract-shaped result, at a tiny size; and the
entry point's refusal to run off the TPU."""

import json
import subprocess
import sys

import pytest

from benchmark.spec import ROOT, Spec
from benchmark.tests.tiny import run_tiny

CELLS = sorted(Spec().cells)
SEED = 2 ** 31 + 11


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu_without_device_metrics(cell):
    res = run_tiny(cell, SEED)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] == {}      # no CPU number under a metric's name
    assert list(res)[-1] == "checks" and res["attempted"] >= 1
    assert res["checks"]["window_units"]["value"] >= 1
    json.dumps(res)


def test_entry_point_refuses_to_run_off_the_tpu():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
