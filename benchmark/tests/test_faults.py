"""A run with the timed path broken underneath reads ``correct: false``,
once for each fault the cells can have (no cell spans chips, so "the
exchange between chips left out" has no case here), and once for each
cell's control: a guarantee of the configuration broken.

Each cell's traffic file names its control, the traffic the control
runs on and the seed its tiny runs take (``correctness``), so a cell
added as files needs no edit here."""

import pytest

from benchmark import faults
from benchmark.spec import Spec
from benchmark.tests.tiny import run_tiny

SPEC = Spec()
CELLS = sorted(SPEC.cells)


def correctness(cell: str) -> dict:
    return SPEC.traffic(SPEC.cell(cell)["traffic"])["correctness"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_reads_not_correct(cell, fault):
    res = run_tiny(cell, correctness(cell)["tiny_seed"],
                   fault=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(cell):
    c = correctness(cell)
    control = faults.CONTROLS[c["control"]]
    res = run_tiny(cell, c["tiny_seed"],
                   fault=faults.FAULTS.get(control.get("fault")),
                   config=control.get("config"),
                   traffic=c["control_traffic"])
    assert not res["correct"], res["checks"]
