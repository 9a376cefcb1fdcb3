"""The benchmark's four-chip cell, ``pmkid-dict-4chip``, driven at the
benchmark's tiny size on CPU devices with device streams on: the sound
program reads ``correct: true``, and the cell's control, half of every
batch left unchecked, reads ``false``.  Two devices stand in for the
host's chips (each stream compiles its own programs, so the stream
count bounds the compile bill); both runs share one process's
compiles."""

import jax
import pytest

from benchmark import faults
from benchmark.spec import Spec
from benchmark.tests.tiny import run_tiny
from dwpa_tpu import parallel

CELL = "pmkid-dict-4chip"


@pytest.fixture
def two_streams(monkeypatch):
    mesh_of = parallel.default_mesh
    monkeypatch.setattr(parallel, "default_mesh", lambda devices=None:
                        mesh_of(devices or jax.devices()[:2]))


def _correctness():
    spec = Spec()
    return spec.traffic(spec.cell(CELL)["traffic"])["correctness"]


def test_cell_on_streams_reads_correct(two_streams):
    res = run_tiny(CELL, _correctness()["tiny_seed"],
                   config={"device_streams": "on"})
    assert res["correct"], res["checks"]
    assert res["checks"]["window_units"]["value"] >= 1
    assert res["checks"]["engine_retries"]["value"] == 0


def test_cell_control_on_streams_reads_not_correct(two_streams):
    c = _correctness()
    res = run_tiny(CELL, c["tiny_seed"], config={"device_streams": "on"},
                   fault=faults.FAULTS[faults.CONTROLS[c["control"]]["fault"]],
                   traffic=c["control_traffic"])
    assert not res["correct"], res["checks"]
