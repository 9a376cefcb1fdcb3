"""The trace reduction on a slice of a real v5e trace (recorded in a
``pmkid-dict`` window on the chip, PR 22, and clipped to one second)."""

import json
import os

import pytest

from benchmark import trace as TR
from benchmark.kernels import PBKDF2

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PHASES = {"none", "get_work", "dict_download", "engine_init", "put_work",
          "work_unit", "pass1", "pass2", "feed:produce", "prdict"}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "window.json")) as f:
        w = json.load(f)
    red = TR.reduce(os.path.join(DATA, "window.xplane.pb"), w["anchor_ns"],
                    w["t0_ns"], w["t1_ns"])
    return w, red


def test_reduction_reads_as_recorded(recorded):
    w, red = recorded
    want = w["expect"]
    assert red["chips"] == want["chips"] == 1
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["op_s"] == pytest.approx(want["op_s"])
    assert [g[0] for g in red["idle_gaps"]] == [g[0] for g in
                                                want["idle_gaps"]]


def test_device_facts_are_sound(recorded):
    _, red = recorded
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(red["op_s"].values()) >= red["busy_s"] * 0.999
    assert all(name.startswith("%") for name in red["op_s"])
    k = TR.kernel_seconds(red, PBKDF2)
    assert 0 < k <= red["busy_s"]
    assert any(PBKDF2[0] in name for name, _ in TR.top_ops(red, 3))
    gaps = red["idle_gaps"]
    assert len(gaps) <= 10
    assert all(phase in PHASES or phase.startswith("feed")
               for phase, _ in gaps)
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in gaps) <= idle + 1e-9


def test_union_merges_overlaps():
    assert TR._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


def test_no_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        TR.find_xplane(str(tmp_path))
