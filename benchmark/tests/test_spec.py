"""BENCHMARK.json and the files it names: valid as committed, and a cell,
a configuration or a metric added as new files is found by name."""

import json
import os
import shutil

import pytest

from benchmark.spec import ROOT, Spec


def test_committed_benchmark_is_valid():
    assert Spec().validate() == []


@pytest.fixture
def copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    return tmp_path


def _edit(root, fn):
    path = root / "BENCHMARK.json"
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc))


def test_a_new_cell_is_found_and_validated(copy):
    mix = {"groups": 4, "correctness": {
        "control": "half_batch", "control_traffic": {}, "tiny_seed": 7}}
    (copy / "benchmark" / "workloads" / "new-mix.json").write_text(
        json.dumps(mix))
    (copy / "benchmark" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 1.0\n")

    def add(doc):
        doc["workloads"].append({
            "name": "new-cell", "config": doc["configs"][0]["name"],
            "traffic": "new-mix", "chips": 1, "why": "a test"})
        doc["per_layer"].append({
            "name": "new_metric", "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "client loop",
            "moves": "pmk_per_s", "workloads": ["new-cell"]})
    _edit(copy, add)
    spec = Spec(str(copy))
    assert spec.validate() == []
    assert spec.traffic(spec.cell("new-cell")["traffic"]) == mix
    assert [m["name"] for m in spec.per_layer_for("new-cell")] == ["new_metric"]
    assert "setup_s" in [m["name"] for m in spec.end_to_end_for("new-cell")]
    assert spec.reader("new_metric").read(None) == 1.0


@pytest.mark.parametrize("fault, expect", [
    (lambda d: d["workloads"][0].update(config="nope"), "unknown config"),
    (lambda d: d["workloads"][0].update(traffic="nope"), "no traffic file"),
    (lambda d: d["per_layer"][0].update(moves="nope"), "moves unknown"),
    (lambda d: d["per_layer"][0].update(name="no_such_reader"),
     "no reader file"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0])),
     "duplicate names"),
    (lambda d: d["end_to_end"][0].update(workloads=[]), "does not report"),
    (lambda d: d["configs"][0].update(reduced=[]), "reduced"),
])
def test_faults_in_the_declaration_are_found(copy, fault, expect):
    _edit(copy, fault)
    assert any(expect in msg for msg in Spec(str(copy)).validate())


@pytest.mark.parametrize("correctness, expect", [
    (None, "has no correctness"),
    ({"control": "nope", "control_traffic": {}, "tiny_seed": 1},
     "unknown control"),
    ({"control": "nc2", "control_traffic": {}, "tiny_seed": -1},
     "bad tiny_seed"),
])
def test_a_traffic_file_without_its_correctness_is_found(copy, correctness,
                                                         expect):
    spec = Spec(str(copy))
    path = copy / "benchmark" / "workloads" / (
        spec.doc["workloads"][0]["traffic"] + ".json")
    doc = json.loads(path.read_text())
    doc.pop("correctness")
    if correctness is not None:
        doc["correctness"] = correctness
    path.write_text(json.dumps(doc))
    assert any(expect in msg for msg in Spec(str(copy)).validate())
