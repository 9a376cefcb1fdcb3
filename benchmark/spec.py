"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``benchmark/configs/<config>.json`` (the ``file`` of
its entry), a traffic mix ``benchmark/workloads/<traffic>.json``, and a
metric ``benchmark/metrics/<name>.py`` with a ``read(run)`` that returns
a number or None.  A later PR adds a cell, a configuration or a metric
by adding files and entries; nothing here names one.
"""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Spec:
    """The benchmark as ``root/BENCHMARK.json`` declares it."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.e2e = {m["name"]: m for m in self.doc["end_to_end"]}
        self.layer = {m["name"]: m for m in self.doc["per_layer"]}

    def path(self, *parts) -> str:
        return os.path.join(self.root, *parts)

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(self.cells)})")
        return self.cells[name]

    def config(self, name: str) -> dict:
        return load_json(self.path(self.configs[name]["file"]))

    def traffic(self, name: str) -> dict:
        return load_json(self.path("benchmark", "workloads", f"{name}.json"))

    def reader(self, metric: str):
        path = self.path("benchmark", "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def end_to_end_for(self, cell: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", self.cells)]

    def per_layer_for(self, cell: str) -> list:
        e2e = {m["name"] for m in self.end_to_end_for(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def metrics_for(self, cell: str, trace: bool) -> list:
        return self.per_layer_for(cell) if trace else self.end_to_end_for(cell)

    def _check_correctness(self, w: dict) -> list:
        from .faults import CONTROLS

        c = self.traffic(w["traffic"]).get("correctness")
        if not isinstance(c, dict):
            return [f"cell {w['name']}: traffic file has no correctness"]
        bad = []
        if c.get("control") not in CONTROLS:
            bad.append(f"cell {w['name']}: unknown control "
                       f"{c.get('control')!r}")
        if not isinstance(c.get("control_traffic"), dict):
            bad.append(f"cell {w['name']}: control_traffic not an object")
        if not isinstance(c.get("tiny_seed"), int) or c["tiny_seed"] < 0:
            bad.append(f"cell {w['name']}: bad tiny_seed")
        return bad

    def validate(self) -> list:
        """Every fault found in the declared files, as messages."""
        bad = []
        doc = self.doc
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in doc[key]]
            if len(names) != len(set(names)):
                bad.append(f"{key}: duplicate names")
            for n in names:
                if not NAME_RE.match(n):
                    bad.append(f"{key}: bad name {n!r}")
        for c in doc["configs"]:
            if not os.path.isfile(self.path(c["file"])):
                bad.append(f"config {c['name']}: no file {c['file']}")
                continue
            f = self.config(c["name"])
            if f.get("reduced") != c["reduced"]:
                bad.append(f"config {c['name']}: file's reduced != entry's")
            if not any(w["config"] == c["name"] for w in doc["workloads"]):
                bad.append(f"config {c['name']}: used by no cell")
        pairs = set()
        for w in doc["workloads"]:
            if w["config"] not in self.configs:
                bad.append(f"cell {w['name']}: unknown config {w['config']}")
            if not os.path.isfile(self.path("benchmark", "workloads",
                                            f"{w['traffic']}.json")):
                bad.append(f"cell {w['name']}: no traffic file "
                           f"{w['traffic']}.json")
            else:
                bad += self._check_correctness(w)
            if w["chips"] not in (1, 4):
                bad.append(f"cell {w['name']}: chips {w['chips']}")
            elif (w["config"] in self.configs and os.path.isfile(
                    self.path(self.configs[w["config"]]["file"]))
                  and self.config(w["config"]).get("chips") != w["chips"]):
                bad.append(f"cell {w['name']}: chips differ from its config")
            if (w["config"], w["traffic"]) in pairs:
                bad.append(f"cell {w['name']}: config and traffic repeat")
            pairs.add((w["config"], w["traffic"]))
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            for c in m.get("workloads", []):
                if c not in self.cells:
                    bad.append(f"metric {m['name']}: unknown cell {c}")
            if not os.path.isfile(self.path("benchmark", "metrics",
                                            f"{m['name']}.py")):
                bad.append(f"metric {m['name']}: no reader file")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"metric {m['name']}: end-to-end source")
        for m in doc["per_layer"]:
            if m["moves"] not in self.e2e:
                bad.append(f"metric {m['name']}: moves unknown "
                           f"{m['moves']!r}")
                continue
            for c in m.get("workloads", []):
                if c in self.cells and m["moves"] not in {
                        x["name"] for x in self.end_to_end_for(c)}:
                    bad.append(f"metric {m['name']}: cell {c} does not "
                               f"report {m['moves']}")
        for w in doc["workloads"]:
            e2e = {m["name"] for m in self.end_to_end_for(w["name"])}
            if "setup_s" not in e2e or len(e2e) < 2:
                bad.append(f"cell {w['name']}: needs setup_s and another "
                           "end-to-end metric")
            if not self.per_layer_for(w["name"]):
                bad.append(f"cell {w['name']}: no per-layer metric")
        return bad
