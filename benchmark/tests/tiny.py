"""Tiny sizes for driving a cell on the CPU: every cell's own shapes,
planting and guarantees, at batch 64 over a 300-word dictionary."""

import os
import time

from benchmark import harness
from benchmark.harness import _merge, run_cell
from benchmark.spec import ROOT, Spec

#: the tests' own work directory, apart from the one real runs use
WORK = os.path.join(ROOT, ".bench_test_work")

TINY = {"config": {"client": {"batch_size": 64, "device_streams": "off"}},
        "traffic": {"groups": 12, "dict": {"words": 300}}}
SECONDS = 4.0


def run_tiny(cell: str, seed: int, fault=None, config=None,
             traffic=None) -> dict:
    over = {"config": {"client": dict(TINY["config"]["client"],
                                      **(config or {}))},
            "traffic": _merge(traffic or {}, TINY["traffic"])}
    harness.WORK = WORK
    return run_cell(Spec(), cell, seed, SECONDS, False, time.perf_counter(),
                    require_tpu=False, overrides=over, fault=fault)
