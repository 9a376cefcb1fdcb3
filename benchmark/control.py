#!/usr/bin/env python3
"""Correctness readings over many seeds in one process, at a cell's own
size on the chip: the sound program, or the cell's control (the
``correctness`` block of its traffic file names one of
``faults.CONTROLS`` and the planting it runs on), or a fault
(``faults.FAULTS``) planted under the timed path.

``python3 benchmark/control.py --workload <cell> --seeds a,b,c
--seconds <s> [--control | --fault <name>]``

One JSON line per seed: the seed, ``correct`` and every check with its
number.  The benchmark's own runs never call this; it is how the
readings that ``PERF.md`` sets the limits from were taken.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".xla_cache")
os.makedirs(CACHE, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
# libtpu would log under /tmp/tpu_logs, a fixed path the two sides share
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench_logs"))
sys.path[0] = ROOT

from benchmark import faults  # noqa: E402
from benchmark.harness import run_cell  # noqa: E402
from benchmark.spec import Spec  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    g = p.add_mutually_exclusive_group()
    g.add_argument("--control", action="store_true")
    g.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = p.parse_args(argv)
    spec = Spec()
    fault, over = None, None
    if args.fault:
        fault = faults.FAULTS[args.fault]
    elif args.control:
        corr = spec.traffic(spec.cell(args.workload)["traffic"])["correctness"]
        c = faults.CONTROLS[corr["control"]]
        fault = faults.FAULTS.get(c.get("fault"))
        over = {"config": {"client": c.get("config", {})},
                "traffic": corr["control_traffic"]}
    t0 = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(spec, args.workload, seed, args.seconds, False, t0,
                       overrides=over, fault=fault)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": {k: v["value"] for k, v in
                                     res["checks"].items()}}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
