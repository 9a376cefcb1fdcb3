#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.  Exits
non-zero, printing no result, off the TPU or on another chip count than
the cell asks for."""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache lives at one fixed path inside the
# checkout: the program honours the variable and sets no other directory
CACHE = os.path.join(ROOT, ".xla_cache")
os.makedirs(CACHE, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
# libtpu would log under /tmp/tpu_logs, a fixed path the two sides share
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench_logs"))
# the checkout's root, not benchmark/ (whose trace.py would shadow the
# standard library's), is the first place imports look
sys.path[0] = ROOT

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
