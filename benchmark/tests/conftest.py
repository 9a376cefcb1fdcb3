"""CPU only: the benchmark's tests drive the harness at tiny sizes, with
the Pallas kernels in interpret mode.  Run them as
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".pytest_xla_cache"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
