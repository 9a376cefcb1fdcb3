"""Device seconds of the PBKDF2 Pallas kernel's events (per chip) over
the traced slice.  The kernel is found by name (``kernels.PBKDF2``)."""

from benchmark.kernels import PBKDF2
from benchmark.trace import kernel_seconds


def read(run):
    t = run.trace
    if t is None:
        return None
    s = kernel_seconds(t, PBKDF2)
    return s / t["window_s"] if s > 0 else None
