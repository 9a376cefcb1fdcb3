"""PMKs derived per second of the PBKDF2 kernel's device time: the
traced slice's PMKs (as ``pmk_per_s`` counts them, from the algorithm's
one PBKDF2 per candidate and ESSID, whatever implements it) over the
kernel's device seconds in the slice, summed over chips."""

from benchmark.kernels import PBKDF2
from benchmark.trace import kernel_seconds


def read(run):
    t = run.trace
    if t is None:
        return None
    s = kernel_seconds(t, PBKDF2) * t["chips"]
    pmks = run.pmks_until(run.window.t_mark)
    return pmks / s if s > 0 else None
