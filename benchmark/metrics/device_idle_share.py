"""1 - (union of the op intervals on each TPU plane / traced slice),
averaged over the cell's chips, from the profiler trace."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
