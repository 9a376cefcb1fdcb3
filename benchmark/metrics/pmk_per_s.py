"""PMKs per second over the window: every candidate whose device results
the client fetched inside the window (the per-batch checkpoint's count),
times the ESSIDs of its unit, over the window's seconds.  Padding lanes
never count.  The window opens at a unit's ``get_work``; the batch in
flight at its close counts by the share of its interval inside the
window (``Run.pmks_until``)."""


def read(run):
    return run.pmks_in_window() / run.window_s
