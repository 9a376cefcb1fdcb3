"""XLA compilations from the window's start to the end of the unit in
flight at its close, counted by ``dwpa_tpu.analysis.watch_compiles``.
It should read 0: set-up warms every shape the window uses."""


def read(run):
    return run.compiles
