"""Seconds from process start to the window's start: data, server,
JAX start, challenge, prewarm (compiles or cache loads) and the warm-up
units."""


def read(run):
    return run.setup_s
