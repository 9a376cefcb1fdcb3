"""Share of the traced slice in which the device was idle outside every
``dwpa:pass1``/``pass2``/``mask`` span of the client's loop thread: the
unit boundary (put_work, the outbox, the resume file, get_work, the
next unit's engine and archive), per chip, over the slice.  None where
the program writes no ``dwpa:`` spans."""

from benchmark.spans import PASSES, idle_outside, of


def read(run):
    red = of(run)
    if red is None or red["loop"] is None:
        return None
    return idle_outside(red, PASSES) / red["window_s"]
