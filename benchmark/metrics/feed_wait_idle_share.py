"""Share of the traced slice in which the device was idle while the
client's loop thread waited on the candidate feed: idle inside its
``dwpa:feed:wait`` spans (and inside a pass, so this part of the idle
time never overlaps ``unit_boundary_idle_share``'s), per chip, over the
slice.  None where the program writes no ``dwpa:`` spans."""

from benchmark.spans import PASSES, idle_inside, of


def read(run):
    red = of(run)
    if red is None or red["loop"] is None:
        return None
    return idle_inside(red, ("feed:wait",), within=PASSES) / red["window_s"]
