"""The most busy chip's device seconds less the least busy chip's, in
the traced slice, over the slice: 0 where every chip was busy alike.
It grows with a pass of fewer blocks than chips (pass 1's single
block), a unit's last partial wave, or a straggling chip.  A chip is
busy for the slice less its idle stretches (``spans.of(run)["idle"]``);
a chip that ran no operation in the slice has no such entry and was
idle throughout."""

from benchmark.spans import of


def read(run):
    red = of(run)
    if red is None or not red["idle"]:
        return None
    w = red["window_s"]
    busy = [w - sum(e - s for s, e in gaps) / 1e9
            for gaps in red["idle"].values()]
    busy += [0.0] * (run.trace["chips"] - len(busy))
    return (max(busy) - min(busy)) / w
