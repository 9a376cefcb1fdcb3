"""Share of the traced slice in which a chip sat idle because its device
stream had no block: for each chip n, the device's idle time on
``/device:TPU:n`` inside the ``dwpa:stream:wait`` spans whose ``device``
argument names chip n, over the slice, averaged over the chips.

The argument is ``parallel.streams.device_label``'s ``tpu:<id>``.
Device streams run only in a one-process host, where a chip's id is its
ordinal among the host's chips, the n of its plane ``/device:TPU:n``;
so the id after the colon is the plane's number.  A wait still open
when the trace stopped is not in the trace (a span is written when it
stops) and is not counted.  None where the program writes no
``stream:wait`` span."""

from benchmark.spans import _overlap, _union, of


def read(run):
    red = of(run)
    if red is None:
        return None
    waits = {}
    for _, s, e, name, args in red["spans"]:
        chip = str(args.get("device", "")).rpartition(":")[2]
        if name == "stream:wait" and chip.isdigit():
            waits.setdefault(int(chip), []).append((s, e))
    if not waits:
        return None
    idle = sum(_overlap(gaps, _union(waits.get(chip, [])))
               for chip, gaps in red["idle"].items())
    return idle / 1e9 / (red["chips"] or 1) / red["window_s"]
