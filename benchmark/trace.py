"""Reduce a profiler trace (``.xplane.pb``) to the window's device facts.

It reads the trace with ``jax.profiler.ProfileData`` alone:

- device planes are those named ``/device:TPU:<n>``; on each, the events
  of the op lines (``OP_LINES``) are the device's operations, and busy
  time is the union of their intervals inside the window;
- host planes (``/host:...``) carry the benchmark's ``bench:<phase>``
  annotations, one of which (``bench:window_open``) ties the profiler's
  clock to the host clock the window was measured on;
- an idle gap is a stretch of the window in which no operation ran on
  the device; it is put down to the innermost ``bench:`` span that
  covers its middle (``get_work``, ``dict_download``, ``pass1``,
  ``pass2``, ``put_work``, ``engine_init``, ...), or ``none``.
"""

import glob
import os
import re

DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
#: the lines of a device plane whose events are operations
OP_LINES = ("XLA Ops",)
ANCHOR = "bench:window_open"
PREFIX = "bench:"


def op_name(event: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's name (``%pbkdf2_sha1_pmk_pallas.1``)."""
    return event.split(" = ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(path: str, anchor_perf_ns: int, t0_ns: int, t1_ns: int) -> dict:
    """The window [t0_ns, t1_ns) of host ``perf_counter_ns`` time, read
    from the trace at ``path``.  ``anchor_perf_ns`` is the host time at
    which the ``bench:window_open`` annotation was written."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops = {}        # device id -> [(start, end, name)] on the trace clock
    host = []       # (start, end, phase)
    anchor = None
    events = 0      # events read, for the run's stderr
    for plane in pd.planes:
        m = DEVICE_RE.match(plane.name)
        if m:
            evs = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    events += 1
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events += 1
                    if not e.name.startswith(PREFIX):
                        continue
                    if e.name == ANCHOR:
                        anchor = e.start_ns
                    else:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name[len(PREFIX):]))
    if anchor is None:
        raise ValueError(f"{path}: no {ANCHOR} annotation")
    if not ops:
        raise ValueError(f"{path}: no TPU device plane")
    off = anchor - anchor_perf_ns
    w0, w1 = t0_ns + off, t1_ns + off
    window_s = (w1 - w0) / 1e9
    busy, op_s, gaps = {}, {}, []
    for dev, evs in sorted(ops.items()):
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
        merged = _union((s, e) for s, e, _ in clipped)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    chips = len(busy)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        cover = [h for h in host if h[0] <= mid < h[1]]
        phase = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "none"
        named.append([phase, (e - s) / 1e9])
    return {
        "window_s": window_s,
        "chips": chips,
        "busy_s": sum(busy.values()) / chips,
        "op_s": {n: s / chips for n, s in op_s.items()},
        "idle_gaps": named,
        "events": events,
    }


def top_ops(reduced: dict, n: int = 10) -> list:
    return [[name, s] for name, s in
            sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(reduced: dict, patterns) -> float:
    """Device seconds (per chip) of the ops whose name holds a pattern."""
    return sum(s for name, s in reduced["op_s"].items()
               if any(p in name for p in patterns))
