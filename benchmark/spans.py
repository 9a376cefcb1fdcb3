"""Read the program's own names out of a profiler trace: its ``dwpa:``
spans on the host and its named steps on the device.

``trace.py`` reduces the device's operations and the benchmark's
``bench:`` annotations.  This module reads what the program itself
writes into the same trace (``dwpa_tpu/obs/spans.py``, and the step
names ``dwpa_tpu/parallel/step.py`` gives its jitted programs):

- ``modules``: per chip, the ``XLA Modules`` intervals inside the window,
  each with its program's name (``jit_verify_eapol(...)``);
- ``spans``: every ``dwpa:<name>`` annotation of the host planes inside
  the window, with its host line and its arguments;
- ``idle``: per chip, the merged stretches of the window in which no
  operation ran on the device (the complement of the busy time
  ``trace.py`` measures);
- ``loop``: the client's loop thread: the host line that carries
  ``dwpa:work_unit``, or, where no unit ends inside the slice, the one
  that carries most of the loop's other spans (``LOOP_MARKS``).

A span is written into the trace when it stops, so one still open when
the trace stops is missing.  On the loop thread that is the unit in
flight at the slice's end and its pass; both are put back
(``loop_spans``): the pass from the first span its feed annotated
(``feed=<pass>``) outside any recorded span of that pass, the unit from
the first span of a unit's phases after the last recorded unit.

A program that writes no ``dwpa:`` span has no loop line, and the
readers of these facts then return None; its device operations are then
not read again.  The reduction is read once
per run (``of``) and prints two stderr lines: every idle second of the
slice put down to the innermost ``dwpa:`` span on the loop thread, and
the device time of each named program.
"""

import sys
import time

from .trace import ANCHOR, DEVICE_RE, OP_LINES, find_xplane

PREFIX = "dwpa:"
MODULE_LINE = "XLA Modules"
LOOP_SPAN = "work_unit"
#: spans only the loop thread writes
LOOP_MARKS = (LOOP_SPAN, "pass1", "pass2", "mask", "feed:wait", "get_work",
              "put_work", "submit")
#: the loop thread's crack passes
PASSES = ("pass1", "pass2", "mask")
#: spans that run only inside a unit, so one of them after the last
#: recorded unit means a unit was open when the trace stopped
UNIT_PHASES = ("archive", "engine_init", "resume:write", "pass1", "pass2",
               "mask", "founds:record", "submit", "put_work",
               "dict_download", "prdict")
UNIT_PREFIXES = ("engine:", "feed:")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(path: str, anchor_perf_ns: int, t0_ns: int, t1_ns: int) -> dict:
    """The window [t0_ns, t1_ns) of host ``perf_counter_ns`` time, read
    from the trace at ``path`` (the same window and anchor as
    ``trace.reduce``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    anchor = None
    events = 0
    for pi, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    spans.append(((pi, li), e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  name[len(PREFIX):], dict(e.stats)))
                elif name == ANCHOR:
                    anchor = e.start_ns
    for plane in pd.planes:
        m = DEVICE_RE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == MODULE_LINE:
                mods = modules.setdefault(dev, [])
                for e in line.events:
                    mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
            elif line.name in OP_LINES and spans:
                evs = ops.setdefault(dev, [])
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns))
                events += len(evs)
    if anchor is None:
        raise ValueError(f"{path}: no {ANCHOR} annotation")
    off = anchor - anchor_perf_ns
    w0, w1 = t0_ns + off, t1_ns + off
    idle = {}
    for dev, evs in sorted(ops.items()):
        merged = _union((max(s, w0), min(e, w1)) for s, e in evs
                        if e > w0 and s < w1)
        gaps, edge = [], w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        idle[dev] = gaps
    spans = [(k, max(s, w0), min(e, w1), n, a) for k, s, e, n, a in spans
             if e > w0 and s < w1]
    return {
        "w0": w0, "w1": w1, "window_s": (w1 - w0) / 1e9,
        "chips": len(idle) or len(modules),
        "modules": {dev: [(max(s, w0), min(e, w1), n) for s, e, n in mods
                          if e > w0 and s < w1]
                    for dev, mods in modules.items()},
        "spans": spans,
        "idle": idle,
        "loop": loop_line(spans),
        "events": events,
    }


def loop_line(spans):
    """The host line of the client's loop thread among ``(line, start,
    end, name, args)`` spans (see the module docstring), or None."""
    loop = next((k for k, _, _, n, _ in spans if n == LOOP_SPAN), None)
    if loop is None:
        marks = {}
        for k, _, _, n, _ in spans:
            if n in LOOP_MARKS:
                marks[k] = marks.get(k, 0) + 1
        loop = max(marks, key=marks.get) if marks else None
    return loop


def _in_any(s, e, intervals) -> bool:
    return any(a <= s and e <= b for a, b in intervals)


def loop_spans(red: dict) -> list:
    """``(start, end, name, args)`` of the loop thread's spans, with the
    unit and the pass still open when the trace stopped put back (see the
    module docstring)."""
    if red["loop"] is None:
        return []
    own = sorted((s, e, n, a) for k, s, e, n, a in red["spans"]
                 if k == red["loop"])
    w1 = red["w1"]
    out = list(own)
    for p in PASSES:
        closed = [(s, e) for s, e, n, _ in own if n == p]
        orphans = [s for s, e, n, a in own
                   if a.get("feed") == p and not _in_any(s, e, closed)]
        if orphans:
            # a nanosecond early, so that it encloses the child it starts
            # with even where both reach the slice's end
            out.append((min(orphans) - 1, w1, p, {}))
    units = [(s, e) for s, e, n, _ in own if n == LOOP_SPAN]
    last = max((e for _, e in units), default=red["w0"])
    inside = [s for s, e, n, _ in out
              if s >= last and not _in_any(s, e, units)
              and (n in UNIT_PHASES or n.startswith(UNIT_PREFIXES))]
    if inside:
        out.append((min(inside) - 1, w1, LOOP_SPAN, {}))
    return sorted(out, key=lambda x: (x[0], -x[1]))


def flatten(spans) -> list:
    """Well-nested ``(start, end, name, ...)`` spans of one thread as
    disjoint ``(start, end, name)`` pieces, each named after the
    innermost span covering it."""
    out, stack, pos = [], [], None
    for s, e, name, *_ in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            if pos < end:
                out.append((pos, end, n))
                pos = end
        if stack:
            e = min(e, stack[-1][1])
            if pos < s:
                out.append((pos, s, stack[-1][0]))
        pos = s
        stack.append((name, e))
    while stack:
        n, end = stack.pop()
        if pos < end:
            out.append((pos, end, n))
            pos = end
    return out


def idle_by_span(red: dict) -> dict:
    """Idle seconds per chip, by the innermost loop-thread span covering
    them (``none`` outside every span)."""
    pieces = flatten(loop_spans(red))
    out = {}
    for gaps in red["idle"].values():
        covered = i = j = 0
        while i < len(pieces) and j < len(gaps):
            s = max(pieces[i][0], gaps[j][0])
            e = min(pieces[i][1], gaps[j][1])
            if e > s:
                n = pieces[i][2]
                out[n] = out.get(n, 0) + (e - s)
                covered += e - s
            if pieces[i][1] < gaps[j][1]:
                i += 1
            else:
                j += 1
        rest = sum(e - s for s, e in gaps) - covered
        if rest > 0:
            out["none"] = out.get("none", 0) + rest
    chips = red["chips"] or 1
    return {n: v / 1e9 / chips for n, v in out.items()}


def _loop_union(red: dict, names) -> list:
    return _union((s, e) for s, e, n, _ in loop_spans(red) if n in names)


def idle_outside(red: dict, names) -> float:
    """Idle seconds per chip outside every loop-thread span named in
    ``names``."""
    cover = _loop_union(red, names)
    total = sum(sum(e - s for s, e in g) - _overlap(g, cover)
                for g in red["idle"].values())
    return total / 1e9 / (red["chips"] or 1)


def idle_inside(red: dict, names, within=None) -> float:
    """Idle seconds per chip inside the loop-thread spans named in
    ``names`` (and inside those named in ``within``, when given)."""
    cover = _loop_union(red, names)
    if within is not None:
        outer = _loop_union(red, within)
        cover = [(max(s, a), min(e, b)) for s, e in cover for a, b in outer
                 if min(e, b) > max(s, a)]
    return sum(_overlap(g, cover) for g in red["idle"].values()) \
        / 1e9 / (red["chips"] or 1)


def module_seconds(red: dict, pattern: str) -> float:
    """Device seconds per chip in which a program whose name holds
    ``pattern`` ran (the union of its ``XLA Modules`` intervals)."""
    mods = red["modules"]
    if not any(pattern in n for ev in mods.values() for _, _, n in ev):
        return None
    total = sum(sum(e - s for s, e in _union(
        (s, e) for s, e, n in ev if pattern in n)) for ev in mods.values())
    return total / 1e9 / (len(mods) or 1)


def _program(name: str) -> str:
    """``jit_verify_eapol(1234)`` -> ``jit_verify_eapol``."""
    return name.split("(", 1)[0]


def of(run):
    """The run's reduction, read from its trace once and kept on the run;
    None for a run without a trace."""
    if "program_trace" in run.__dict__:
        return run.program_trace
    red = None
    w = getattr(run, "window", None)
    if getattr(run, "trace", None) is not None and \
            getattr(w, "trace_dir", None):
        t = time.perf_counter()
        red = reduce(find_xplane(w.trace_dir), w.anchor_ns,
                     int(w.t_start * 1e9), int(w.t_mark * 1e9))
        _report(red, time.perf_counter() - t)
    run.program_trace = red
    return red


def _report(red: dict, read_s: float):
    by = idle_by_span(red)
    idle = sum(by.values())
    parts = ", ".join(f"{n} {s:.6f} s" for n, s in
                      sorted(by.items(), key=lambda kv: -kv[1]))
    print(f"bench: idle by program span: {idle:.6f} s idle of "
          f"{red['window_s']:.3f} s, outside every dwpa: span on the loop "
          f"thread {by.get('none', 0.0):.6f} s; {parts or 'no loop thread'}"
          f" (read {read_s:.1f} s)", file=sys.stderr, flush=True)
    progs = {}
    for ev in red["modules"].values():
        for s, e, n in ev:
            progs[_program(n)] = progs.get(_program(n), 0) + (e - s)
    chips = red["chips"] or 1
    top = sorted(progs.items(), key=lambda kv: -kv[1])[:8]
    print("bench: device time by program: " + (", ".join(
        f"{n} {s / 1e9 / chips:.6f} s" for n, s in top) or "none"),
        file=sys.stderr, flush=True)
