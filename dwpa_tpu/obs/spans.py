"""Span tracer: named, nested wall-clock timings over ``perf_counter``.

The crack loop's phases (challenge, get_work, dict download, pass 1,
pass 2, put_work) and bench.py's timed regions all publish through one
span API, so the benchmark JSON and live telemetry can never disagree
about what a region took.

**The device-sync rule.** ``time.perf_counter()`` reads the HOST clock;
on TPU, dispatch returns long before execution completes (bench.py's
timing notes), so a span that stops its clock while device work is
still in flight lies by orders of magnitude.  Every span that covers
device work must force a device→host fetch before the clock stops:

- the engine's ``crack*`` methods sync internally (their hits-gate
  fetches the result), so a span wrapping a whole crack call is sound;
- raw device launches need an explicit ``np.asarray(...)`` /
  ``jax.block_until_ready(...)`` inside the span, or a ``sync=`` value
  passed to ``stop()``/the context manager, which is fetched *before*
  the clock is read.

The DW106 lint rule (analysis/linter.py) enforces this statically on
the instrumented files, exactly as DW105 does for bench's legacy
``perf_counter`` spans.

Timings are recorded three times: into the owning registry as a
``dwpa_span_seconds{span=...}`` histogram (scrapeable), into a bounded
in-memory ring of finished-span records (name, parent, start, stop,
depth) that tests use to assert well-nestedness, and into the profiler
trace.  Every span opens a ``jax.profiler.TraceAnnotation`` named
``dwpa:<name>`` when it starts and closes it after the ``sync=`` fetch,
so a ``jax.profiler`` capture shows the host's phases on the same clock
as the device's operations.  Annotation arguments (the tracer's
``args`` plus each span's keywords) ride along, e.g. ``unit=<hkey[:8]>``
on ``work_unit`` and ``feed=<name>`` on the feed's spans.  With no trace
active an annotation costs one native call, so there is no switch.
"""

import contextlib
import threading
import time


def _force_fetch(sync):
    """Materialize ``sync`` on the host: callables are invoked, anything
    else goes through ``np.asarray`` (the same fetch bench.py uses)."""
    if sync is None:
        return
    if callable(sync):
        sync()
        return
    import numpy as np

    np.asarray(sync)


#: prefix of every span's profiler annotation
ANNOTATION_PREFIX = "dwpa:"


def _annotate(name, args):
    """Open the profiler annotation of span ``name`` (closed by
    ``Span._close``)."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(ANNOTATION_PREFIX + name, **args)
    ann.__enter__()
    return ann


class Span:
    """One live timing region.  Created by ``SpanTracer.start``/``span``;
    ``seconds`` is valid after ``stop()``."""

    __slots__ = ("tracer", "name", "parent", "depth", "t0", "t1", "_ann")

    def __init__(self, tracer, name, parent, depth, args):
        self.tracer = tracer
        self.name = name
        self.parent = parent
        self.depth = depth
        self._ann = _annotate(name, args)
        self.t0 = time.perf_counter()
        self.t1 = None

    @property
    def seconds(self) -> float:
        """Duration; live reading while the span is still open."""
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    def elapsed(self) -> float:
        return self.seconds

    def stop(self, sync=None) -> float:
        """Close the span; ``sync`` (device value or callable) is
        fetched/invoked BEFORE the clock is read — the device-sync rule
        above.  Idempotent: a second stop returns the recorded time."""
        if self.t1 is not None:
            return self.seconds
        _force_fetch(sync)
        self.t1 = time.perf_counter()
        self.tracer._finish(self)
        return self.seconds

    def _close(self):
        """Close the profiler annotation, once."""
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)


class SpanTracer:
    """Per-subsystem tracer; records into ``registry`` (default: the
    process-wide one) and keeps the last ``keep`` finished spans.
    ``args`` are annotation arguments every span of this tracer carries
    in the profiler trace."""

    def __init__(self, registry=None, keep: int = 1024, args: dict = None):
        from .metrics import default_registry

        self.registry = registry or default_registry()
        self.args = dict(args or {})
        self._hist = self.registry.histogram(
            "dwpa_span_seconds", "span durations by name")
        self._lock = threading.Lock()
        self._keep = keep
        self.finished = []  # ring of record dicts, oldest first
        self._local = threading.local()

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def start(self, name: str, **args) -> Span:
        """Open a span; ``args`` are its annotation's arguments in the
        profiler trace, on top of the tracer's own."""
        st = self._stack()
        parent = st[-1].name if st else None
        sp = Span(self, name, parent, len(st),
                  {**self.args, **args} if args else self.args)
        st.append(sp)
        return sp

    def _finish(self, sp: Span):
        st = self._stack()
        # pop sp and anything abandoned above it (an exception may have
        # skipped a child's stop; the stack must never wedge), closing
        # the abandoned children's annotations inside sp's own
        if sp in st:
            i = st.index(sp)
            for child in reversed(st[i + 1:]):
                child._close()
            del st[i:]
        sp._close()
        self._hist.labels(span=sp.name).observe(sp.seconds)
        with self._lock:
            self.finished.append({
                "name": sp.name, "parent": sp.parent, "depth": sp.depth,
                "t0": sp.t0, "t1": sp.t1,
            })
            if len(self.finished) > self._keep:
                del self.finished[: len(self.finished) - self._keep]

    @contextlib.contextmanager
    def span(self, name: str, sync=None, **args):
        """Context-managed span.  The body must sync its own device work
        (engine ``crack*`` calls do) or pass ``sync=`` to be fetched at
        exit — see the module docstring.  ``args`` as for ``start``."""
        sp = self.start(name, **args)
        try:
            yield sp
        finally:
            sp.stop(sync=sync)

    def records(self, name: str = None) -> list:
        """Finished-span records, optionally filtered by name."""
        with self._lock:
            recs = list(self.finished)
        return [r for r in recs if name is None or r["name"] == name]


_DEFAULT_TRACER = None
_DEFAULT_TRACER_LOCK = threading.Lock()


def default_tracer() -> SpanTracer:
    """Lazy singleton bound to the default registry."""
    global _DEFAULT_TRACER
    with _DEFAULT_TRACER_LOCK:
        if _DEFAULT_TRACER is None:
            _DEFAULT_TRACER = SpanTracer()
        return _DEFAULT_TRACER
