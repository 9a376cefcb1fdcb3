"""Recompilation sentinel: count XLA compile-cache misses at runtime.

An unnoticed recompile costs more throughput than any kernel tweak: one
mid-sweep XLA compile of the PBKDF2 step is ~20-40 s of dead device time
per occurrence, and a shape leak that recompiles *per batch* turns the
crack loop into a compile loop (the hazard the engine's ``_STEP_CACHE``
/ power-of-two net bucketing exists to prevent — parallel/step.py).

Mechanism: JAX logs one "Finished XLA compilation of <name> ..." record
per compile-cache miss (``jax_log_compiles``); cache hits log nothing.
``watch_compiles`` toggles the flag and attaches a scoped logging
handler, so counting needs no private JAX APIs and works on every
platform (the persistent on-disk compilation cache still logs the
in-process miss, so warm-disk runs count identically).

Usage::

    with watch_compiles() as rep:
        engine.crack(words)
    assert rep.count == 0, rep.names

    with no_recompiles(allowed=0, label="autotune sweep"):
        for batch in sweep:
            engine.crack_batch(batch)      # raises on any compile

Pytest: the ``recompile_sentinel`` fixture (analysis/pytest_plugin.py,
re-exported by tests/conftest.py) wraps ``no_recompiles`` per test.
"""

import contextlib
import logging
import re

import jax

#: emitted by jax._src.dispatch once per compile-cache miss
_COMPILE_RE = re.compile(r"Finished XLA compilation of ([^\s]+) in")
#: loggers that carry the compile events (pxla only adds "Compiling
#: <name> ..." noise — attached so propagation pausing silences it too;
#: the count regex never matches its messages)
_LOGGER_NAMES = ("jax._src.dispatch", "jax._src.interpreters.pxla")


class RecompilationError(AssertionError):
    """A guarded region compiled more than its budget allows."""


class CompileReport:
    """Names of every XLA compilation observed inside the guarded region."""

    def __init__(self):
        self.names = []

    @property
    def count(self) -> int:
        return len(self.names)

    def __repr__(self):
        return f"CompileReport(count={self.count}, names={self.names!r})"


class _CompileCounter(logging.Handler):
    def __init__(self, report):
        super().__init__(level=logging.DEBUG)
        self.report = report

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.report.names.append(m.group(1))


@contextlib.contextmanager
def watch_compiles():
    """Collect-only sentinel: yields a CompileReport that accumulates the
    name of every XLA compilation (compile-cache miss) in the region."""
    report = CompileReport()
    handler = _CompileCounter(report)
    prev_flag = jax.config.jax_log_compiles
    prev_state = []
    jax.config.update("jax_log_compiles", True)
    for name in _LOGGER_NAMES:
        lg = logging.getLogger(name)
        prev_state.append((lg, lg.level, lg.propagate))
        # jax_log_compiles emits at WARNING; an app that quieted the jax
        # loggers must not blind the sentinel.  Propagation is paused so
        # the sentinel's own instrumentation doesn't spray WARNING lines
        # into the guarded region's output.
        if lg.getEffectiveLevel() > logging.WARNING:
            lg.setLevel(logging.WARNING)
        lg.propagate = False
        lg.addHandler(handler)
    try:
        yield report
    finally:
        for lg, lvl, prop in prev_state:
            lg.removeHandler(handler)
            lg.setLevel(lvl)
            lg.propagate = prop
        jax.config.update("jax_log_compiles", prev_flag)


@contextlib.contextmanager
def no_recompiles(allowed: int = 0, label: str = ""):
    """Fail-on-exit sentinel: raises RecompilationError when the region
    compiled more than ``allowed`` XLA programs.

    ``allowed`` budgets intentional one-time compiles (e.g. the first
    batch of a fresh shape bucket); a steady-state sweep guards with the
    default 0 so a per-batch recompile fails the test, not the cron.
    """
    with watch_compiles() as report:
        yield report
    if report.count > allowed:
        where = f" in {label}" if label else ""
        raise RecompilationError(
            f"{report.count} XLA compilation(s){where} where <= {allowed} "
            f"allowed — a shape/static-arg leak is recompiling the hot "
            f"path: {report.names}")
