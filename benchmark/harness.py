"""One run of one cell: data, server, client, window, checks, result.

The run, in order:

1. The cell's traffic from ``--seed`` (``traffic.py``).
2. A fresh server seeded with the dictionary and the warm-up groups.
3. ``TpuCrackClient(ClientConfig(...)).run()`` with the cell's
   configuration, every cache directory empty: challenge, prewarm, then
   one warm-up unit per group shape.
4. At the first ``get_work`` after the warm-up units the window groups
   are ingested and the window opens; it closes ``--seconds`` later.
   Each ``get_work`` in the window first releases the next group, so
   the units take the groups in the traffic's order whatever order the
   server's scheduler would pick among many.  A ``get_work`` after the
   close ends the run; the unit in flight at the close finishes first
   (it is checked, and its batches count up to the close).
5. Peak device memory, then the checks against the plain reference
   (``reference.py``), then the result line.
"""

import argparse
import concurrent.futures
import contextlib
import json
import logging
import os
import re
import shutil
import sys
import threading
import time

import numpy as np

from . import reference as R
from . import trace as TR
from . import traffic as T
from .spec import Spec, load_json

WORK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_work")
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

#: dictionary words the reference samples besides the planted ones
SAMPLE = 16
#: seconds of the window a ``--trace 1`` run traces, from its start
TRACE_SECONDS = 6.0


class NoChip(Exception):
    """JAX found no TPU, or not as many chips as the cell asks for."""


def probe_device(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu:
        if dev["platform"] != "tpu":
            raise NoChip(f"no TPU: JAX found {dev['platform']}")
        if dev["count"] != chips:
            raise NoChip(f"the cell asks for {chips} chip(s), JAX found "
                         f"{dev['count']}")
        if dev["kind"] not in load_json(PEAKS)["devices"]:
            raise NoChip(f"device kind {dev['kind']!r} not in peaks.json")
    return dev


def memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class CompileLog(logging.Handler):
    """JAX's compile log over the set-up: seconds per program (compile,
    or load from the persistent cache) and the cache's hits and misses,
    so that ``PERF.md`` can say where set-up goes."""

    LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla",
               "jax._src.compiler")
    DONE = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.seconds = {}
        self.count = self.hits = self.misses = 0
        # any thread that compiles logs here (feed producers, streams)
        self._mu = threading.Lock()

    def emit(self, record):
        msg = record.getMessage()
        m = self.DONE.search(msg)
        with self._mu:
            if m:
                self.count += 1
                self.seconds[m.group(1)] = (
                    self.seconds.get(m.group(1), 0.0) + float(m.group(2)))
            elif msg.startswith("Persistent compilation cache hit"):
                self.hits += 1
            elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
                self.misses += 1

    @contextlib.contextmanager
    def watching(self):
        import jax

        flag = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        saved = []
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            saved.append((lg, lg.level, lg.propagate))
            if lg.getEffectiveLevel() > logging.WARNING:
                lg.setLevel(logging.WARNING)
            lg.propagate = False
            lg.addHandler(self)
        try:
            yield self
        finally:
            for lg, level, prop in saved:
                lg.removeHandler(self)
                lg.setLevel(level)
                lg.propagate = prop
            jax.config.update("jax_log_compiles", flag)

    def summary(self) -> str:
        top = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:4]
        return (f"{self.count} programs compiled or loaded in "
                f"{sum(self.seconds.values()):.1f} s (persistent cache: "
                f"{self.hits} hits, {self.misses} misses); longest "
                + ", ".join(f"{n} {t:.1f} s" for n, t in top))


class FullCollections:
    """Python's full (generation 2) collections, as (start, seconds) on
    the host clock: a pause of the whole process that the run's stderr
    reports beside the window's stalls."""

    def __init__(self):
        self.spans = []
        self._t = None

    def __call__(self, phase: str, info: dict):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.spans.append((self._t, time.perf_counter() - self._t))
            self._t = None

    @contextlib.contextmanager
    def watching(self):
        import gc

        gc.callbacks.append(self)
        try:
            yield self
        finally:
            gc.callbacks.remove(self)


class Window:
    """The measured window, opened from the client's ``get_work`` gate.

    A traced run traces the window's first ``TRACE_SECONDS`` (the whole
    window's trace overflows the device's trace buffers on the
    verify-heavy cells); the per-layer metrics are read over that slice,
    ``[t_start, t_mark)``, and the end-to-end ones over the window."""

    def __init__(self, seconds: float, n_warm: int, on_open, on_unit,
                 trace_dir=None, fault=None):
        self.seconds = seconds
        self.fault = fault
        self.n_warm = n_warm
        self.on_open = on_open
        self.on_unit = on_unit
        self.trace_dir = trace_dir
        self.t_start = self.t_end = self.t_mark = None
        self.i_start = None
        self.anchor_ns = None
        self.registry = None
        self.starve = [None, None]
        self.compiles = None
        self.stop_s = None               # seconds stop_trace took
        self._stack = contextlib.ExitStack()
        self._timer = None

    def gate(self, i: int):
        from .loop import WindowClosed

        if self.t_start is None:
            if i < self.n_warm:
                return
            self._open(i)
        elif time.perf_counter() >= self.t_end:
            raise WindowClosed()
        self.on_unit(i - self.i_start)

    def _open(self, i: int):
        import jax

        from dwpa_tpu.analysis.recompile import watch_compiles

        self.on_open()
        slice_s = self.seconds
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # every Python call: 14M events
            # the runtime's own host events (level 2) are not read here;
            # the benchmark's TraceAnnotations are level 1
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            slice_s = min(self.seconds, TRACE_SECONDS)
        self.compiles = self._stack.enter_context(watch_compiles())
        if self.fault is not None:
            self._stack.enter_context(self.fault())
        self.starve[0] = starve_seconds(self.registry)
        a = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(TR.ANCHOR):
            b = time.perf_counter_ns()
        self.anchor_ns = (a + b) // 2
        self.i_start = i
        self.t_start = time.perf_counter()
        self.t_end = self.t_start + self.seconds
        self.t_mark = self.t_start + slice_s
        self._timer = threading.Timer(slice_s, self._at_mark)
        self._timer.start()

    def _at_mark(self):
        import jax

        self.starve[1] = starve_seconds(self.registry)
        if self.trace_dir is not None:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t

    def close(self):
        if self._timer is not None:
            self._timer.join()
        self._stack.close()


def starve_seconds(registry) -> float:
    snap = registry.snapshot().get("dwpa_feed_consumer_starve_seconds")
    return sum(s["sum"] for s in snap["samples"]) if snap else 0.0


class Run:
    """Everything one run recorded; metric readers take it whole."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def window_units(self) -> list:
        return self.rec.units[self.window.i_start:]

    @property
    def window_s(self) -> float:
        return self.window.seconds

    @property
    def layer_s(self) -> float:
        """Length of the span the per-layer metrics read."""
        return self.window.t_mark - self.window.t_start

    def pmks_until(self, t: float) -> float:
        """PMKs whose results the client fetched from the window's start
        until ``t``; the batch in flight at ``t`` counts by the share of
        its interval (from the fetch before it) that lies before ``t``,
        so the count does not jump by a whole batch when ``t`` moves
        across a fetch."""
        done, last = 0.0, self.window.t_start
        for at, n, e in self.rec.progress:
            if at < self.window.t_start:
                continue
            if at >= t:
                return done + n * e * (t - last) / (at - last)
            done += n * e
            last = at
        return done

    def pmks_in_window(self) -> float:
        return self.pmks_until(self.window.t_end)


def drive(spec: Spec, cell_name: str, seed: int, seconds: float,
          trace: bool, t_process: float, require_tpu: bool = True,
          overrides: dict = None, fault=None) -> Run:
    """Steps 1-4 of the module docstring; returns the recorded run.
    ``fault`` (see ``faults.py``) is planted while the window is open."""
    cell = spec.cell(cell_name)
    overrides = overrides or {}
    cfg = _merge(spec.config(cell["config"]), overrides.get("config"))
    params = _merge(spec.traffic(cell["traffic"]), overrides.get("traffic"))
    device = probe_device(cell["chips"], require_tpu)

    from dwpa_tpu.client.main import ClientConfig
    from dwpa_tpu.utils.compcache import enable_compilation_cache

    from .loop import (BenchClient, Recorder, Server, WindowClosed,
                       stderr_log, timed_engine)

    enable_compilation_cache()
    setup_log = CompileLog()
    setup_watch = contextlib.ExitStack()
    setup_watch.enter_context(setup_log.watching())
    traffic = T.build(params, seed)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    server = Server(os.path.join(WORK, "server"))
    try:
        server.publish(traffic.words, "bench")
        server.ingest(traffic.warm)
        server.release()

        def open_window():
            setup_watch.close()
            server.ingest(traffic.groups)

        def release_next(k: int):
            # past the last group the server has none: the client's
            # NoNets nap fails the run (``loop.BenchClient``)
            if k < len(traffic.groups):
                server.release(traffic.groups[k].essid)

        window = Window(seconds, len(traffic.warm), open_window,
                        release_next,
                        os.path.join(WORK, "trace") if trace else None,
                        fault)
        rec = Recorder()
        client = BenchClient(
            ClientConfig(base_url=server.url,
                         workdir=os.path.join(WORK, "client"),
                         **cfg["client"]),
            rec, window.gate, log=stderr_log)
        window.registry = client.registry
        gcs = FullCollections()
        try:
            with timed_engine(rec), gcs.watching():
                try:
                    client.run()
                except WindowClosed:
                    pass
        finally:
            window.close()
        nets = server.nets()
    finally:
        setup_watch.close()
        server.close()
    return Run(spec=spec, cell=cell, cfg=cfg, params=params, seed=seed,
               traffic=traffic, device=device, t_process=t_process,
               rec=rec, window=window, nets=nets,
               retries=client.registry.value(
                   "dwpa_client_engine_retries_total") or 0,
               setup_s=window.t_start - t_process,
               compiles=window.compiles.count,
               compile_names=sorted(set(window.compiles.names)),
               setup_log=setup_log, gcs=gcs.spans, trace=None)


def check(run: Run, threads: int = 8) -> dict:
    """The run's answers against the plain reference: every completed
    unit's nets, over the planted PSKs and a seeded sample of the
    dictionary's other words.  Returns ``{name: {value, max|min}}``."""
    nc = run.cfg["guarantees"]["nc_window"]
    words = run.traffic.words
    rng = np.random.default_rng([run.seed, 1])
    cands = list(dict.fromkeys(
        run.traffic.planted
        + [words[int(i)] for i in rng.integers(0, len(words), SAMPLE)]))
    units = [u for u in run.rec.units if u.result is not None]
    lines = {h: R.parse(h) for u in units for h in u.hashes}
    essids = {ln.essid for ln in lines.values()}
    submitted = [(R.parse(f.line.raw), f.psk)
                 for u in units for f in u.result.founds]
    pairs = {(c, e) for e in essids for c in cands if 8 <= len(c) <= 63}
    pairs |= {(psk, ln.essid) for ln, psk in submitted}
    pairs = sorted(pairs)
    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        pmk = dict(zip(pairs, ex.map(lambda p: R.pmk_from_psk(*p), pairs)))

    def pmk_of(psk, essid):
        return pmk[(psk, essid)]

    reported = {f.line.raw for u in units for f in u.result.founds}
    missed = extra = client_missed = 0
    for raw, ln in lines.items():
        want = R.found_psk(ln, cands, nc, pmk_of)
        state, got = run.nets.get(raw, (None, None))
        got = got if state == 1 else None
        if want is not None and got != want:
            missed += 1
        elif want is None and got is not None:
            extra += 1
        if want is not None and raw not in reported:
            client_missed += 1
    wrong = sum(1 for ln, psk in submitted
                if not R.cracks(ln, pmk_of(psk, ln.essid), nc))
    in_window = [u for u in run.window_units if u.result is not None]
    return {
        "missed": {"value": missed, "max": 0},
        "extra": {"value": extra, "max": 0},
        "client_missed": {"value": client_missed, "max": 0},
        "submitted_wrong": {"value": wrong, "max": 0},
        "engine_retries": {"value": int(run.retries), "max": 0},
        "put_work_rejected": {"value": sum(1 for u in units
                                           if not u.result.accepted),
                              "max": 0},
        "window_units": {"value": len(in_window), "min": 1},
        "nets_checked": {"value": len(lines), "min": 1},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"]
               for c in checks.values())


def reduce_trace(run: Run):
    w = run.window
    t = time.perf_counter()
    path = TR.find_xplane(w.trace_dir)
    run.trace = TR.reduce(path, w.anchor_ns, int(w.t_start * 1e9),
                          int(w.t_mark * 1e9))
    print(f"bench: trace: stop_trace {w.stop_s:.1f} s, read "
          f"{time.perf_counter() - t:.1f} s, {os.path.getsize(path)} "
          f"bytes, {run.trace['events']} events", file=sys.stderr,
          flush=True)


def result(run: Run, trace: bool, checks: dict, on_tpu: bool) -> dict:
    """The contract's last line.  Off the TPU it carries no metric: a
    CPU number is never reported under a device metric's name."""
    metrics = {}
    if on_tpu:
        for m in run.spec.metrics_for(run.cell["name"], trace):
            v = run.spec.reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    units = run.window_units
    failed = sum(1 for u in units
                 if u.result is None or not u.result.accepted)
    device = dict(run.device, memory_peak_bytes=run.memory_peak)
    out = {"correct": passed(checks), "attempted": len(units),
           "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": TR.top_ops(run.trace),
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def run_cell(spec: Spec, cell: str, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             overrides: dict = None, fault=None) -> dict:
    run = drive(spec, cell, seed, seconds, trace, t_process,
                require_tpu=require_tpu, overrides=overrides, fault=fault)
    run.memory_peak = memory_peak()
    on_tpu = run.device["platform"] == "tpu"
    if trace and on_tpu:
        reduce_trace(run)
    checks = check(run)
    units = run.window_units
    done = [u for u in units if u.t_ack is not None
            and u.t_ack <= run.window.t_end]
    print(f"bench: set-up {run.setup_log.summary()}", file=sys.stderr)
    print(f"bench: {cell} seed {seed}: setup {run.setup_s:.3f} s, "
          f"{len(units)} units in the window ({len(done)} acknowledged "
          f"inside it), {run.pmks_in_window():.0f} PMKs inside it, "
          f"{run.compiles} compiles {run.compile_names}", file=sys.stderr,
          flush=True)
    w = run.window
    gcs = [d for t, d in run.gcs if w.t_start <= t < w.t_end]
    print(f"bench: {len(gcs)} full collections in the window, "
          f"{sum(gcs):.3f} s, longest {max(gcs, default=0.0):.3f} s",
          file=sys.stderr, flush=True)
    return result(run, trace, checks, on_tpu)


def report(res: dict):
    """The checks as the last lines of stderr, then the result line as
    the last line of stdout."""
    for name, c in res["checks"].items():
        lim = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name}: {c['value']} (limit {lim})", file=sys.stderr,
              flush=True)
    print(json.dumps(res), flush=True)


def main(argv=None, t_process: float = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    p = argparse.ArgumentParser(description="One run of one benchmark cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative integer")
    try:
        spec = Spec()
        res = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_process)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    report(res)
    return 0
