"""dwpa_tpu.obs unit tests: registry semantics (types, labels, merge,
Prometheus rendering), span nesting + the device-sync hook, and the
logging config (console format preserved; DWPA_LOG=json structured).
"""

import io
import json
import logging

import pytest

from dwpa_tpu.obs import (MetricsRegistry, SpanTracer, allgather_json,
                          default_registry, is_emitter, merged_slice_snapshot,
                          setup_logging)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("dwpa_t_total", "things")
    c.inc()
    c.labels(kind="a").inc(3)
    assert r.value("dwpa_t_total") == 1
    assert r.value("dwpa_t_total", kind="a") == 3

    g = r.gauge("dwpa_t_gauge")
    g.set(5)
    g.dec(2)
    assert r.value("dwpa_t_gauge") == 3
    with pytest.raises(TypeError):
        c.set(1)  # counters don't set
    with pytest.raises(TypeError):
        g.observe(1)  # gauges don't observe

    h = r.histogram("dwpa_t_seconds", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5)
    h.observe(50)
    snap = r.snapshot()["dwpa_t_seconds"]["samples"][0]
    assert snap["count"] == 3 and snap["sum"] == 55.5
    assert snap["buckets"] == [1, 1, 1]  # per-bound + overflow


def test_family_registration_idempotent_but_type_strict():
    r = MetricsRegistry()
    a = r.counter("dwpa_t_total", "first help wins")
    b = r.counter("dwpa_t_total", "ignored")
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("dwpa_t_total")


def test_prometheus_rendering_escapes_and_cumulates():
    r = MetricsRegistry()
    r.counter("dwpa_t_total", 'help with \\ and\nnewline').labels(
        q='va"l\nue').inc()
    h = r.histogram("dwpa_t_seconds", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    text = r.render_prometheus()
    assert '# HELP dwpa_t_total help with \\\\ and\\nnewline' in text
    assert 'dwpa_t_total{q="va\\"l\\nue"} 1' in text
    # cumulative buckets: le="1" holds 1, +Inf holds all 2
    assert 'dwpa_t_seconds_bucket{le="1"} 1' in text
    assert 'dwpa_t_seconds_bucket{le="+Inf"} 2' in text
    assert 'dwpa_t_seconds_count 2' in text
    assert json.loads(r.render_json())  # JSON form parses


def test_snapshot_merge_sums_everything():
    a, b = MetricsRegistry(), MetricsRegistry()
    for r, n in ((a, 2), (b, 5)):
        r.counter("dwpa_t_total").inc(n)
        r.gauge("dwpa_t_pmks").labels(**{"pass": "2"}).set(n * 100)
        r.histogram("dwpa_t_seconds", buckets=(1.0,)).observe(n)
    merged = MetricsRegistry()
    merged.merge_snapshot(a.snapshot())
    merged.merge_snapshot(b.snapshot())
    assert merged.value("dwpa_t_total") == 7
    # additive gauges SUM: per-host PMK/s -> slice PMK/s
    assert merged.value("dwpa_t_pmks", **{"pass": "2"}) == 700
    hist = merged.snapshot()["dwpa_t_seconds"]["samples"][0]
    assert hist["count"] == 2 and hist["sum"] == 7


def test_merge_rejects_mismatched_buckets():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("dwpa_t_seconds", buckets=(1.0,)).observe(0.5)
    b.histogram("dwpa_t_seconds", buckets=(2.0,)).observe(0.5)
    m = MetricsRegistry()
    m.merge_snapshot(a.snapshot())
    with pytest.raises(ValueError, match="bucket bounds"):
        m.merge_snapshot(b.snapshot())


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_record_histogram():
    r = MetricsRegistry()
    t = SpanTracer(r)
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.records()
    assert (inner["name"], inner["parent"], inner["depth"]) == \
        ("inner", "outer", 1)
    assert (outer["name"], outer["parent"], outer["depth"]) == \
        ("outer", None, 0)
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    assert r.value("dwpa_span_seconds", span="inner") == 1


def test_span_stack_recovers_from_abandoned_child():
    """An exception that skips a child's stop must not wedge the stack:
    stopping the parent discards the abandoned child."""
    t = SpanTracer(MetricsRegistry())
    outer = t.start("outer")
    t.start("abandoned")  # never stopped
    outer.stop()
    with t.span("after") as sp:
        pass
    assert sp.depth == 0  # stack fully unwound
    names = [x["name"] for x in t.records()]
    assert names == ["outer", "after"]


def test_span_stop_idempotent_and_sync_callable_runs_before_clock():
    t = SpanTracer(MetricsRegistry())
    ran = []
    sp = t.start("s")
    sp.stop(sync=lambda: ran.append(1))
    first = sp.seconds
    assert ran == [1]
    assert sp.stop() == first  # second stop: no re-record
    assert len(t.records("s")) == 1


def _profiled(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` trace written to
    ``tmp_path``; returns the trace's ``dwpa:`` host events as
    ``(name, start_ns, end_ns, stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dwpa:"):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_spans_write_profiler_annotations(tmp_path):
    """Every span is a ``dwpa:<name>`` event of the profiler trace,
    nested as the spans nest, with its arguments; the start()/stop()
    form and a child abandoned by an exception close theirs too."""
    t = SpanTracer(MetricsRegistry())
    feed = SpanTracer(MetricsRegistry(), args={"feed": "pass2"})

    def body():
        with t.span("work_unit", unit="0123abcd"):
            with t.span("pass2"):
                with feed.span("feed:wait"):
                    pass
        sp = t.start("prewarm")
        sp.stop()
        with pytest.raises(RuntimeError):
            with t.span("outer"):
                t.start("abandoned")  # never stopped
                raise RuntimeError("skip the child's stop")

    evs = {name: (s, e, stats) for name, s, e, stats in _profiled(tmp_path,
                                                                 body)}
    assert set(evs) == {"dwpa:work_unit", "dwpa:pass2", "dwpa:feed:wait",
                        "dwpa:prewarm", "dwpa:outer", "dwpa:abandoned"}

    def inside(child, parent):
        return evs[parent][0] <= evs[child][0] <= evs[child][1] \
            <= evs[parent][1]

    assert inside("dwpa:pass2", "dwpa:work_unit")
    assert inside("dwpa:feed:wait", "dwpa:pass2")
    assert inside("dwpa:abandoned", "dwpa:outer")
    assert evs["dwpa:work_unit"][2] == {"unit": "0123abcd"}
    assert evs["dwpa:feed:wait"][2] == {"feed": "pass2"}
    assert evs["dwpa:work_unit"][1] <= evs["dwpa:prewarm"][0]
    assert [r["name"] for r in t.records()] == [
        "pass2", "work_unit", "prewarm", "outer"]


def test_span_annotation_closes_after_the_sync_fetch(tmp_path):
    """The device-sync rule holds for the annotation as for the clock:
    the ``sync=`` fetch runs inside the ``dwpa:`` event."""
    import time as _time

    t = SpanTracer(MetricsRegistry())

    def body():
        with t.span("collect", sync=lambda: _time.sleep(0.05)):
            pass

    (name, s, e, _), = _profiled(tmp_path, body)
    assert name == "dwpa:collect"
    assert e - s >= 0.05e9
    rec, = t.records("collect")
    assert 0.05 <= rec["t1"] - rec["t0"] <= (e - s) / 1e9


# ---------------------------------------------------------------------------
# logging
# ---------------------------------------------------------------------------


def test_setup_logging_plain_preserves_console_format(monkeypatch):
    monkeypatch.delenv("DWPA_LOG", raising=False)
    buf = io.StringIO()
    logger = setup_logging(stream=buf, force=True)
    try:
        logging.getLogger("dwpa_tpu.client").info("challenge: passed")
        assert buf.getvalue() == "challenge: passed\n"
    finally:
        setup_logging(force=True)  # restore a default handler


def test_setup_logging_json_lines(monkeypatch):
    monkeypatch.setenv("DWPA_LOG", "json")
    buf = io.StringIO()
    setup_logging(stream=buf, force=True)
    try:
        logging.getLogger("dwpa_tpu.server.jobs").warning("tick failed")
        rec = json.loads(buf.getvalue())
        assert rec["level"] == "WARNING"
        assert rec["logger"] == "dwpa_tpu.server.jobs"
        assert rec["msg"] == "tick failed"
        assert rec["ts"].endswith("Z")
    finally:
        monkeypatch.delenv("DWPA_LOG")
        setup_logging(force=True)


def test_setup_logging_idempotent():
    a = setup_logging()
    n = len(a.handlers)
    b = setup_logging()
    assert a is b and len(b.handlers) == n


# ---------------------------------------------------------------------------
# multi-host plumbing (single-process paths; the collective forms ride
# the same process_allgather contract tests/test_multihost.py exercises)
# ---------------------------------------------------------------------------


def test_single_process_allgather_and_emitter():
    assert is_emitter()
    assert allgather_json({"a": 1}) == [{"a": 1}]


def test_merged_slice_snapshot_single_process():
    r = MetricsRegistry()
    r.gauge("dwpa_client_pmk_per_s").labels(**{"pass": "2"}).set(123.0)
    merged = merged_slice_snapshot(r)
    assert merged.value("dwpa_client_pmk_per_s", **{"pass": "2"}) == 123.0


def test_default_registry_is_shared():
    assert default_registry() is default_registry()
