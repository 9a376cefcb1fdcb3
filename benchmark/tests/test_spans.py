"""The readers of the program's own spans and step names
(``benchmark/spans.py``): on synthetic reductions, on the first recorded
trace (``window.xplane.pb``, from a program that names no step and
writes no ``dwpa:`` span), and on a slice of a ``pmkid-dict`` chip trace
that holds them."""

import json
import os
import shutil

import pytest

from benchmark import spans as SP
from benchmark import trace as TR
from benchmark.harness import Run
from benchmark.spec import Spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
NEW = ("verify_share", "unit_boundary_idle_share", "feed_wait_idle_share")


def read(name, run):
    return Spec().reader(name).read(run)


def _red():
    """One second: a unit with its passes and a feed wait, the boundary,
    then a unit and a pass still open when the trace stopped."""
    loop = (1, 0)
    spans = [
        (loop, 50 * MS, 600 * MS, "work_unit", {"unit": "0123abcd"}),
        (loop, 120 * MS, 200 * MS, "pass1", {}),
        (loop, 250 * MS, 580 * MS, "pass2", {}),
        (loop, 380 * MS, 520 * MS, "feed:wait", {"feed": "pass2"}),
        (loop, 585 * MS, 595 * MS, "submit", {}),
        (loop, 620 * MS, 640 * MS, "get_work", {}),
        (loop, 700 * MS, 705 * MS, "resume:write", {}),
        (loop, 850 * MS, 950 * MS, "feed:wait", {"feed": "pass2"}),
        ((1, 1), 0, 1000 * MS, "feed:produce", {"feed": "pass2"}),
    ]
    return {"w0": 0, "w1": 1000 * MS, "window_s": 1.0, "chips": 1,
            "loop": loop, "spans": spans,
            "idle": {0: [(0, 100 * MS), (400 * MS, 500 * MS),
                         (900 * MS, 1000 * MS)]},
            "modules": {0: [(100 * MS, 300 * MS, "jit_pbkdf2_pmk(1)"),
                            (300 * MS, 400 * MS, "jit_verify_eapol(2)"),
                            (350 * MS, 380 * MS, "jit_verify_assemble(3)"),
                            (500 * MS, 900 * MS, "jit_verify_cmac(4)")]}}


def test_flatten_names_each_piece_after_the_innermost_span():
    assert SP.flatten([(0, 10, "A"), (2, 5, "B"), (6, 8, "C"),
                       (12, 14, "D")]) == [
        (0, 2, "A"), (2, 5, "B"), (5, 6, "A"), (6, 8, "C"), (8, 10, "A"),
        (12, 14, "D")]


def test_spans_open_at_the_trace_stop_are_put_back():
    spans = SP.loop_spans(_red())
    # each a nanosecond before the first span it encloses
    assert (850 * MS - 1, 1000 * MS, "pass2", {}) in spans
    assert (700 * MS - 1, 1000 * MS, "work_unit", {}) in spans
    assert not any(n == "feed:produce" for _, _, n, _ in spans)


def test_loop_thread_without_a_closed_unit():
    """A slice inside one long unit holds no ``dwpa:work_unit``: the loop
    thread is the line with most of the loop's own spans."""
    red = _red()
    red["spans"] = [sp for sp in red["spans"] if sp[3] != "work_unit"]
    assert SP.loop_line(red["spans"]) == (1, 0)
    assert SP.loop_line([sp for sp in red["spans"]
                         if sp[3] == "feed:produce"]) is None
    spans = SP.loop_spans(red)
    assert spans[0][2] == "work_unit" and spans[0][1] == 1000 * MS


def test_idle_by_innermost_loop_span():
    by = SP.idle_by_span(_red())
    assert by == pytest.approx({"none": 0.05, "work_unit": 0.05,
                                "feed:wait": 0.15, "pass2": 0.05})


def test_idle_outside_and_inside_the_passes():
    red = _red()
    assert SP.idle_outside(red, SP.PASSES) == pytest.approx(0.1)
    assert SP.idle_inside(red, ("feed:wait",), within=SP.PASSES) == \
        pytest.approx(0.15)


def test_module_seconds_is_a_union_by_name():
    red = _red()
    assert SP.module_seconds(red, "verify") == pytest.approx(0.5)
    assert SP.module_seconds(red, "pbkdf2") == pytest.approx(0.2)
    assert SP.module_seconds(red, "rules") is None


def test_readers_on_a_reduction():
    run = Run(trace={}, program_trace=_red())
    assert read("verify_share", run) == pytest.approx(0.5)
    assert read("unit_boundary_idle_share", run) == pytest.approx(0.1)
    assert read("feed_wait_idle_share", run) == pytest.approx(0.15)


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_a_trace(name):
    assert read(name, Run(trace=None)) is None


class _Window:
    def __init__(self, trace_dir, rec):
        self.trace_dir = trace_dir
        self.anchor_ns = rec["anchor_ns"]
        self.t_start = rec["t0_ns"] / 1e9
        self.t_mark = rec["t1_ns"] / 1e9


def _recorded_run(tmp_path, stem):
    with open(os.path.join(DATA, f"{stem}.json")) as f:
        rec = json.load(f)
    shutil.copy(os.path.join(DATA, f"{stem}.xplane.pb"), tmp_path)
    run = Run(window=_Window(str(tmp_path), rec))
    run.trace = TR.reduce(os.path.join(DATA, f"{stem}.xplane.pb"),
                          rec["anchor_ns"], rec["t0_ns"], rec["t1_ns"])
    return run, rec


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(tmp_path, name):
    """``window.xplane.pb`` comes from a program that names no step and
    writes no ``dwpa:`` span: the readers return None and do not raise."""
    run, _ = _recorded_run(tmp_path, "window")
    assert read(name, run) is None
    assert SP.of(run)["loop"] is None


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """A second of a ``pmkid-dict`` window on a v5e chip: the end
    of a unit, the boundary, and the next unit's pass 1 and the start of
    its pass 2, which was still open when the trace stopped."""
    run, rec = _recorded_run(tmp_path_factory.mktemp("program"), "program")
    return run, rec["expect"]


def test_program_slice_reads_as_recorded(program):
    run, want = program
    red = SP.of(run)
    assert list(red["loop"]) == want["loop"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    for name in NEW:
        assert read(name, run) == pytest.approx(want[name]), name
    assert SP.idle_by_span(red) == pytest.approx(want["idle_by_span"])


def test_program_slice_names_its_steps(program):
    run, _ = program
    progs = {SP._program(n) for ev in SP.of(run)["modules"].values()
             for _, _, n in ev}
    assert progs == {"jit_pbkdf2_pmk", "jit_verify_pmkid"}
    assert 0 < read("verify_share", run) < 0.05


def test_idle_parts_are_disjoint_and_cover_the_device_idle(program):
    run, _ = program
    idle = read("device_idle_share", run)
    ub = read("unit_boundary_idle_share", run)
    fw = read("feed_wait_idle_share", run)
    assert ub > 0 and fw > 0
    assert ub + fw <= idle + 1e-12
    by = SP.idle_by_span(SP.of(run))
    assert sum(by.values()) == pytest.approx(idle * run.trace["window_s"])


def test_only_the_benchmark_gate_is_outside_every_span(program):
    """Every idle instant of the loop thread lies inside some ``dwpa:``
    span but the benchmark's gate before ``get_work``: well under a
    millisecond a boundary."""
    run, _ = program
    assert SP.idle_by_span(SP.of(run)).get("none", 0.0) < 0.001
