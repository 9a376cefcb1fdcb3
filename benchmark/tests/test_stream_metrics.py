"""The device-stream readers (``stream_wait_idle_share``,
``chip_busy_spread``) on a synthetic four-chip reduction, and on the
recorded one-chip trace, whose program runs no device stream."""

import pytest

from benchmark.harness import Run
from benchmark.spec import Spec
from benchmark.tests.test_spans import MS, _recorded_run

NEW = ("stream_wait_idle_share", "chip_busy_spread")


def read(name, run):
    return Spec().reader(name).read(run)


def _red():
    """One second on four chips, each chip's stream on a host line of its
    own.  Chip 0 is idle 100 ms, all of it inside its stream's waits;
    chip 1 idle 300 ms, 200 ms of it inside its waits; chip 2 idle 500 ms
    with no wait of its own (chip 0's wait over it does not count);
    chip 3 busy throughout.  The loop thread's pass spans do not count."""
    def wait(line, s, e, chip):
        return ((1, line), s * MS, e * MS, "stream:wait",
                {"device": f"tpu:{chip}"})

    spans = [
        ((1, 0), 0, 1000 * MS, "pass2", {}),
        wait(1, 0, 60, 0), wait(1, 900, 1000, 0), wait(1, 300, 700, 0),
        wait(2, 0, 200, 1),
        ((1, 3), 0, 200 * MS, "stream:dispatch", {"device": "tpu:2"}),
        ((1, 4), 0, 1000 * MS, "stream:collect", {"device": "tpu:3"}),
    ]
    idle = {0: [(0, 60 * MS), (960 * MS, 1000 * MS)],
            1: [(100 * MS, 300 * MS), (500 * MS, 600 * MS)],
            2: [(300 * MS, 800 * MS)],
            3: []}
    return {"w0": 0, "w1": 1000 * MS, "window_s": 1.0, "chips": 4,
            "loop": (1, 0), "spans": spans, "idle": idle, "modules": {}}


def _run(red, chips=4):
    return Run(trace={"chips": chips, "window_s": 1.0}, program_trace=red)


def test_stream_wait_idle_share_counts_each_chips_own_waits():
    # (100 + 100 + 0 + 0) ms over 4 chips, over 1 s
    assert read("stream_wait_idle_share", _run(_red())) == \
        pytest.approx(0.05)


def test_chip_busy_spread_is_max_less_min_busy():
    # busy 0.9, 0.7, 0.5, 1.0
    assert read("chip_busy_spread", _run(_red())) == pytest.approx(0.5)


def test_a_chip_without_operations_is_idle_throughout():
    red = _red()
    del red["idle"][3]
    assert read("chip_busy_spread", _run(red)) == pytest.approx(0.9)


def test_no_stream_wait_reads_nothing():
    red = _red()
    red["spans"] = [sp for sp in red["spans"] if sp[3] != "stream:wait"]
    assert read("stream_wait_idle_share", _run(red)) is None
    assert read("chip_busy_spread", _run(red)) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_a_trace(name):
    assert read(name, Run(trace=None)) is None


def test_a_one_chip_program_without_streams(tmp_path):
    """A slice of a ``pmkid-dict`` chip trace: one chip, no device
    stream, so no wait to read and no spread between chips."""
    run, _ = _recorded_run(tmp_path, "program")
    assert read("stream_wait_idle_share", run) is None
    assert read("chip_busy_spread", run) == 0.0
