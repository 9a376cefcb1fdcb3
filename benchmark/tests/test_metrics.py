"""The metric readers on a synthetic run record."""

import pytest

from benchmark.harness import Run
from benchmark.spec import Spec


class _W:
    seconds = 10.0
    t_start, t_end, t_mark = 100.0, 110.0, 106.0
    i_start = 1
    starve = [2.0, 2.6]


class _U:
    def __init__(self, t_get, t_ack):
        self.t_get, self.t_ack = t_get, t_ack


class _R:
    # (host time a batch's results were fetched, candidates, ESSIDs)
    progress = [(99.0, 500, 1),               # warm-up: before the window
                (102.0, 1000, 1), (104.0, 1000, 2), (111.0, 1000, 1)]
    units = [_U(90.0, 99.5),                   # the warm-up unit
             _U(100.0, 103.0), _U(103.0, 105.0), _U(105.0, 112.0)]


@pytest.fixture
def run():
    return Run(window=_W(), rec=_R(), setup_s=42.0, compiles=3, trace=None)


def read(name, run):
    return Spec().reader(name).read(run)


def test_pmk_per_s_counts_the_batch_in_flight_by_its_share(run):
    # 1000 + 2 x 1000 inside, then 6/7 of the batch fetched at 111
    assert read("pmk_per_s", run) == pytest.approx(
        (3000 + 1000 * 6 / 7) / 10.0)


def test_counters_and_setup(run):
    assert read("feed_starve_share", run) == pytest.approx(0.1)
    assert read("window_compiles", run) == 3
    assert read("setup_s", run) == 42.0


@pytest.mark.parametrize("name", ["device_idle_share", "pbkdf2_kernel_share",
                                  "pbkdf2_kernel_rate"])
def test_trace_metrics_read_nothing_without_a_trace(run, name):
    assert read(name, run) is None


def test_trace_metrics(run):
    run.trace = {"window_s": 6.0, "busy_s": 4.5, "chips": 1,
                 "op_s": {"%pbkdf2_sha1_pmk_pallas.1": 3.0, "%while.1": 1.5}}
    assert read("device_idle_share", run) == pytest.approx(0.25)
    assert read("pbkdf2_kernel_share", run) == pytest.approx(0.5)
    # PMKs until the mark (106): 1000 + 2000 + 2/7 of the batch fetched
    # at 111, over 3.0 kernel seconds
    assert read("pbkdf2_kernel_rate", run) == pytest.approx(
        (3000 + 1000 * 2 / 7) / 3.0)
