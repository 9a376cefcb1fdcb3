"""A broken accelerator backend is an error, never "no accelerator".

Every device-or-host gate asks ``utils.device.on_tpu``.  When JAX's
backend fails to initialise, each gate must raise instead of quietly
moving the work to the host; and bench.py, which measures the chip
only, must exit non-zero without printing a result on a CPU backend.
"""

import os
import sys

import jax
import numpy as np
import pytest

from dwpa_tpu.gen import vendors
from dwpa_tpu.models import m22000 as m
from dwpa_tpu.server.precrack import PmkBatcher
from dwpa_tpu.utils.device import on_tpu


class BackendDown(RuntimeError):
    pass


@pytest.fixture
def broken_backend(monkeypatch):
    def devices(*a, **k):
        raise BackendDown("TPU backend failed to initialise")

    monkeypatch.setattr(jax, "devices", devices)


def test_on_tpu_false_on_cpu():
    assert on_tpu() is False


def test_engine_kernel_choice_raises(broken_backend):
    s1, s2 = m.essid_salt_blocks(b"gate")
    with pytest.raises(BackendDown):
        m._pmk_impl(np.zeros((8, 16), np.uint32), s1, s2)


def test_precrack_device_gate_raises(broken_backend):
    with pytest.raises(BackendDown):
        PmkBatcher(device="auto").device_enabled()
    # explicit choices never ask the backend
    assert PmkBatcher(device="off").device_enabled() is False


def test_vendor_gates_raise(broken_backend):
    with pytest.raises(BackendDown):
        next(vendors.thomson_candidates("ABCDEF"))
    with pytest.raises(BackendDown):
        list(vendors.vendor_candidates(b"\x00\x11\x22\x33\x44\x55",
                                       b"SpeedTouchABCDEF"))
    with pytest.raises(BackendDown):
        next(vendors._thomson_search_device("ABCDEF", [4], [1]))


def test_bench_refuses_cpu(capsys, monkeypatch):
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.modules.pop("bench", None)
    import bench

    assert bench.main() != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU only" in out.err
