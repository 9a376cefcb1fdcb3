"""Device-stream executor (dwpa_tpu.parallel.streams).

Layers under test:

- PARITY — ``crack_streams`` vs ``crack_blocks`` over the identical
  framed feed (mixed keyvers + mixed ESSIDs): same found list, same
  per-block ``on_batch`` sequence (the resume-framing contract), and a
  warm second run under the recompile sentinel at ``allowed=0``;
- RESUME — a stream run resumed at ``skip=k`` covers exactly the
  lockstep path's unskipped tail;
- TELEMETRY — per-device ``dwpa_stream_*`` series and the
  ``stream:dispatch``/``stream:collect`` spans;
- FAULTS — a crashing stream's unfinished blocks requeue onto a
  survivor (excluded-style retry) without breaking demux order or
  leaking threads; a block out of eligible streams surfaces as
  ``StreamError`` with its global offset.

Real-engine tests run 3 streams (each stream compiles its own
single-device step per hash kind, so the stream count bounds the
compile bill) and share ``BATCH = 32`` with tests/test_sched.py so the
lockstep compiles are reused within a tier-1 run.  Fault tests use
fake engines — no device work at all.
"""

import threading
import types

import jax
import pytest

from dwpa_tpu import testing as synth
from dwpa_tpu.feed import frame_blocks
from dwpa_tpu.models.m22000 import M22000Engine
from dwpa_tpu.obs import MetricsRegistry
from dwpa_tpu.obs.spans import SpanTracer
from dwpa_tpu.parallel import StreamError, StreamExecutor
from dwpa_tpu.parallel.streams import (default_feed_workers, device_label,
                                       streams_default)

BATCH = 32
NSTREAMS = 3


def _lines():
    """Mixed keyvers + mixed ESSIDs; NetD is never cracked so neither
    path early-stops and consumed counts stay comparable."""
    return [
        synth.make_pmkid_line(b"stream-pass-a", b"StreamNetA", seed="st1"),
        synth.make_eapol_line(b"stream-pass-b", b"StreamNetB", keyver=2,
                              seed="st2"),
        synth.make_eapol_line(b"stream-pass-c", b"StreamNetC", keyver=3,
                              seed="st3"),
        synth.make_pmkid_line(b"not-in-keyspace", b"StreamNetD", seed="st4"),
    ]


def _words():
    """5 blocks of 32; the three PSKs land in different blocks."""
    words = [b"stjunk%04d" % i for i in range(160)]
    words[3] = b"stream-pass-a"
    words[40] = b"stream-pass-b"
    words[100] = b"stream-pass-c"
    return words


def _keys(founds):
    return sorted((f.line.essid, f.psk, f.nc, f.endian, f.pmk)
                  for f in founds)


def _batch_log(founds):
    return sorted(f.psk for f in founds)


def _stream_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(("stream-", "sched-stream-"))]


# ---------------------------------------------------------------------------
# parity with the lockstep path
# ---------------------------------------------------------------------------


def test_streams_match_lockstep_and_stay_compiled(recompile_sentinel):
    """The tentpole contract: identical found lists AND an identical
    per-block on_batch sequence (ordered demux = unchanged resume
    framing), then a warm rerun with zero recompiles."""
    lines, words = _lines(), _words()
    devices = jax.devices()[:NSTREAMS]

    lock_eng = M22000Engine(lines, batch_size=BATCH)
    lock_log = []
    lock_founds = lock_eng.crack_blocks(
        frame_blocks(iter(words), lock_eng.batch_size),
        on_batch=lambda c, f: lock_log.append((c, _batch_log(f))))

    reg = MetricsRegistry()
    tracer = SpanTracer(reg)
    st_eng = M22000Engine(lines, batch_size=BATCH)
    st_log = []
    st_founds = st_eng.crack_streams(
        frame_blocks(iter(words), st_eng.batch_size),
        on_batch=lambda c, f: st_log.append((c, _batch_log(f))),
        devices=devices, registry=reg, tracer=tracer)

    assert _keys(st_founds) == _keys(lock_founds)
    assert [p for _, ps in st_log for p in ps]  # founds reported per block
    assert st_log == lock_log
    assert sum(c for c, _ in st_log) == len(words)
    # both engines pruned their live view identically
    assert {n.line.essid for n in st_eng.nets} == \
        {n.line.essid for n in lock_eng.nets} == {b"StreamNetD"}
    assert _stream_threads() == []

    # telemetry: every series labeled by device, spans from stream side
    labels = [device_label(d) for d in devices]
    total = sum(reg.value("dwpa_stream_blocks_total", device=lb) or 0
                for lb in labels)
    assert total == len(st_log)
    for lb in labels:
        busy = reg.value("dwpa_stream_busy_fraction", device=lb)
        if busy is not None:         # a stream that got no block sets none
            assert 0.0 <= busy <= 1.0
        depth = reg.value("dwpa_stream_queue_depth", device=lb)
        assert depth is None or depth >= 0
    names = {r["name"] for r in tracer.records()}
    assert {"stream:dispatch", "stream:collect"} <= names

    # warm rerun: every per-device step is already in _STEP_CACHE
    warm = M22000Engine(lines, batch_size=BATCH)
    with recompile_sentinel(allowed=0, label="warm stream rerun"):
        warm_founds = warm.crack_streams(
            frame_blocks(iter(words), warm.batch_size), devices=devices)
    assert _keys(warm_founds) == _keys(lock_founds)


def test_streams_resume_skip_equivalence():
    """A stream run resumed at skip=k equals the lockstep run over the
    same unskipped tail: same found list, same consumed floor, and the
    first block keeps the global offset ``skip``."""
    lines, words = _lines(), _words()
    skip = 64   # past pass-a AND pass-b; only pass-c remains
    tail = words[skip:]
    devices = jax.devices()[:NSTREAMS]

    lock_eng = M22000Engine(lines, batch_size=BATCH)
    lock_founds = lock_eng.crack_blocks(
        frame_blocks(iter(tail), lock_eng.batch_size, base_offset=skip))

    st_eng = M22000Engine(lines, batch_size=BATCH)
    st_log = []
    blocks = list(frame_blocks(iter(tail), st_eng.batch_size,
                               base_offset=skip))
    offsets = [b.offset for b in blocks]
    st_founds = st_eng.crack_streams(
        iter(blocks), on_batch=lambda c, f: st_log.append(c),
        devices=devices)

    assert offsets[0] == skip
    assert _keys(st_founds) == _keys(lock_founds)
    assert {f.psk for f in st_founds} == {b"stream-pass-c"}
    assert sum(st_log) == len(tail)


def test_streams_default_policy():
    """Single-process multi-device (the forced-8-CPU test mesh) turns
    streams on; the feed defaults to one producer per device."""
    assert jax.process_count() == 1 and jax.local_device_count() == 8
    assert streams_default() is True
    assert default_feed_workers() == 8


# ---------------------------------------------------------------------------
# fault injection (fake engines — no device work)
# ---------------------------------------------------------------------------


class _FakeNet:
    def __init__(self, line):
        self.line = line


class _FakeEngine:
    """The slice of the engine surface DeviceStream touches."""

    PIPELINE_DEPTH = 3

    def __init__(self, lines, fail_offsets=()):
        self.nets = [_FakeNet(ln) for ln in lines]
        self.groups = {b"X": list(self.nets)}
        self.fail_offsets = set(fail_offsets)
        self.seen = []

    def warm_groups(self):
        pass

    def _prepare_block(self, block):
        return block

    def _dispatch(self, prep):
        if prep.offset in self.fail_offsets:
            raise RuntimeError(f"injected fault at {prep.offset}")
        return prep

    def _collect(self, disp):
        self.seen.append(disp.offset)
        return []

    def remove(self, found):
        self.nets = [n for n in self.nets if n.line is not found.line]


def _fake_blocks(k, batch=32):
    return [types.SimpleNamespace(offset=i * batch, count=batch)
            for i in range(k)]


def _fake_devices(k):
    return [types.SimpleNamespace(platform="fake", id=i) for i in range(k)]


def test_stream_crash_requeues_to_survivor():
    """Stream 0 dies mid-run: its unfinished blocks go back to the
    queue with stream 0 excluded, the survivor completes them, demux
    order and counts are unchanged, and no stream thread leaks.  Each
    requeue is counted for stream 0's device and as an engine retry,
    so a fault the survivor hid still shows."""
    lines = [object(), object()]
    engines = {}

    def factory(device):
        fail = (64,) if device.id == 0 else ()
        engines[device.id] = _FakeEngine(lines, fail_offsets=fail)
        return engines[device.id]

    reg = MetricsRegistry()
    ex = StreamExecutor(factory, _fake_devices(2), registry=reg)
    blocks = _fake_blocks(6)
    log = []
    founds = ex.run(iter(blocks), on_batch=lambda c, f: log.append(c))
    assert founds == []
    assert log == [32] * 6                      # every block, in order
    assert len(ex.block_streams) == 6
    # the poisoned block (offset 64, seq 2) was completed by stream 1
    assert ex.block_streams[2] == 1
    assert 64 in engines[1].seen and 64 not in engines[0].seen
    assert _stream_threads() == []
    requeued = reg.value("dwpa_stream_requeues_total", device="fake:0")
    assert requeued >= 1                        # at least the poisoned one
    assert not reg.value("dwpa_stream_requeues_total", device="fake:1")
    assert reg.value("dwpa_client_engine_retries_total") == requeued


def test_stream_crash_out_of_streams_is_fatal():
    """With a single stream there is no survivor to requeue onto: the
    run surfaces a StreamError carrying a failed block's global offset
    and still joins every thread.  The poison sits on the FIRST block
    so the unretryable block is deterministic."""
    def factory(device):
        return _FakeEngine([object()], fail_offsets=(0,))

    ex = StreamExecutor(factory, _fake_devices(1))
    with pytest.raises(StreamError) as err:
        ex.run(iter(_fake_blocks(6)))
    assert err.value.offset == 0
    assert "injected fault" in str(err.value)
    assert _stream_threads() == []


def test_stream_crash_everywhere_exhausts_attempts():
    """A block that fails on EVERY stream runs out of eligible streams
    and aborts instead of cycling the queue forever."""
    def factory(device):
        return _FakeEngine([object()], fail_offsets=(0,))

    ex = StreamExecutor(factory, _fake_devices(2), max_attempts=5)
    with pytest.raises(StreamError) as err:
        ex.run(iter(_fake_blocks(1)))
    assert err.value.offset == 0
    assert _stream_threads() == []


def test_stream_feed_error_propagates():
    """A feeder exception (FeedError &co) aborts the run with the
    ORIGINAL exception type — the client's retry layer keys off it."""
    class _Boom(Exception):
        pass

    def feed():
        yield from _fake_blocks(2)
        raise _Boom("source died")

    def factory(device):
        return _FakeEngine([object()])

    ex = StreamExecutor(factory, _fake_devices(2))
    with pytest.raises(_Boom):
        ex.run(feed())
    assert _stream_threads() == []


def test_stream_found_dedup_and_cross_stream_prune():
    """Every block claims the same net: the demux reports it once
    (first block in global order wins) and the prune lands on the
    stream's engine at a later block boundary.  A single stream plus a
    slow prepare on later blocks makes the emitter-vs-worker
    interleaving deterministic enough to observe the prune."""
    import time

    line = object()

    class _Hit:
        def __init__(self):
            self.line = line

    class _HitEngine(_FakeEngine):
        def _collect(self, disp):
            super()._collect(disp)
            if disp.offset > 0:
                time.sleep(0.05)  # let the emitter push block 0's prune
            return [_Hit()]       # every block claims the same net

    engines = {}

    def factory(device):
        engines[device.id] = _HitEngine([line])
        return engines[device.id]

    ex = StreamExecutor(factory, _fake_devices(1))
    founds = ex.run(iter(_fake_blocks(4)))
    assert len(founds) == 1       # deduped by line identity
    assert engines[0].nets == []  # the prune reached the live view
    assert _stream_threads() == []
