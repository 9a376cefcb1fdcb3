"""dwpa_tpu.feed: framing determinism (the resume/lockstep contracts),
producer/consumer pipelining, fault-with-offset delivery, double-buffered
staging, and the engine/client integration of the candidate feed.

The framing tests pin the EXACT ``(mine, global_count)`` sequences of
the former ``client.main.shard_word_blocks`` (which now delegates to
``feed.framing``): resume skip-by-count and the SPMD-lockstep batch
shapes both hang off that framing, so it is compared against a naive
reference implementation across ragged geometries, not just spot
values.
"""

import itertools
import threading
import time
from collections import deque

import pytest

import jax

from dwpa_tpu import testing as tfx
from dwpa_tpu.feed import Block, CandidateFeed, DeviceStager, FeedError
from dwpa_tpu.feed import framing
from dwpa_tpu.feed.framing import frame_blocks, skip_stream
from dwpa_tpu.models.m22000 import M22000Engine
from dwpa_tpu.obs import MetricsRegistry


def _legacy_shard_word_blocks(words, nproc, pid, batch_size, pad_word=b""):
    """The pre-feed client slicer, verbatim — the reference the framing
    must reproduce exactly (it materialized batch_size * nproc words per
    block on EVERY host, which is what the feed framing fixes)."""
    words = iter(words)
    while True:
        block = list(itertools.islice(words, batch_size * nproc))
        if not block:
            return
        blk = min(batch_size, -(-len(block) // nproc))
        mine = block[pid * blk:(pid + 1) * blk]
        mine += [pad_word] * (blk - len(mine))
        yield mine, len(block)


def _per_word_frame_blocks(words, batch_size, nproc=1, pid=0, pad_word=b"",
                           base_offset=0):
    """The framer before slice framing, verbatim but for the watermark:
    one interpreter loop iteration per source word, pruning the window's
    left edge as the block grows — the reference for the whole ``Block``
    sequence the slice framer must reproduce."""
    blk_of = framing._blk
    it = iter(words)
    gsize = batch_size * nproc
    hi = (pid + 1) * batch_size
    offset = base_offset
    while True:
        buf = deque()
        c = 0
        for w in it:
            i = c
            c += 1
            if i < hi and pid * blk_of(i + 1, batch_size, nproc) <= i:
                buf.append((i, w))
            start = pid * blk_of(c, batch_size, nproc)
            while buf and buf[0][0] < start:
                buf.popleft()
            if c == gsize:
                break
        if c == 0:
            return
        blk = blk_of(c, batch_size, nproc)
        start = pid * blk
        mine = [w for i, w in buf if i < start + blk]
        nreal = len(mine)
        mine += [pad_word] * (blk - nreal)
        yield Block(offset=offset, count=c, words=mine, padded=(nreal == 0))
        offset += c
        if c < gsize:
            return


def _feed_threads():
    return [t for t in threading.enumerate() if t.name.startswith("dwpa-feed")]


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------


def test_framing_identical_to_legacy_slicer():
    """Satellite: each host materializes only its shard slice, but the
    emitted (mine, global_count) sequences are IDENTICAL to the old
    list(islice(...)) slicer — across full blocks, ragged tails, empty
    shards and degenerate stream lengths."""
    for n in (0, 1, 2, 5, 16, 47, 96, 97, 191, 200):
        words = [b"w%05d" % i for i in range(n)]
        for nproc in (1, 2, 3, 5):
            for pid in range(nproc):
                for bs in (4, 16):
                    got = [(b.words, b.count)
                           for b in frame_blocks(iter(words), bs,
                                                 nproc=nproc, pid=pid)]
                    ref = list(_legacy_shard_word_blocks(
                        iter(words), nproc, pid, bs))
                    assert got == ref, (n, nproc, pid, bs)


@pytest.mark.parametrize("batch_size", (1, 4, 16))
@pytest.mark.parametrize("nproc,pid",
                         [(n, p) for n in range(1, 5) for p in range(n)])
def test_slice_framing_matches_per_word_framer(monkeypatch, nproc, pid,
                                               batch_size):
    """The slice framer yields the per-word framer's exact ``Block``
    sequence (offset, count, words, padded) on every geometry: empty
    and one-word streams, exact multiples of the global block and one
    either side, with and without a base offset and pad word, from a
    one-pass generator, and with a chunk (hosts other than 0) of one
    word, a few words, and the default."""
    g = batch_size * nproc
    lengths = sorted({0, 1} | {k * g + d for k in (1, 2, 3)
                               for d in (-1, 0, 1)})
    for chunk in (1, 3, framing._CHUNK):
        monkeypatch.setattr(framing, "_CHUNK", chunk)
        for n in lengths:
            for kw in ({}, {"pad_word": b"<pad>", "base_offset": 1000}):
                def words():
                    return (b"w%05d" % i for i in range(n))

                got = [(b.offset, b.count, b.words, b.padded)
                       for b in frame_blocks(words(), batch_size,
                                             nproc=nproc, pid=pid, **kw)]
                ref = [(b.offset, b.count, b.words, b.padded)
                       for b in _per_word_frame_blocks(
                           words(), batch_size, nproc=nproc, pid=pid, **kw)]
                assert got == ref, (chunk, n, kw)


def test_client_shard_word_blocks_delegates():
    """The kept-for-compat client entry point rides the feed framing."""
    from dwpa_tpu.client.main import shard_word_blocks

    words = [b"w%05d" % i for i in range(2 * 3 * 16 + 11)]
    for pid in range(3):
        assert (list(shard_word_blocks(iter(words), 3, pid, 16))
                == list(_legacy_shard_word_blocks(iter(words), 3, pid, 16)))


def test_framing_buffers_only_the_host_slice():
    """The memory fix the delegation exists for: peak buffering stays
    well under the batch_size * nproc words the legacy slicer
    materialized (exactly batch_size for host 0 and for full blocks)."""
    bs, nproc = 64, 4
    words = [b"w%06d" % i for i in range(bs * nproc * 3 + 17)]
    for pid in range(nproc):
        mark = []
        list(frame_blocks(iter(words), bs, nproc=nproc, pid=pid,
                          watermark=mark))
        bound = (pid + 1) * (nproc - pid) * bs / nproc + 1
        assert max(mark) <= bound < bs * nproc, (pid, max(mark), bound)
    # host 0's buffer is exactly one slice
    mark0 = []
    list(frame_blocks(iter(words), bs, nproc=nproc, pid=0, watermark=mark0))
    assert max(mark0) == bs


def test_blocks_carry_global_offsets_and_counts():
    blocks = list(frame_blocks((b"c%04d" % i for i in range(150)), 64,
                               base_offset=1000))
    assert [(b.offset, b.count) for b in blocks] == \
        [(1000, 64), (1064, 64), (1128, 22)]
    assert not any(b.padded for b in blocks)


def test_empty_shard_is_an_all_padding_block(monkeypatch):
    """Satellite: the fake two-process harness — with the jax process
    geometry monkeypatched to a 2-host slice, a global block too short
    to reach host 1 still arrives there as an all-padding framed block
    (the lockstep dispatch ``_padding_prep`` needs), and the offsets
    keep advancing by the GLOBAL count on both hosts."""
    monkeypatch.setattr(jax, "process_count", lambda: 2)

    # global stream: one full block (2*bs) then a 1-word tail block —
    # host 1's slice of the tail is empty
    bs = 8
    words = [b"word%04d" % i for i in range(2 * bs + 1)]
    per_host = {}
    for pid in (0, 1):
        monkeypatch.setattr(jax, "process_index", lambda p=pid: p)
        feed = CandidateFeed(iter(words), batch_size=bs, producers=0,
                             registry=MetricsRegistry())
        per_host[pid] = list(feed)
        feed.close()
    # both hosts: same block count, same (offset, count) framing
    for pid in (0, 1):
        assert [(b.offset, b.count) for b in per_host[pid]] == \
            [(0, 2 * bs), (2 * bs, 1)]
    tail0, tail1 = per_host[0][-1], per_host[1][-1]
    assert tail0.words == [words[-1]] and not tail0.padded
    assert tail1.words == [b""] and tail1.padded  # all-padding, dispatched
    # resume offsets advance by the global count on BOTH hosts
    assert tail1.offset + tail1.count == len(words)


def test_skip_stream_counts_short_streams():
    assert skip_stream(iter(range(10)), 4) == 4
    assert skip_stream(iter(range(3)), 10) == 3
    assert skip_stream(iter(range(3)), 0) == 0


# ---------------------------------------------------------------------------
# the feed pipeline
# ---------------------------------------------------------------------------


def test_feed_delivers_in_order_with_telemetry():
    reg = MetricsRegistry()
    n = 1000
    feed = CandidateFeed((b"c%06d" % i for i in range(n)), batch_size=64,
                         registry=reg, name="t1")
    blocks = list(feed)
    feed.close()
    assert [b.offset for b in blocks] == [i * 64 for i in range(len(blocks))]
    assert sum(b.count for b in blocks) == n
    assert [w for b in blocks for w in b.words] == \
        [b"c%06d" % i for i in range(n)]
    # telemetry contract: the documented dwpa_feed_* names are live
    assert reg.value("dwpa_feed_blocks_total", feed="t1") == len(blocks)
    assert reg.value("dwpa_feed_candidates_total", feed="t1") == n
    assert reg.value("dwpa_feed_bytes_total", feed="t1") == 7 * n
    assert reg.value("dwpa_feed_queue_depth", feed="t1") is not None
    # starve histogram: one observation per consumer wait — each
    # consumed block's, and the one that ends the stream
    assert reg.value("dwpa_feed_consumer_starve_seconds",
                     feed="t1") == len(blocks) + 1
    # producer work landed in feed: spans
    assert reg.value("dwpa_span_seconds", span="feed:produce") == len(blocks)
    assert not _feed_threads()


def test_feed_backpressure_bounds_source_consumption():
    """A slow consumer must not let producers run away with the source:
    at most depth blocks are framed ahead of the consumer."""
    pulled = [0]

    def src():
        for i in range(100 * 16):
            pulled[0] += 1
            yield b"w%06d" % i

    feed = CandidateFeed(src(), batch_size=16, depth=2, producers=1,
                         registry=MetricsRegistry())
    taken = 0
    try:
        for _ in feed:
            taken += 1
            time.sleep(0.01)  # slow consumer
            # frames in flight <= depth; +1 block may be mid-framing
            assert pulled[0] <= (taken + 2 + 1) * 16
            if taken >= 6:
                break
    finally:
        feed.close()
    assert not _feed_threads()


def test_producer_fault_reraised_with_offset():
    def faulty():
        for i in range(200):
            if i == 150:
                raise ValueError("disk on fire")
            yield b"x%06d" % i

    feed = CandidateFeed(faulty(), batch_size=64, registry=MetricsRegistry())
    got = []
    with pytest.raises(FeedError) as e:
        for b in feed:
            got.append(b)
    feed.close()
    # two whole blocks delivered; the fault carries the failing block's
    # global offset and chains the original exception
    assert [b.offset for b in got] == [0, 64]
    assert e.value.offset == 128
    assert isinstance(e.value.__cause__, ValueError)
    assert "offset 128" in str(e.value)
    assert not _feed_threads()

    # a source that breaks partway through the SECOND block: the first
    # block still arrives, then the fault at the framing frontier (the
    # end of the last framed block), and nothing after it
    def breaks_in_block_two():
        for i in range(100):
            yield b"y%06d" % i
        raise ValueError("truncated gzip")

    for producers in (1, 0):
        feed = CandidateFeed(breaks_in_block_two(), batch_size=64,
                             producers=producers,
                             registry=MetricsRegistry())
        first = next(feed)
        assert (first.offset, first.count) == (0, 64)
        assert first.words == [b"y%06d" % i for i in range(64)]
        with pytest.raises(FeedError) as e:
            next(feed)
        assert e.value.offset == 64, producers
        assert isinstance(e.value.__cause__, ValueError)
        if producers:
            # the fault stands: no block is delivered after it
            with pytest.raises(FeedError):
                next(feed)
        else:
            assert next(feed, None) is None
        feed.close()
    assert not _feed_threads()


def test_inline_mode_runs_without_threads():
    before = set(threading.enumerate())
    feed = CandidateFeed((b"c%05d" % i for i in range(130)), batch_size=64,
                         producers=0, skip=10, registry=MetricsRegistry())
    assert feed.skipped == 10  # eager in inline mode
    blocks = list(feed)
    feed.close()
    assert set(threading.enumerate()) == before
    assert [(b.offset, b.count) for b in blocks] == [(10, 64), (74, 56)]
    # inline faults keep the offset contract
    def faulty():
        yield b"ok-000001"
        raise OSError("gone")

    feed = CandidateFeed(faulty(), batch_size=4, producers=0,
                         registry=MetricsRegistry())
    with pytest.raises(FeedError) as e:
        list(feed)
    assert e.value.offset == 0 and isinstance(e.value.__cause__, OSError)


def test_skip_fast_forward_and_words_view():
    n = 100
    feed = CandidateFeed((b"c%05d" % i for i in range(n)), batch_size=16,
                         skip=30, registry=MetricsRegistry())
    words = list(feed.words())
    feed.close()
    assert feed.skipped == 30
    assert words == [b"c%05d" % i for i in range(30, n)]
    # skip beyond the stream: everything consumed, nothing framed
    feed = CandidateFeed((b"c%05d" % i for i in range(5)), batch_size=16,
                         skip=30, registry=MetricsRegistry())
    assert list(feed) == []
    assert feed.skipped == 5
    feed.close()


def test_close_is_idempotent_and_unblocks_producers():
    feed = CandidateFeed((b"w%07d" % i for i in range(10 ** 6)),
                         batch_size=64, depth=2,
                         registry=MetricsRegistry())
    next(iter(feed))  # producers are live and backpressured
    feed.close()
    feed.close()
    assert not _feed_threads()


# ---------------------------------------------------------------------------
# staging + engine integration
# ---------------------------------------------------------------------------


def test_device_stager_stages_one_block_ahead():
    staged = []

    class FakeEngine:
        def _prepare_block(self, blk):
            staged.append(blk.offset)
            return ("prep", blk.offset)

    blocks = [Block(offset=i * 4, count=4, words=[b"w"] * 4)
              for i in range(3)]
    out = []
    for blk, prep in DeviceStager(FakeEngine(), iter(blocks)):
        # when block N is handed over, N+1's H2D is already enqueued
        assert staged[:len(out) + 2] == [b.offset
                                         for b in blocks[:len(out) + 2]]
        assert prep == ("prep", blk.offset)
        out.append(blk.offset)
    assert out == [0, 4, 8] and staged == [0, 4, 8]


def test_crack_blocks_finds_psk_and_reports_global_counts():
    psk = b"feed-psk-01"
    eng = M22000Engine([tfx.make_pmkid_line(psk, b"FeedNet", seed="cb1")],
                       batch_size=64)
    words = [b"no-%06d" % i for i in range(150)] + [psk]
    reg = MetricsRegistry()
    feed = CandidateFeed(iter(words), batch_size=64,
                         prepack=eng.host_packer(), registry=reg, name="cb")
    reports = []
    founds = eng.crack_blocks(
        feed, on_batch=lambda c, f: reports.append(c))
    feed.close()
    assert [f.psk for f in founds] == [psk]
    # stream-order accounting: cumulative consumed == block offsets+counts
    assert reports == [64, 64, 23]
    assert sum(reports) == len(words)


def test_crack_blocks_prepacked_matches_unpacked():
    """The producer-side native prepack must be an optimization, never a
    semantic change: same founds with and without it (and with the $HEX
    decode exercised through both paths)."""
    psk = b"prepack-psk7"
    words = ([b"chaff-%05d" % i for i in range(40)]
             + [b"$HEX[" + psk.hex().encode() + b"]"]
             + [b"x", b"tail-%05d" % 1])  # b"x" is length-filtered
    founds = {}
    for label, prepack in (("packed", True), ("plain", False)):
        eng = M22000Engine(
            [tfx.make_pmkid_line(psk, b"PrepackNet", seed="pp1")],
            batch_size=16)
        feed = CandidateFeed(
            iter(words), batch_size=16,
            prepack=eng.host_packer() if prepack else None,
            registry=MetricsRegistry())
        founds[label] = [f.psk for f in eng.crack_blocks(feed)]
        feed.close()
    assert founds["packed"] == founds["plain"] == [psk]


def test_crack_blocks_skips_invalid_block_but_reports_count():
    """A block with zero valid words (single-process) is not dispatched
    but its count still reaches on_batch — the resume contract."""
    eng = M22000Engine(
        [tfx.make_pmkid_line(b"skipblk-psk", b"SkipNet", seed="sb1")],
        batch_size=16)
    words = [b"x"] * 16 + [b"valid-%05d" % i for i in range(16)]
    feed = CandidateFeed(iter(words), batch_size=16,
                         prepack=eng.host_packer(),
                         registry=MetricsRegistry())
    reports = []
    eng.crack_blocks(feed, on_batch=lambda c, f: reports.append(c))
    feed.close()
    assert reports == [16, 16]


def test_stage_times_prepare_is_residual_with_prepack():
    """Satellite: with producer-side packing, the engine's "prepare"
    accumulator measures only the on-thread staging residual — the keys
    survive (API compat) but pack time lives in the feed's spans."""
    eng = M22000Engine(
        [tfx.make_pmkid_line(b"residual-psk", b"ResNet", seed="st1")],
        batch_size=64)
    assert set(eng.stage_times) == {"prepare", "dispatch", "collect"}
    reg = MetricsRegistry()
    feed = CandidateFeed((b"w%06d" % i for i in range(64 * 4)),
                         batch_size=64, prepack=eng.host_packer(),
                         registry=reg, name="res")
    eng.crack_blocks(feed)
    feed.close()
    # producer pack time was recorded to the feed span, not "prepare"
    assert reg.value("dwpa_span_seconds", span="feed:produce") == 4
    assert eng.stage_times["prepare"] < eng.stage_times["collect"] + \
        eng.stage_times["dispatch"] + 10  # smoke: keys populated, finite


def test_feed_wait_spans_are_the_starve_samples():
    """The consumer's waits are ``feed:wait`` spans, and the starve
    histogram observes exactly their seconds: one timing."""
    reg = MetricsRegistry()
    feed = CandidateFeed((b"c%06d" % i for i in range(500)), batch_size=32,
                         registry=reg, name="t2")
    blocks = list(feed)
    feed.close()
    waits = feed.tracer.records("feed:wait")
    assert len(waits) == len(blocks) + 1
    assert reg.value("dwpa_feed_consumer_starve_seconds",
                     feed="t2") == len(waits)
    snap = reg.snapshot()["dwpa_feed_consumer_starve_seconds"]["samples"]
    assert sum(s["sum"] for s in snap) == pytest.approx(
        sum(r["t1"] - r["t0"] for r in waits))
    assert feed.tracer.args == {"feed": "t2"}


@pytest.mark.parametrize("producers", (1, 0))
def test_feed_frame_spans_count_each_framing_call(producers):
    """Every framing call is a ``feed:frame`` span and one
    ``dwpa_feed_frame_seconds`` observation: one per framed block, plus
    the call that finds the stream's end."""
    reg = MetricsRegistry()
    feed = CandidateFeed((b"c%06d" % i for i in range(500)), batch_size=32,
                         producers=producers, registry=reg, name="t3")
    blocks = list(feed)
    feed.close()
    assert len(blocks) == 16
    frames = feed.tracer.records("feed:frame")
    assert len(frames) == len(blocks) + 1
    assert reg.value("dwpa_feed_frame_seconds", feed="t3") == len(frames)
    assert reg.value("dwpa_span_seconds", span="feed:frame") == len(frames)
    snap = reg.snapshot()["dwpa_feed_frame_seconds"]["samples"]
    assert sum(s["sum"] for s in snap) == pytest.approx(
        sum(r["t1"] - r["t0"] for r in frames))


def test_stage_times_are_the_engine_span_sums():
    """``stage_times`` is the per-engine sum of its ``engine:prepare``,
    ``engine:dispatch`` and ``engine:collect`` spans."""
    from dwpa_tpu.obs import SpanTracer

    tracer = SpanTracer(MetricsRegistry())
    eng = M22000Engine(
        [tfx.make_pmkid_line(b"span-psk-1", b"SpanNet", seed="sp1")],
        batch_size=64, tracer=tracer)
    eng.crack((b"word%06d" % i for i in range(64 * 3)))
    for stage in ("prepare", "dispatch", "collect"):
        recs = tracer.records("engine:" + stage)
        assert len(recs) == 3, stage
        assert eng.stage_times[stage] == pytest.approx(
            sum(r["t1"] - r["t0"] for r in recs)), stage
