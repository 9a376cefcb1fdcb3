"""Loopback client <-> server integration tests.

Runs TpuCrackClient against make_wsgi_app entirely in-process: a
ServerAPI whose ``fetch`` invokes the WSGI app directly, so the complete
reference flow (help_crack.py:881-957) — challenge gate, get_work, dict
download + md5 check, two-pass crack, put_work, resume replay, autotune —
is exercised over the exact wire protocol with no sockets.
"""

import gzip
import hashlib
import io
import json
import os
import urllib.parse

import jax
import pytest

from dwpa_tpu import testing as tfx
from dwpa_tpu.client.main import ClientConfig, TpuCrackClient
from dwpa_tpu.client.protocol import NoNets, ServerAPI, VersionRejected
from dwpa_tpu.models import hashline as hl
from dwpa_tpu.obs import MetricsRegistry
from dwpa_tpu.obs import spans as obs_spans
from dwpa_tpu.parallel.streams import device_label
from dwpa_tpu.server import Database, ServerCore, make_wsgi_app

PSK = b"loopback-psk1"
ESSID = b"LoopbackNet"


class LoopbackAPI(ServerAPI):
    """ServerAPI whose transport is a direct WSGI call (no sockets)."""

    def __init__(self, app, **kw):
        kw.setdefault("max_tries", 1)
        kw.setdefault("sleep", lambda s: None)
        super().__init__("http://loopback/", **kw)
        self.app = app
        self.requests = []

    def fetch(self, url: str, data: dict = None, max_tries: int = None) -> bytes:
        parsed = urllib.parse.urlparse(url)
        body = json.dumps(data).encode() if data is not None else b""
        environ = {
            "REQUEST_METHOD": "POST" if data is not None else "GET",
            "PATH_INFO": parsed.path or "/",
            "QUERY_STRING": parsed.query,
            "CONTENT_LENGTH": str(len(body)),
            "wsgi.input": io.BytesIO(body),
            "REMOTE_ADDR": "127.0.0.1",
        }
        out = {}

        def start_response(status, headers):
            out["status"] = status

        resp = b"".join(self.app(environ, start_response))
        self.requests.append((environ["REQUEST_METHOD"], url))
        if not out["status"].startswith("200"):
            raise ConnectionError(f"{url}: {out['status']}")
        return resp


@pytest.fixture
def server(tmp_path):
    db = Database(":memory:")
    core = ServerCore(db, dictdir=str(tmp_path / "dicts"), capdir=str(tmp_path / "caps"))
    return core


def _add_dict(core, words, name="loop.txt.gz"):
    os.makedirs(core.dictdir, exist_ok=True)
    blob = gzip.compress(b"\n".join(words) + b"\n")
    path = os.path.join(core.dictdir, name)
    with open(path, "wb") as f:
        f.write(blob)
    dhash = hashlib.md5(blob).hexdigest()
    core.add_dict(f"dict/{name}", name, dhash, len(words), rules=None)
    return path, dhash


def _ingest(core, lines):
    core.add_hashlines(lines)
    core.db.x("UPDATE nets SET algo = ''")  # release to volunteers


def _client(server, tmp_path, registry=None, **cfg_kw):
    cfg_kw.setdefault("batch_size", 64)
    cfg_kw.setdefault("dictcount", 1)
    # Lockstep by default: on the forced-8-device 1-core test host the
    # stream path trades one fused 8-way execution for 8 serialized
    # single-device ones, several times slower at these toy batch
    # sizes.  test_metrics_after_one_work_unit opts back in ("auto")
    # and carries the stream-path assertions for the whole file.
    cfg_kw.setdefault("device_streams", "off")
    cfg = ClientConfig(base_url="http://loopback/",
                       workdir=str(tmp_path / "work"), **cfg_kw)
    api = LoopbackAPI(make_wsgi_app(server))
    return TpuCrackClient(cfg, api=api, log=lambda *a, **k: None,
                          registry=registry)


def test_full_round_trip(server, tmp_path):
    """get_work -> crack -> put_work: the net ends cracked server-side,
    the potfile records the found, and the lease is closed."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="rt1"),
                     tfx.make_eapol_line(PSK, ESSID, keyver=2, seed="rt2")])
    _add_dict(server, [b"nope-000001", PSK, b"nope-000002"])
    client = _client(server, tmp_path)

    assert client.challenge()
    work = client.api.get_work(client.dictcount)
    assert len(work["hashes"]) == 2  # same-SSID nets grouped into one unit
    res = client.process_work(work)

    assert res.accepted
    assert sorted(f.psk for f in res.founds) == [PSK, PSK]
    rows = server.db.q("SELECT n_state, pass FROM nets")
    assert all(r["n_state"] == 1 and r["pass"] == PSK for r in rows)
    assert server.db.q1("SELECT COUNT(*) c FROM n2d WHERE hkey IS NOT NULL")["c"] == 0
    # potfile written, resume cleared
    pot = open(client.potfile).read()
    assert PSK.decode() in pot
    assert not os.path.exists(client.resume_path)


def test_run_loop_with_challenge_gate(server, tmp_path):
    """client.run(): challenge gate passes, one unit processed end-to-end."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="rl1")])
    _add_dict(server, [PSK])
    client = _client(server, tmp_path, max_work_units=1)
    assert client.run() == 1
    assert server.db.q1("SELECT n_state FROM nets")["n_state"] == 1


def test_resume_replay_after_crash(server, tmp_path):
    """A resume snapshot from a crashed session is replayed instead of
    fetching new work (help_crack.py:745-763)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="rr1")])
    _add_dict(server, [PSK])
    crashed = _client(server, tmp_path)
    work = crashed.api.get_work(1)
    crashed._write_resume(work)  # simulated crash: resume left behind

    revived = _client(server, tmp_path)

    def fail_get_work(dictcount):
        raise AssertionError("must replay the resume, not fetch new work")

    revived.api.get_work = fail_get_work
    revived.cfg.max_work_units = 1
    # the challenge still gates a resumed session
    assert revived.challenge()
    replayed = revived._read_resume()
    assert replayed == work
    res = revived.process_work(replayed)
    assert res.accepted
    assert not os.path.exists(revived.resume_path)


def test_corrupt_resume_discarded(server, tmp_path):
    client = _client(server, tmp_path)
    with open(client.resume_path, "w") as f:
        f.write("{not json")
    assert client._read_resume() is None
    assert not os.path.exists(client.resume_path)


def test_resume_rejected_on_batch_size_change(server, tmp_path):
    """A resume written under a different -b must restart the unit, not
    skip-by-count: the batch size changes crack_rules' chunk boundaries,
    so the old done counter indexes a DIFFERENT candidate order and a
    replay would silently skip candidates that were never tried."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="bs1")])
    _add_dict(server, [PSK])
    crashed = _client(server, tmp_path, batch_size=64)
    work = crashed.api.get_work(1)
    work["_progress"] = {"done": 37, "cand": []}  # mid-unit checkpoint
    crashed._write_resume(work)
    assert work["_batch"] == 64  # stamped alongside _ver/_nproc

    # same build, same topology, different -b: the snapshot is discarded
    revived = _client(server, tmp_path, batch_size=32)
    assert revived._read_resume() is None
    assert not os.path.exists(revived.resume_path)

    # unchanged -b still replays (the stamp must not over-reject)
    crashed._write_resume(work)
    same = _client(server, tmp_path, batch_size=64)
    assert same._read_resume() == work


def test_metrics_after_one_work_unit(server, tmp_path, monkeypatch):
    """Telemetry contract for one loopback unit (the ISSUE-2 acceptance
    check): transport counters for get_work/put_work, a nonzero PMK/s
    gauge, the autotune/dictcount instruments, and well-nested spans."""
    annotated = []   # (span name, profiler-annotation arguments)
    real_annotate = obs_spans._annotate

    def annotate(name, args):
        annotated.append((name, dict(args)))
        return real_annotate(name, args)

    monkeypatch.setattr(obs_spans, "_annotate", annotate)
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="mt1")])
    _add_dict(server, [b"filler-000001", PSK, b"filler-000002"])
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg, device_streams="auto")
    # streams on under the 8-device single-process test mesh; a real
    # single-chip host (DWPA_TEST_TPU=1) legitimately stays lockstep
    use_streams = client._use_streams()
    assert use_streams == (jax.local_device_count() > 1
                           and jax.process_count() == 1)

    work = client.api.get_work(client.dictcount)
    res = client.process_work(work)
    assert res.accepted

    # transport counters: one get_work, one put_work, one dict download
    assert reg.value("dwpa_client_requests_total", endpoint="get_work") == 1
    assert reg.value("dwpa_client_requests_total", endpoint="put_work") == 1
    assert reg.value("dwpa_client_requests_total",
                     endpoint="dict_download") == 1
    # engine throughput: pass 2 carried the dict, so its PMK/s gauge is
    # live and positive (pass 1 may be too fast to register)
    assert reg.value("dwpa_client_pmk_per_s", **{"pass": "2"}) > 0
    # unit accounting + autotune: a sub-second unit tunes dictcount up
    assert reg.value("dwpa_client_work_units_total", accepted="true") == 1
    assert reg.value("dwpa_client_founds_total") == 1
    assert reg.value("dwpa_client_autotune_total", direction="up") == 1
    assert reg.value("dwpa_client_dictcount") == 2
    # no resume, no recompile-counter surprises recorded as gauges
    assert reg.value("dwpa_client_resume_skipped_total") is None

    # resilience telemetry (ISSUE-10): the retry/backoff/circuit/outbox
    # families are registered up front — present in the scrape even on a
    # fault-free run — the circuit rests CLOSED, and the unit's found
    # flowed through the outbox (journaled before put_work, then acked)
    from dwpa_tpu.client.protocol import CircuitBreaker

    assert reg.value("dwpa_client_circuit_state") == CircuitBreaker.CLOSED
    assert reg.value("dwpa_outbox_pending_total") == 1
    assert reg.value("dwpa_outbox_acked_total") == 1
    assert client.outbox.pending_count() == 0
    assert reg.series("dwpa_client_retries_total") == {}  # clean transport
    scrape = reg.render_prometheus()
    for fam in ("dwpa_client_retries_total", "dwpa_client_backoff_seconds",
                "dwpa_client_circuit_state", "dwpa_outbox_pending_total",
                "dwpa_outbox_acked_total"):
        assert fam in scrape, fam

    # spans: the work_unit span parents pass1/pass2/dict_download/
    # put_work, and every child interval nests inside it
    recs = client.tracer.records()
    by_name = {r["name"]: r for r in recs}
    for name in ("work_unit", "pass1", "pass2", "put_work",
                 "dict_download", "get_work"):
        assert name in by_name, (name, sorted(by_name))
    unit = by_name["work_unit"]
    for name, parent in (("pass1", "work_unit"), ("pass2", "work_unit"),
                         ("submit", "work_unit"),
                         # put_work is the submission's transport call
                         ("put_work", "submit"),
                         # the lazy dict fetch fires when pass 2 first
                         # pulls its stream, so it nests under pass2
                         ("dict_download", "pass2")):
        child = by_name[name]
        assert child["parent"] == parent, child
        assert unit["t0"] <= child["t0"] <= child["t1"] <= unit["t1"], child
    assert by_name["get_work"]["parent"] is None
    # span durations also land in the registry histogram
    assert reg.value("dwpa_span_seconds", span="work_unit") == 1

    # candidate-feed telemetry (ISSUE-3): both passes consumed from the
    # feed, so the dwpa_feed_* family is live per pass, block counts are
    # positive, and the candidate counters cover the unit's stream
    for feed_name in ("pass1", "pass2"):
        assert reg.value("dwpa_feed_blocks_total", feed=feed_name) >= 1, \
            feed_name
        assert reg.value("dwpa_feed_consumer_starve_seconds",
                         feed=feed_name) >= 1
    fed = sum(reg.series("dwpa_feed_candidates_total").values())
    assert fed >= res.candidates_tried
    assert reg.value("dwpa_span_seconds", span="feed:produce") >= 2

    # device-stream telemetry (ISSUE-8): the 8-device single-process
    # test mesh turns streams on by default, so every pass ran as
    # per-device streams — blocks land in the per-device counter and
    # the stream spans are traced alongside the pass spans
    if use_streams:
        stream_blocks = reg.series("dwpa_stream_blocks_total")
        assert stream_blocks and sum(stream_blocks.values()) >= 2  # 2 passes
        for labels, busy in reg.series("dwpa_stream_busy_fraction").items():
            assert 0.0 <= busy <= 1.0, (labels, busy)
        for labels, depth in reg.series("dwpa_stream_queue_depth").items():
            assert depth >= 0, (labels, depth)
        assert {"stream:wait", "stream:dispatch", "stream:collect"} <= \
            {r["name"] for r in recs}
        # every stream waited at least on its first pull; the seconds
        # are its stream:wait spans', per device
        waits = reg.series("dwpa_stream_wait_seconds_total")
        assert len(waits) == jax.local_device_count()
        assert all(v >= 0 for v in waits.values())
        assert not any(reg.series("dwpa_stream_requeues_total").values())
        assert not reg.value("dwpa_client_engine_retries_total")
        # each stream span names its chip in the profiler trace
        labels = {device_label(d) for d in jax.local_devices()}
        stream_args = [a for n, a in annotated if n.startswith("stream:")]
        assert {a["device"] for a in stream_args} <= labels
        assert all("device" in a for a in stream_args)
        assert any(n == "stream:wait" for n, _ in annotated)


def test_streams_compile_nothing_after_warm_up(server, tmp_path,
                                              monkeypatch):
    """Device streams: after ``prewarm`` and one warm-up unit per group
    shape, each cracked in pass 1 (one block, so one stream runs it),
    a further unit of each shape compiles nothing on whichever streams
    pull its blocks."""
    from dwpa_tpu import parallel
    from dwpa_tpu.analysis import watch_compiles

    # two streams bound the compile bill of a CPU run
    mesh_of = parallel.default_mesh
    monkeypatch.setattr(parallel, "default_mesh", lambda devices=None:
                        mesh_of(devices or jax.devices()[:2]))
    words = [b"streamwarm-%06d" % i for i in range(1280)]   # 10 blocks
    _add_dict(server, words)
    # at 128 a pass-1 block holds both warm-up nets' candidates
    client = _client(server, tmp_path, device_streams="on", batch_size=128)
    assert client._use_streams()
    client.prewarm()

    def unit(essid, psks, seed):
        _ingest(server, [tfx.make_pmkid_line(p, essid, seed=f"{seed}-{i}")
                         for i, p in enumerate(psks)])
        res = client.process_work(client.api.get_work(client.dictcount))
        assert res.accepted and res.founds

    # warm-up units: the ESSID is the PSK, a pass-1 candidate
    unit(b"WarmOneNet", [b"WarmOneNet"], "sw1")
    unit(b"WarmTwoNet", [b"WarmTwoNet"] * 2, "sw2")
    with watch_compiles() as comp:
        # a find deep in the dictionary; the other net runs it through
        unit(b"WindowTwo", [words[1000], b"not-in-the-dict"], "sw3")
        unit(b"WindowOne", [words[1200]], "sw4")
    assert comp.count == 0, comp.names


def test_pmkstore_metrics_and_warm_unit(server, tmp_path):
    """PMK-store loopback contract (the ISSUE-4 acceptance check): with
    --pmk-cache-dir set, one work unit surfaces the dwpa_pmkstore_*
    metric set in the registry (and so in the ?metrics scrape rendering),
    and a REPLAY of the same unit serves its candidates from the cache —
    hits recorded, the PSK still cracked through cached PMKs."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="pm1")])
    _add_dict(server, [b"cacheable-%06d" % i for i in range(30)] + [PSK])
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg,
                     pmk_cache_dir=str(tmp_path / "pmkcache"))

    work = client.api.get_work(client.dictcount)
    res = client.process_work(dict(work))
    assert res.accepted and [f.psk for f in res.founds] == [PSK]
    # cold unit: the dwpa_pmkstore_* family is live — misses counted,
    # every derived PMK written back, names present in the scrape form
    assert reg.value("dwpa_pmkstore_misses_total") > 0
    assert reg.value("dwpa_pmkstore_writes_total") > 0
    text = reg.render_prometheus()
    for name in ("dwpa_pmkstore_hits_total", "dwpa_pmkstore_misses_total",
                 "dwpa_pmkstore_writes_total", "dwpa_pmkstore_bytes",
                 "dwpa_pmkstore_hit_ratio"):
        assert name in text, name

    # warm replay of the same unit (server-side state reset): the dict
    # stream repeats, so pass 2 runs on cache hits
    server.db.x("UPDATE nets SET n_state = 0, pass = NULL, algo = ''")
    hits_before = reg.value("dwpa_pmkstore_hits_total") or 0
    res2 = client.process_work(dict(work))
    assert res2.accepted and [f.psk for f in res2.founds] == [PSK]
    assert reg.value("dwpa_pmkstore_hits_total") > hits_before
    assert 0 < reg.value("dwpa_pmkstore_hit_ratio") <= 1


def test_dictcache_metrics_and_warm_unit(server, tmp_path):
    """Packed-dict-cache loopback contract (the ISSUE-9 acceptance
    check): with --dict-cache-dir set, the first unit cold-streams the
    dict while writing the packed cache (misses counted, bytes on
    disk), and a REPLAY of the same unit serves pass 2 from mmap'd
    packed blocks — hits recorded, the warm words/s gauge live, the PSK
    still cracked with the identical found list."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="dc1")])
    _add_dict(server, [b"cacheable-%06d" % i for i in range(30)] + [PSK])
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg,
                     dict_cache_dir=str(tmp_path / "dictcache"))

    work = client.api.get_work(client.dictcount)
    res = client.process_work(dict(work))
    assert res.accepted and [f.psk for f in res.founds] == [PSK]
    # cold unit: blocks streamed past the cache, the entry committed
    assert reg.value("dwpa_dictcache_miss_blocks_total") > 0
    assert not reg.value("dwpa_dictcache_hit_blocks_total")
    assert reg.value("dwpa_dictcache_bytes") > 0
    text = reg.render_prometheus()
    for name in ("dwpa_dictcache_hit_blocks_total",
                 "dwpa_dictcache_miss_blocks_total",
                 "dwpa_dictcache_bytes", "dwpa_dictcache_words_per_s"):
        assert name in text, name

    # warm replay of the same unit (server-side state reset): pass 2
    # now serves pre-packed blocks, zero gunzip, zero re-packing
    server.db.x("UPDATE nets SET n_state = 0, pass = NULL, algo = ''")
    misses_before = reg.value("dwpa_dictcache_miss_blocks_total")
    res2 = client.process_work(dict(work))
    assert res2.accepted and [f.psk for f in res2.founds] == [PSK]
    assert reg.value("dwpa_dictcache_hit_blocks_total") > 0
    assert reg.value("dwpa_dictcache_miss_blocks_total") == misses_before
    assert reg.value("dwpa_dictcache_words_per_s", feed="warm") > 0


def test_rules_metrics_in_loopback_unit(server, tmp_path):
    """Mesh-aggregate feed telemetry contract (the ISSUE-11 acceptance
    check): a rules unit surfaces the device-expansion counters — every
    (word, rule) pair lands in EXACTLY one of
    dwpa_rules_device_expanded_total or
    dwpa_rules_host_fallback_total{reason="purge"|"overflow"} — and the
    rules:expand span is traced inside pass 2."""
    mangled = b"METRICWORD9!"  # 'metricword9!' through 'u'
    _ingest(server, [tfx.make_pmkid_line(mangled, ESSID, seed="rm1")])
    words = [b"metricword9!", b"metricfill-1", b"metricfill-2", b"y" * 70]
    os.makedirs(server.dictdir, exist_ok=True)
    blob = gzip.compress(b"\n".join(words) + b"\n")
    open(os.path.join(server.dictdir, "rm.txt.gz"), "wb").write(blob)
    # ':' and 'u' expand on device; '@a' purges on the host interpreter
    server.add_dict("dict/rm.txt.gz", "rm.txt.gz",
                    hashlib.md5(blob).hexdigest(), len(words),
                    rules=":\nu\n@a\n")
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg)

    work = client.api.get_work(1)
    res = client.process_work(work)
    assert res.accepted and [f.psk for f in res.founds] == [mangled]

    # 3 eligible bases x 2 device rules; 3 x 1 purge rule host-applied;
    # the 70-byte base falls back to the host for ALL 3 rules
    dev = reg.value("dwpa_rules_device_expanded_total")
    purge = reg.value("dwpa_rules_host_fallback_total", reason="purge")
    over = reg.value("dwpa_rules_host_fallback_total", reason="overflow")
    assert dev == 3 * 2
    assert purge == 3 * 1
    assert over == 1 * 3
    # conservation: the split is a partition of the expanded keyspace
    assert dev + purge + over == len(words) * 3
    for name in ("dwpa_rules_device_expanded_total",
                 "dwpa_rules_host_fallback_total"):
        assert name in reg.render_prometheus(), name

    # the expansion span fires inside the pass2 interval
    recs = client.tracer.records()
    spans = [r for r in recs if r["name"] == "rules:expand"]
    assert spans
    p2 = next(r for r in recs if r["name"] == "pass2")
    assert all(p2["t0"] <= s["t0"] <= s["t1"] <= p2["t1"] for s in spans)


def test_potfile_fsync_per_found(server, tmp_path, monkeypatch):
    """Potfile appends are flushed AND fsynced per found: a crash right
    after put_work must not lose the only local copy of a cracked PSK
    to the page cache."""
    import dwpa_tpu.client.main as cm

    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(cm.os, "fsync",
                        lambda fd: (synced.append(fd), real_fsync(fd))[1])
    client = _client(server, tmp_path)

    class _Line:
        raw = "WPA*01*fsync-test"

    class _Found:
        line = _Line()
        psk = b"fsyncpsk1"

    client._record_founds([_Found(), _Found()])
    assert len(synced) == 2
    pot = open(client.potfile).read()
    assert pot.count("fsyncpsk1") == 2


def test_outbox_exactly_once_after_kill_before_put_work(server, tmp_path):
    """Kill between crack and put_work (ISSUE-10): the found is journaled
    in the outbox before the first submission attempt, a restarted client
    delivers it exactly once, and a resume-replay re-crack of the same
    unit never double-submits the acked key."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="xo1")])
    _add_dict(server, [PSK])
    client = _client(server, tmp_path)
    work = client.api.get_work(1)

    def killed(hkey, cand, max_tries=None, epoch=None):
        raise ConnectionError("killed between crack and put_work")

    client.api.put_work = killed
    res = client.process_work(work)
    assert not res.accepted and [f.psk for f in res.founds] == [PSK]
    assert client.outbox.pending_count() == 1  # journaled, not lost
    assert server.db.q1(
        "SELECT COUNT(*) c FROM nets WHERE n_state = 1")["c"] == 0

    # "restart": a fresh client over the same workdir replays the journal
    # and the startup drain delivers the found exactly once.
    revived = _client(server, tmp_path)
    assert revived.outbox.pending_count() == 1
    revived._drain_outbox()
    assert revived.outbox.pending_count() == 0
    rows = server.db.q("SELECT n_state, pass FROM nets")
    assert [(r["n_state"], r["pass"]) for r in rows] == [(1, PSK)]

    # The resume file survived the crash too: replaying the unit
    # re-cracks the same PSK, but record() drops the acked key so the
    # server never sees a second submission.
    puts = []
    real_put = revived.api.put_work
    revived.api.put_work = lambda hkey, cand, max_tries=None, epoch=None: (
        puts.append(list(cand))
        or real_put(hkey, cand, max_tries=max_tries, epoch=epoch))
    res2 = revived.process_work(dict(work))
    assert res2.accepted
    assert puts == []  # all founds already acked: no put_work at all


def test_shard_word_blocks_covers_stream_in_lockstep():
    """The no-rules pass-2 slicer (multi-host): per block, the hosts'
    shards partition the global stream in order, every host yields the
    SAME number of same-sized batches (padding, not absence, for short
    tails — the SPMD-lockstep contract), and the reported global counts
    sum to the stream length."""
    from dwpa_tpu.client.main import shard_word_blocks

    words = [b"w%05d" % i for i in range(2 * 3 * 16 + 11)]  # ragged tail
    nproc, bs = 3, 16
    per_host = [list(shard_word_blocks(words, nproc, pid, bs))
                for pid in range(nproc)]
    # identical block structure on every host
    nblocks = {len(h) for h in per_host}
    assert nblocks == {len(per_host[0])}
    for blocks in zip(*per_host):
        sizes = {len(mine) for mine, _ in blocks}
        gcounts = {g for _, g in blocks}
        assert len(sizes) == 1 and len(gcounts) == 1  # lockstep
    # concatenating the hosts' shards per block (padding stripped)
    # reconstructs the global stream exactly once, in order
    rebuilt = []
    for blocks in zip(*per_host):
        for mine, _ in blocks:
            rebuilt.extend(w for w in mine if w != b"")
    assert rebuilt == words
    assert sum(g for _, g in per_host[0]) == len(words)
    # full blocks shard to exactly batch_size per host
    assert all(len(mine) == bs for mine, _ in per_host[1][:-1])


def test_dict_md5_mismatch_rejected(server, tmp_path):
    """A corrupted dict download fails the md5 gate (help_crack.py:533-534)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="md5-1")])
    path, dhash = _add_dict(server, [PSK])
    with open(path, "ab") as f:
        f.write(b"corruption\n")  # server file changes after registration
    client = _client(server, tmp_path)
    work = client.api.get_work(1)
    with pytest.raises(ValueError, match="md5 mismatch"):
        client._fetch_dicts(work)


def test_autotune_moves_dictcount(server, tmp_path):
    client = _client(server, tmp_path)
    client.cfg.pace_target = 1e9  # everything is "fast"
    for _ in range(20):
        client._autotune(elapsed=1.0)
    assert client.dictcount == 15  # clamped at the reference cap
    client.cfg.pace_target = 0.0  # everything is "slow"
    for _ in range(20):
        client._autotune(elapsed=1.0)
    assert client.dictcount == 1


def test_challenge_gate_failure_exits(server, tmp_path, monkeypatch):
    """A cracker that cannot reproduce the known PSK must not fetch work
    (help_crack.py:886-895)."""
    client = _client(server, tmp_path)

    class BrokenEngine:
        def __init__(self, *a, **k):
            self.groups = {}

        def crack(self, words):
            return []

    import dwpa_tpu.client.main as cm

    monkeypatch.setattr(cm, "M22000Engine", BrokenEngine)
    with pytest.raises(SystemExit):
        client.run()


def test_version_gate_and_no_nets(server, tmp_path):
    app = make_wsgi_app(server)
    old = LoopbackAPI(app, hc_ver="2.0.0")
    with pytest.raises(VersionRejected):
        old.get_work(1)
    empty = LoopbackAPI(app)
    with pytest.raises(NoNets):
        empty.get_work(1)


def test_prdict_pass1_candidates(server, tmp_path):
    """The dynamic PROBEREQUEST dict feeds pass 1 (help_crack.py:557-568):
    a PSK present only as a probed SSID in the same capture still cracks
    the unit even though no server dict contains it."""
    probed_psk = b"ProbedNetwork1"
    # One capture: the handshake's PSK is also some station's probed SSID.
    blob, _ = tfx.make_handshake_capture(probed_psk, ESSID, probes=[probed_psk])
    from dwpa_tpu.server.api import submit_capture

    submit_capture(server, blob)
    server.db.x("UPDATE nets SET algo = ''")
    _add_dict(server, [b"filler-word-1"])  # server dict does NOT contain it

    client = _client(server, tmp_path)
    work = client.api.get_work(1)
    assert work.get("prdict") is True
    res = client.process_work(work)
    assert any(f.psk == probed_psk for f in res.founds)
    rows = server.db.q("SELECT n_state, pass FROM nets")
    assert all(r["n_state"] == 1 and r["pass"] == probed_psk for r in rows)


def test_intra_unit_checkpoint_written(server, tmp_path, monkeypatch):
    """_progress (done counter + founds) is checkpointed after every
    completed batch — the hashcat --session analog (SURVEY.md §5.4)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="ck1")])
    words = [b"filler-%06d" % i for i in range(40)] + [PSK]
    _add_dict(server, words)
    client = _client(server, tmp_path, batch_size=16)
    snapshots = []
    real_write = client._write_resume
    monkeypatch.setattr(
        client, "_write_resume",
        lambda work: (snapshots.append(json.loads(json.dumps(work))),
                      real_write(work))[1],
    )
    work = client.api.get_work(client.dictcount)
    res = client.process_work(work)
    assert res.accepted and [f.psk for f in res.founds] == [PSK]
    dones = [s["_progress"]["done"] for s in snapshots if "_progress" in s]
    assert dones and dones == sorted(dones) and dones[-1] >= len(words)
    # the found PSK was checkpointed before put_work
    assert any(s["_progress"]["cand"] for s in snapshots if "_progress" in s)


def test_resume_skips_done_and_resubmits_founds(server, tmp_path):
    """A resumed unit skips the completed prefix and re-submits prior
    founds (which may not have reached the server before the crash)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="ck2")])
    net = server.db.q1("SELECT bssid FROM nets")
    from dwpa_tpu.server.db import long2mac
    mac = long2mac(net["bssid"])
    # dict whose PSK sits inside the "already done" prefix
    _add_dict(server, [PSK] + [b"filler-%06d" % i for i in range(40)])
    client = _client(server, tmp_path, batch_size=16)
    work = client.api.get_work(client.dictcount)
    work["_progress"] = {
        "done": 10 ** 6,  # far past the whole stream: nothing re-tried
        "cand": [{"k": mac.hex(), "v": PSK.hex()}],
    }
    res = client.process_work(work)
    assert res.candidates_tried == 0 and res.founds == []
    assert res.accepted
    row = server.db.q1("SELECT n_state, pass FROM nets")
    assert row["n_state"] == 1 and row["pass"] == PSK


def test_cracked_dict_runs_in_pass1_with_rkg(server, tmp_path):
    """A work unit carrying cracked.txt.gz: the client streams it (plus
    the server's rkg.txt.gz) through the work rules in pass 1 and cracks
    a net whose PSK only appears in the rkg dictionary
    (help_crack.py:469-509)."""
    from dwpa_tpu.server.jobs import regen_rkg_dict

    _ingest(server, [tfx.make_eapol_line(PSK, ESSID, keyver=2, seed="cd1")])
    # the vendor-key dict holds the PSK; the cracked dict holds chaff
    server.add_hashlines([tfx.make_pmkid_line(PSK, b"OtherNet", seed="cd1v")])
    server.db.x(
        "UPDATE nets SET algo = 'Vendor', n_state = 1, pass = ? "
        "WHERE ssid = ?", (PSK, b"OtherNet"))
    regen_rkg_dict(server, os.path.join(server.dictdir, "rkg.txt.gz"))
    _add_dict(server, [b"chaff-00001", b"chaff-00002"], name="cracked.txt.gz")

    client = _client(server, tmp_path)
    work = client.api.get_work(client.dictcount)
    assert any("cracked.txt.gz" in d["dpath"] for d in work["dicts"])
    res = client.process_work(work)
    assert [f.psk for f in res.founds] == [PSK]
    assert server.db.q1(
        "SELECT n_state FROM nets WHERE ssid = ?", (ESSID,))["n_state"] == 1


def test_cracked_dict_refresh_cadence(server, tmp_path):
    """cracked.txt.gz is re-downloaded only every cracked_refresh units
    (DAW dl_count, help_crack.py:524-529)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="cd2")])
    _add_dict(server, [PSK], name="cracked.txt.gz")
    client = _client(server, tmp_path, cracked_refresh=3)
    work = client.api.get_work(client.dictcount)

    def dl_count():
        return sum(1 for m, u in client.api.requests if "cracked.txt.gz" in u)

    list(client._cracked_candidates(dict(work), []))  # first use: downloads
    assert dl_count() == 1
    list(client._cracked_candidates(dict(work), []))  # countdown=2: cached
    list(client._cracked_candidates(dict(work), []))  # countdown=1: cached
    assert dl_count() == 1
    list(client._cracked_candidates(dict(work), []))  # countdown=0: refresh
    assert dl_count() == 2


def test_archive_logs_appended(server, tmp_path):
    """archive.22000 / archive.res audit logs accumulate one entry per
    unit (DAW, help_crack.py:453-456,741-743)."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="ar1")])
    _add_dict(server, [PSK])
    client = _client(server, tmp_path, max_work_units=1)
    assert client.run() == 1
    arc = open(os.path.join(client.cfg.workdir, "archive.22000")).read()
    assert arc.count("WPA*") >= 1
    res_lines = open(os.path.join(client.cfg.workdir, "archive.res")).read()
    assert json.loads(res_lines.splitlines()[-1])["hkey"]


def test_rules_unit_runs_on_device_path(server, tmp_path, monkeypatch):
    """Pass 2 of a rules work unit goes through the device-expansion
    seam (crack_rules_blocks / crack_rules_streams — the hashcat-on-GPU
    analog of the reference client's ``-S -r`` invocation,
    help_crack.py:773), NOT host expansion: apply_rules must never see
    the pass-2 dict stream, and the legacy flat crack_rules entry is
    reserved for multi-host slices."""
    import dwpa_tpu.client.main as cm
    from dwpa_tpu.models.m22000 import M22000Engine as Eng
    from dwpa_tpu.rules import wpa_rules_text

    mangled = b"Devword77!1"  # 'devword77!' through 'c $1'
    _ingest(server, [tfx.make_pmkid_line(mangled, ESSID, seed="dv1")])
    os.makedirs(server.dictdir, exist_ok=True)
    blob = gzip.compress(b"devword77!\n")
    path = os.path.join(server.dictdir, "dv.txt.gz")
    open(path, "wb").write(blob)
    server.add_dict("dict/dv.txt.gz", "dv.txt.gz",
                    hashlib.md5(blob).hexdigest(), 1, rules=wpa_rules_text())

    calls = []
    real_blocks = Eng.crack_rules_blocks
    real_streams = Eng.crack_rules_streams
    monkeypatch.setattr(
        Eng, "crack_rules_blocks",
        lambda self, *a, **k: (calls.append(k.get("skip", 0)),
                               real_blocks(self, *a, **k))[1])
    monkeypatch.setattr(
        Eng, "crack_rules_streams",
        lambda self, *a, **k: (calls.append(k.get("skip", 0)),
                               real_streams(self, *a, **k))[1])
    monkeypatch.setattr(
        Eng, "crack_rules",
        lambda self, *a, **k: (_ for _ in ()).throw(
            AssertionError("single-process pass 2 must dispatch through "
                           "the blocks/streams seam")))
    monkeypatch.setattr(
        cm, "apply_rules",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("pass 2 must not host-expand rules")))

    client = _client(server, tmp_path)
    work = client.api.get_work(1)
    res = client.process_work(work)
    assert calls == [0]  # device path used, fresh unit -> no skip
    assert [f.psk for f in res.founds] == [mangled]
    assert server.db.q1("SELECT n_state FROM nets")["n_state"] == 1


def test_rules_unit_resume_mid_pass2(server, tmp_path):
    """A crash mid-pass-2 of a rules unit resumes through crack_rules'
    skip: the completed prefix is not re-reported, the PSK (reachable
    only via a device rule late in pass 2) still cracks, and the unit
    submits."""
    from dwpa_tpu.rules import parse_rule

    base = [b"resume%04dw" % i for i in range(90)]
    psk = parse_rule("u").apply(base[85])
    _ingest(server, [tfx.make_pmkid_line(psk, ESSID, seed="rm1")])
    os.makedirs(server.dictdir, exist_ok=True)
    blob = gzip.compress(b"\n".join(base) + b"\n")
    path = os.path.join(server.dictdir, "rm.txt.gz")
    open(path, "wb").write(blob)
    server.add_dict("dict/rm.txt.gz", "rm.txt.gz",
                    hashlib.md5(blob).hexdigest(), len(base),
                    rules="u\n$Z")

    first = _client(server, tmp_path, batch_size=32)
    work = first.api.get_work(1)
    res1 = first.process_work(dict(work))
    assert [f.psk for f in res1.founds] == [psk]
    total = res1.candidates_tried

    # Replay the unit as a crash at ~40% of the stream: pass 1 is empty
    # (no targeted hit material beyond generators), so most of the skip
    # lands inside crack_rules.
    server.db.x("UPDATE nets SET n_state = 0, pass = NULL, algo = ''")
    skip = int(total * 0.4)
    resumed = _client(server, tmp_path / "second", batch_size=32)
    work2 = dict(work)
    work2["_progress"] = {"done": skip, "cand": []}
    res2 = resumed.process_work(work2)
    assert [f.psk for f in res2.founds] == [psk]
    assert res2.accepted
    # reported remainder never exceeds the unskipped tail (at-least-once
    # may re-TRY a straddling sub-batch, but never re-COUNT it)
    assert 0 < res2.candidates_tried <= total - skip
    assert server.db.q1("SELECT n_state FROM nets")["n_state"] == 1


def test_client_cli_multihost_flags():
    """The CLI exposes the slice-join knobs (INSTALL.md multi-host
    recipe) without touching single-process defaults."""
    from dwpa_tpu.client.__main__ import build_parser

    a = build_parser().parse_args(["http://s/"])
    assert not a.multihost and a.coordinator is None
    a = build_parser().parse_args(["http://s/", "--multihost"])
    assert a.multihost
    a = build_parser().parse_args(
        ["http://s/", "--coordinator", "h0:8476",
         "--num-processes", "2", "--process-id", "1"])
    assert (a.coordinator, a.num_processes, a.process_id) == ("h0:8476", 2, 1)
    # a partial manual-cluster spec is a usage error, not a deep JAX
    # traceback (and never a silently-ignored flag)
    from dwpa_tpu.client.__main__ import main as cli_main

    for argv in (["http://s/", "--coordinator", "h0:8476"],
                 ["http://s/", "--num-processes", "2", "--process-id", "1"]):
        with pytest.raises(SystemExit) as e:
            cli_main(argv)
        assert e.value.code == 2  # argparse usage error


def test_fused_metrics_in_scrape_and_executor_wiring(server, tmp_path):
    """Unit-fusion telemetry contract: the dwpa_fused_* family and the
    engine-retry counter are registered up front (names visible in the
    ?metrics scrape before any fused batch runs), and fused_executor()
    binds the client's knobs/registry/tracer/store."""
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg,
                     unit_queue=3, fuse_max_units=4)
    text = reg.render_prometheus()
    for name in ("dwpa_fused_units_per_batch", "dwpa_fused_fill_fraction",
                 "dwpa_unit_queue_depth", "dwpa_client_engine_retries_total"):
        assert name in text, name
    ex = client.fused_executor([])
    assert ex.batch_size == client.cfg.batch_size
    assert ex.unit_queue == 3 and ex.fuse_max_units == 4
    assert ex.registry is reg and ex.tracer is client.tracer
    assert ex.pmk_store is client.pmk_store


def test_engine_error_recovery_halves_batch(server, tmp_path):
    """In-process engine recovery: a crack dispatch that raises is
    retried once at half the batch — with the _progress checkpoint
    dropped first, since skip-by-count is unsound across a batch-size
    change — and the unit completes without touching the retry loop."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="er1")])
    _add_dict(server, [PSK])
    reg = MetricsRegistry()
    client = _client(server, tmp_path, registry=reg)
    work = client.api.get_work(1)
    work["_progress"] = {"done": 0, "cand": []}
    seen = []
    real = client.process_work

    def flaky(w):
        seen.append((client.cfg.batch_size, "_progress" in w))
        if len(seen) == 1:
            raise RuntimeError("injected XLA OOM")
        return real(w)

    client.process_work = flaky
    res = client._process_with_recovery(work)
    assert res is not None and res.accepted
    assert seen == [(64, True), (32, False)]
    assert client.cfg.batch_size == 64  # restored after the retry
    assert reg.value("dwpa_client_engine_retries_total") == 1


def test_engine_error_persistent_requeues_then_abandons(server, tmp_path):
    """Both recovery attempts failing requeues the unit with backoff via
    the resume file; ENGINE_RETRY_LIMIT total attempts abandon it."""
    _ingest(server, [tfx.make_pmkid_line(PSK, ESSID, seed="er2")])
    _add_dict(server, [PSK])
    client = _client(server, tmp_path)
    slept = []
    client.api.sleep = slept.append
    work = client.api.get_work(1)

    def boom(w):
        raise RuntimeError("device fell off the bus")

    client.process_work = boom
    assert client._process_with_recovery(work) is None
    assert work["_attempts"] == 1
    assert client.cfg.batch_size == 64  # restored before the resume stamp
    assert slept == [client.api.backoff]
    assert client._read_resume() == work  # requeued for the next loop pass
    assert client._process_with_recovery(work) is None
    assert client._process_with_recovery(work) is None
    assert work["_attempts"] == client.ENGINE_RETRY_LIMIT
    assert len(slept) == 2  # the abandoning attempt does not back off
    assert client._read_resume() is None  # abandoned, not wedged


def test_bundled_wpa_rules_crack_mangled_psk(server, tmp_path):
    """A dict packed with the bundled WPA ruleset cracks a PSK that is a
    base word through a rule ('c $1'), end-to-end over the wire — the
    bestWPA.rule distribution flow (get_work.php:84-92)."""
    from dwpa_tpu.rules import wpa_rules_text

    mangled = b"Loopword9!1"  # 'loopword9!' through 'c $1'
    _ingest(server, [tfx.make_pmkid_line(mangled, ESSID, seed="wr1")])
    os.makedirs(server.dictdir, exist_ok=True)
    blob = gzip.compress(b"loopword9!\n")
    path = os.path.join(server.dictdir, "wr.txt.gz")
    open(path, "wb").write(blob)
    server.add_dict("dict/wr.txt.gz", "wr.txt.gz",
                    hashlib.md5(blob).hexdigest(), 1,
                    rules=wpa_rules_text())
    client = _client(server, tmp_path)
    work = client.api.get_work(1)
    assert work.get("rules")  # merged + base64'd into the unit
    res = client.process_work(work)
    assert [f.psk for f in res.founds] == [mangled]
    assert server.db.q1("SELECT n_state FROM nets")["n_state"] == 1
