"""Mixed-ESSID batch fusion (dwpa_tpu.sched + the per-lane-salt kernels).

Layers under test:

- the PER-LANE SALT kernel path — ``pmk_kernel`` with ``[B, 16]`` salt
  blocks bit-exact vs hashlib per lane, and the Pallas formulation's
  per-lane prologue vs the XLA path at reduced iterations;
- the PACKER (``sched.fuse``) — static width table properties, lane
  layout, store hit/miss composition;
- the ENGINE fused path (``crack_fused``) — differential against the
  serial per-unit path for mixed keyvers + mixed ESSIDs in ONE batch,
  found-PSK demux (a hit in unit A must not surface in unit B),
  resume-skip equivalence, and the recompile-sentinel proof that the
  fused widths keep XLA compiles bounded;
- the EXECUTOR (``sched.executor``) — wave assembly, ESSID-collision
  deferral, and the retry/requeue/backoff recovery contract.

Engine tests share ``BATCH = 32`` (fused widths {8, 16, 32} on the
8-device test mesh) so the per-lane PBKDF2 compiles are paid once.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from dwpa_tpu import testing as synth
from dwpa_tpu.models.m22000 import M22000Engine, essid_salt_blocks, pmk_kernel
from dwpa_tpu.obs import MetricsRegistry
from dwpa_tpu.obs.spans import SpanTracer
from dwpa_tpu.sched import (MultiUnitExecutor, WorkUnit, fuse_units,
                            fused_width, fused_widths)
from dwpa_tpu.utils import bytesops as bo

BATCH = 32


def _lane_salts(essids):
    """[B, 16] salt block pair for a per-lane ESSID assignment."""
    s1 = np.zeros((len(essids), 16), np.uint32)
    s2 = np.zeros((len(essids), 16), np.uint32)
    for i, e in enumerate(essids):
        s1[i], s2[i] = essid_salt_blocks(e)
    return s1, s2


# ---------------------------------------------------------------------------
# per-lane salt kernels
# ---------------------------------------------------------------------------


def test_per_lane_salt_kernel_matches_hashlib():
    """Lane b's PMK must be PBKDF2(pw_b, essid_b) exactly — the whole
    correctness contract of the fused path's salt gather."""
    essids = [b"LaneNetA", b"LaneNetB"]
    pws = [b"perlanepw%02d" % i for i in range(8)]
    lane_essid = [essids[i % 2] for i in range(8)]
    rows = bo.pack_passwords_be(pws).astype(np.uint32)
    s1, s2 = _lane_salts(lane_essid)
    pmk = np.asarray(pmk_kernel(rows, s1, s2))
    for i in range(8):
        ref = hashlib.pbkdf2_hmac("sha1", pws[i], lane_essid[i], 4096, 32)
        assert bo.words_to_bytes_be(pmk[:, i]) == ref


def test_scalar_salt_fast_path_unchanged():
    """uint32[16] salts still take the broadcast fast path and agree
    with the per-lane path when every lane shares one ESSID."""
    essid = b"ScalarNet"
    pws = [b"scalarpw%02d" % i for i in range(8)]
    rows = bo.pack_passwords_be(pws).astype(np.uint32)
    a, b = essid_salt_blocks(essid)
    scalar = np.asarray(pmk_kernel(rows, a, b))
    s1, s2 = _lane_salts([essid] * 8)
    np.testing.assert_array_equal(scalar, np.asarray(pmk_kernel(rows, s1, s2)))


def test_pallas_per_lane_prologue_matches_xla():
    """The Pallas formulation's per-lane U1 prologue (the ONLY part of
    the kernel the 2-D salt mode touches) against the XLA path, at
    reduced iterations (CPU interpret mode)."""
    from dwpa_tpu.ops.pbkdf2 import pbkdf2_sha1_pmk
    from dwpa_tpu.ops.pbkdf2_pallas import pbkdf2_sha1_pmk_pallas
    on_tpu = jax.devices()[0].platform == "tpu"
    pws = [b"fusedpw%03d" % i for i in range(6)]
    lane_essid = [b"PallasNet%d" % (i % 3) for i in range(6)]
    rows = jnp.asarray(bo.pack_passwords_be(pws))
    s1, s2 = _lane_salts(lane_essid)
    pw = [rows[:, i] for i in range(16)]
    ref = np.asarray(jnp.stack(pbkdf2_sha1_pmk(
        pw, [s1[:, i] for i in range(16)], [s2[:, i] for i in range(16)],
        iterations=2)))
    got = np.asarray(pbkdf2_sha1_pmk_pallas(
        rows, jnp.asarray(s1), jnp.asarray(s2), iterations=2, tile=8,
        interpret=not on_tpu))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# the packer
# ---------------------------------------------------------------------------


def test_fused_widths_bounded_and_mesh_aligned():
    n = 8
    for batch in (32, 64, 4096, 16384):
        widths = fused_widths(batch, n)
        assert 1 <= len(widths) <= 3
        assert widths[-1] == batch
        assert all(w % n == 0 and w > 0 for w in widths)
        assert list(widths) == sorted(widths)
        for total in (0, 1, n, batch // 2, batch):
            w = fused_width(batch, n, total)
            assert w in widths and w >= total


def test_fuse_units_layout_and_fill():
    parts = [(b"FuseA", [b"alphaword%02d" % i for i in range(5)], 5),
             (b"FuseB", [b"betaword%03d" % i for i in range(3)], 3)]
    fb = fuse_units(parts, BATCH, 8, max_units=4)
    assert fb.total == 8 and fb.width == fused_width(BATCH, 8, 8)
    assert fb.nmiss == 8 and fb.idx is None  # no store: all-miss layout
    assert [u.lo for u in fb.units] == [0, 5]
    assert fb.fill == 8 / fb.width
    # lane-major unit_id: lanes 0-4 unit 0, lanes 5-7 unit 1, pad 0
    assert list(fb.unit_id[:8]) == [0] * 5 + [1] * 3
    # salt table rows are each unit's own blocks, padded with row 0
    s1a, _ = essid_salt_blocks(b"FuseA")
    s1b, _ = essid_salt_blocks(b"FuseB")
    np.testing.assert_array_equal(fb.table1[0], s1a)
    np.testing.assert_array_equal(fb.table1[1], s1b)
    np.testing.assert_array_equal(fb.table1[2], s1a)
    assert fb.table1.shape == (4, 16)


# ---------------------------------------------------------------------------
# engine: fused vs serial, demux, resume, recompiles
# ---------------------------------------------------------------------------


def _mixed_units():
    """Three units, three keyvers, three ESSIDs — one fused batch."""
    psks = [b"fusedpass-A1", b"fusedpass-B2", b"fusedpass-C3"]
    lines = [
        synth.make_pmkid_line(psks[0], b"MixNetA", seed="mx-a"),
        synth.make_eapol_line(psks[1], b"MixNetB", keyver=2, seed="mx-b"),
        synth.make_eapol_line(psks[2], b"MixNetC", keyver=3, seed="mx-c"),
    ]
    units = []
    for i, (essid, psk) in enumerate(
            zip([b"MixNetA", b"MixNetB", b"MixNetC"], psks)):
        words = [b"mixjunk%d%03d" % (i, j) for j in range(7)] + [psk]
        units.append((essid, words))
    return lines, units, psks


def test_fused_matches_serial_mixed_keyvers_and_essids():
    """The acceptance parity: mixed keyvers (pmkid/eapol/cmac) + mixed
    ESSIDs fused into one batch produce the identical found list the
    serial per-unit path produces (oracle verification on in both)."""
    lines, units, psks = _mixed_units()
    fused_eng = M22000Engine(lines, batch_size=BATCH)
    events = []
    fused = fused_eng.crack_fused(
        units, on_batch=lambda k, c, f: events.append((k, c)))
    serial = []
    for (essid, words), line in zip(units, lines):
        serial += M22000Engine([line], batch_size=BATCH).crack(words)
    key = lambda f: (f.line.essid, f.psk, f.nc, f.endian, f.pmk)
    assert sorted(map(key, fused)) == sorted(map(key, serial))
    assert sorted(f.psk for f in fused) == sorted(psks)
    # per-unit coverage reporting (the resume contract)
    assert sorted(events) == sorted((e, len(w)) for e, w in units)


def test_found_demux_no_cross_unit_leak():
    """The SAME password cracks unit A's net and appears in unit B's
    words too (B's net uses a different PSK): the hit must surface
    under unit A only — B's window sees the word under B's ESSID, where
    it does not match anything."""
    shared = b"shared-secret-pw"
    la = synth.make_pmkid_line(shared, b"DemuxA", seed="dm-a")
    lb = synth.make_pmkid_line(b"other-pass-b9", b"DemuxB", seed="dm-b")
    eng = M22000Engine([la, lb], batch_size=BATCH)
    by_unit = {}
    founds = eng.crack_fused(
        [(b"DemuxA", [b"demuxjunk%03d" % i for i in range(4)] + [shared]),
         (b"DemuxB", [shared] + [b"demuxjunk%03d" % i for i in range(4)])],
        on_batch=lambda k, c, f: by_unit.setdefault(k, []).extend(f))
    assert [f.psk for f in founds] == [shared]
    assert founds[0].line.essid == b"DemuxA"
    assert [f.line.essid for f in by_unit.get(b"DemuxA", [])] == [b"DemuxA"]
    assert by_unit.get(b"DemuxB", []) == []


def test_same_password_two_units_each_attributed():
    """Both nets share one password; the word rides in BOTH units: each
    unit's on_batch receives exactly its own net's find."""
    pw = b"both-nets-pass7"
    la = synth.make_pmkid_line(pw, b"AttrA", seed="at-a")
    lb = synth.make_eapol_line(pw, b"AttrB", keyver=2, seed="at-b")
    eng = M22000Engine([la, lb], batch_size=BATCH)
    by_unit = {}
    founds = eng.crack_fused(
        [(b"AttrA", [pw, b"attrjunk%03d" % 0]),
         (b"AttrB", [b"attrjunk%03d" % 1, pw])],
        on_batch=lambda k, c, f: by_unit.setdefault(k, []).extend(f))
    assert len(founds) == 2
    assert [f.line.essid for f in by_unit[b"AttrA"]] == [b"AttrA"]
    assert [f.line.essid for f in by_unit[b"AttrB"]] == [b"AttrB"]


def test_resume_skip_equivalence_under_fusion():
    """A unit resumed at skip=k through the executor covers exactly the
    serial path's unskipped tail: same found, and the consumed floor
    accounts skip + tail."""
    psk = b"resume-fused-1"
    line = synth.make_pmkid_line(psk, b"ResumeNet", seed="rs")
    words = [b"resumew%04d" % i for i in range(21)] + [psk]
    skip = 9
    ex = MultiUnitExecutor(
        [WorkUnit(uid=0, lines=[line], words=words, skip=skip)],
        batch_size=BATCH)
    done = ex.run()
    assert len(done) == 1 and [f.psk for f in done[0].founds] == [psk]
    assert done[0].consumed == len(words)  # skip + unskipped tail
    # serial reference over the identical tail
    serial = M22000Engine([line], batch_size=BATCH).crack(words[skip:])
    assert [f.psk for f in serial] == [psk]


def test_fused_width_sweep_recompile_bounded(recompile_sentinel):
    """The static-width proof for fusion: after one warmup per fused
    width, ANY unit mix — 1..4 units, any fill — reuses compiled
    programs (allowed=0).  Word lengths stay in one column-trim bucket
    so the sweep exercises only the width axis."""
    mesh_n = 8
    widths = fused_widths(BATCH, mesh_n)
    assert len(widths) <= 3

    def eng():
        # no PSK in keyspace: every batch takes the all-miss gate path
        return M22000Engine(
            [synth.make_pmkid_line(b"not-in-keyspace", b"SweepNet%d" % i,
                                   seed=f"sw{i}") for i in range(4)],
            batch_size=BATCH)

    n = 0

    def unit(essid_i, nwords):
        nonlocal n
        n += 1
        return (b"SweepNet%d" % essid_i,
                [b"sw%04d%03d" % (n, j) for j in range(nwords)])

    # warm every fused width once (single-unit batches)
    for w in widths:
        eng().crack_fused([unit(0, min(w, BATCH))])
    with recompile_sentinel(allowed=0, label="fused width sweep"):
        eng().crack_fused([unit(0, 3), unit(1, 2)])            # small width
        eng().crack_fused([unit(i, 3) for i in range(4)])      # mid width
        eng().crack_fused([unit(i, 8) for i in range(4)])      # full width
        eng().crack_fused([unit(2, 1)])                        # tiny again


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------


def _units(k, prefix=b"ExNet", psk_fmt=b"expass%03d", nwords=6):
    out = []
    for i in range(k):
        psk = psk_fmt % i
        line = synth.make_pmkid_line(psk, prefix + b"%d" % i, seed=f"ex{i}")
        words = [b"exwords%d%03d" % (i, j) for j in range(nwords)] + [psk]
        out.append(WorkUnit(uid=i, lines=[line], words=words))
    return out


def test_executor_metrics_and_spans():
    reg = MetricsRegistry()
    tracer = SpanTracer(reg)
    ex = MultiUnitExecutor(_units(3), batch_size=BATCH, unit_queue=3,
                           fuse_max_units=4, registry=reg, tracer=tracer)
    done = ex.run()
    assert len(done) == 3 and all(len(u.founds) == 1 for u in done)
    assert reg.value("dwpa_fused_units_per_batch") >= 1  # histogram count
    assert 0.0 < reg.value("dwpa_fused_fill_fraction") <= 1.0
    assert reg.value("dwpa_unit_queue_depth") is not None
    names = {r["name"] for r in tracer.records()}
    assert {"sched:fuse", "sched:demux"} <= names


def test_executor_essid_collision_defers_to_next_wave():
    """Two units over the SAME ESSID cannot share a salt-table row; the
    second waits one wave and both still complete."""
    psk1, psk2 = b"collide-one1", b"collide-two2"
    line = synth.make_pmkid_line(psk1, b"CollideNet", seed="co")
    u1 = WorkUnit(uid=1, lines=[line], words=[psk1, b"cjunkcjunk1"])
    u2 = WorkUnit(uid=2, lines=[line], words=[b"cjunkcjunk2", psk1])
    ex = MultiUnitExecutor([u1, u2], batch_size=BATCH, fuse_max_units=4)
    done = ex.run()
    assert {u.uid for u in done} == {1, 2}
    # the first unit to crack the net wins; the other covers its words
    assert sum(len(u.founds) for u in done) >= 1
    assert all(u.consumed == 2 for u in done)


def test_executor_retry_halves_batch_then_requeues():
    """Satellite recovery contract: a raising wave retries once at half
    batch; persistent failure requeues with backoff until max_retries,
    then the unit lands in ``failed`` instead of wedging the stream."""
    units = _units(1)
    attempts = []

    class _Boom:
        def crack_fused(self, *a, **k):
            raise RuntimeError("injected device error")

    def factory(lines, batch_size):
        attempts.append(batch_size)
        return _Boom()

    reg = MetricsRegistry()
    slept = []
    ex = MultiUnitExecutor(units, batch_size=BATCH, registry=reg,
                           engine_factory=factory, max_retries=2,
                           backoff_s=0.5, sleep=slept.append)
    done = ex.run()
    assert done == [] and ex.failed == units
    # per failed wave: one try at BATCH, one at BATCH // 2
    assert attempts == [BATCH, BATCH // 2] * 3
    assert slept == [0.5, 1.0]  # exponential backoff between requeues
    assert reg.value("dwpa_fused_retries_total") == 3


def test_executor_recovers_on_transient_error():
    """One transient failure: the half-batch retry completes the wave
    and the unit still cracks."""
    units = _units(2)
    state = {"raised": False}

    def factory(lines, batch_size):
        if not state["raised"]:
            state["raised"] = True

            class _Boom:
                def crack_fused(self, *a, **k):
                    raise RuntimeError("transient")

            return _Boom()
        return M22000Engine(lines, batch_size=batch_size)

    ex = MultiUnitExecutor(units, batch_size=BATCH, engine_factory=factory)
    done = ex.run()
    assert len(done) == 2 and all(len(u.founds) == 1 for u in done)
    assert ex.failed == []


def test_executor_leaves_no_orphan_threads():
    """Thread-lifecycle audit: run() joins its unit producer (and the
    per-device stream drainers join inside the wave), so no ``sched-*``
    thread survives a completed run — the feed-soak no-orphan idiom
    extended to the executor."""
    import threading

    def _sched_threads():
        return [t for t in threading.enumerate()
                if t.name.startswith("sched-") and t.is_alive()]

    psk = b"orphan-check-1"
    line = synth.make_pmkid_line(psk, b"OrphanNet", seed="oc")
    units = [WorkUnit(uid=i, lines=[line],
                      words=[b"w%04d" % i, psk])
             for i in range(3)]
    ex = MultiUnitExecutor(units, batch_size=BATCH, unit_queue=2)
    done = ex.run()
    assert len(done) == 3
    deadline = __import__("time").time() + 10.0
    while _sched_threads() and __import__("time").time() < deadline:
        for t in _sched_threads():
            t.join(timeout=0.2)
    assert _sched_threads() == []
