#!/usr/bin/env python3
"""dwpa_tpu benchmark harness — prints ONE JSON line.

Tracks BASELINE.json's configs on the local accelerator:

  #1  single m22000 PMKID line x 1k-word dict slice (engine end-to-end)
  #2  single WPA2 4-way EAPOL line x dict (adds PRF-512 + MIC + NC search)
  #5  8-digit mask brute (?d x 8) — pure PBKDF2 throughput, no dict I/O

The headline metric is config #5's PMK/s on this chip.  North star
(BASELINE.json): >= 2x a hashcat-CUDA RTX 4090 (~2.5e6 PMK/s on m22000)
across a v5e-8, i.e. a per-chip share of 2 * 2.5e6 / 8 = 625k PMK/s;
``vs_baseline`` is the fraction of that per-chip share this run achieved.

Timing notes: every sample forces a device->host fetch of the result
(``np.asarray``) before the clock stops, so no timed region can end at
the enqueue.  Each repetition feeds distinct inputs so no layer can
serve a cached result.

Runs on a TPU only: with no TPU it exits non-zero before measuring
anything, so a CPU run can never print under the device keys.  The
device probe lives in ``main()``, not at import: the host-feed bench
spawns worker processes that re-import this module, and a child that
reached for the chip the parent holds would fail or hang.

Every timed region runs through the obs span tracer (dwpa_tpu.obs), so
the numbers in this JSON line and the live ``dwpa_span_seconds``
telemetry are the SAME measurement — they cannot disagree.  The spans
inherit the sync rule above: each region's body ends in an engine
``crack*`` call or an ``np.asarray`` fetch (lint rule DW106 checks
this file statically, as DW105 did for the raw perf_counter spans).
"""

import json
import os
import sys

import numpy as np

import jax

from dwpa_tpu import testing as T
from dwpa_tpu.analysis import watch_compiles
from dwpa_tpu.models.m22000 import M22000Engine
from dwpa_tpu.obs import SpanTracer, default_registry

TRACER = SpanTracer(default_registry())

RTX4090_PMKS = 2.5e6           # hashcat-CUDA m22000 on one RTX 4090
PER_CHIP_TARGET = 2 * RTX4090_PMKS / 8   # north-star share per v5e chip

def tpu_selftest() -> dict:
    """Preflight: pin the production Pallas kernel against hashlib on the
    real chip, every round.

    The suite's conftest forces the CPU platform, so its full-4096
    bit-exactness test only runs when someone sets DWPA_TEST_TPU=1 —
    which recorded rounds never did.  This preflight closes that gap:
    the exact kernel configuration the headline number is measured on
    (hoisted prologue, default tile) is verified oracle-exact here, in
    the same driver-recorded run, or bench fails loudly (rc != 0).
    """
    import hashlib

    import jax.numpy as jnp

    from dwpa_tpu.models.m22000 import essid_salt_blocks
    from dwpa_tpu.ops.pbkdf2_pallas import pbkdf2_sha1_pmk_pallas
    from dwpa_tpu.utils import bytesops as bo

    essid = b"bench-selftest"
    s1, s2 = essid_salt_blocks(essid)
    # Lengths straddling both trimmed-width buckets and the 20-byte
    # SHA-1 block boundary, like the TPU-gated unit test.
    pws = [b"pw%06d" % i for i in range(32)]
    pws += [b"longpassphrase-%016d" % i for i in range(32)]
    out = np.asarray(
        pbkdf2_sha1_pmk_pallas(
            jnp.asarray(bo.pack_passwords_be(pws)), jnp.asarray(s1), jnp.asarray(s2)
        )
    )
    for i in range(0, len(pws), 7):
        ref = hashlib.pbkdf2_hmac("sha1", pws[i], essid, 4096, 32)
        got = bo.words_to_bytes_be(out[:, i])
        if got != ref:
            raise SystemExit(
                f"TPU SELFTEST FAILED: Pallas PBKDF2 not bit-exact for {pws[i]!r}"
            )
    return {"label": "tpu_selftest", "status": "pass",
            "check": "pallas_pbkdf2_4096_vs_hashlib", "words": len(pws)}


def bench_mask_pbkdf2(batch: int, batches: int = 8) -> dict:
    """Config #5: PBKDF2 throughput on the ?d x 8 keyspace, end to end.

    The real product path: ``M22000Engine.crack_mask`` generates
    candidates ON DEVICE (gen.mask.device_mask_words — iota→digits→pack;
    zero host packing, zero candidate H2D) and streams batches through
    the engine's pipelined crack loop, so per-batch dispatch and the
    hits-gate round trip hide behind compute.  Each batch covers a
    distinct keyspace slice (no layer can serve a cached result).
    """
    psk = b"not-in-keyspace"  # ?d keyspace can't contain letters: all-miss
    engine = M22000Engine(
        [T.make_pmkid_line(psk, b"bench-essid", seed="mask5")],
        batch_size=batch,
    )
    mask = "?d?d?d?d?d?d?d?d"
    n = batches * batch
    # Warmup (compile) on a keyspace slice disjoint from the timed run.
    # The sentinel proves the headline number measures steady state: a
    # nonzero ``recompiles`` means the timed run paid XLA compile time.
    engine.crack_mask(mask, skip=n, limit=batch)
    with watch_compiles() as comp:
        with TRACER.span("bench:mask_pbkdf2") as sp:
            engine.crack_mask(mask, skip=0, limit=n)
        dt = sp.seconds
    return {"pmk_per_s": n / dt, "batch": batch, "batches": batches,
            "seconds": dt, "candidate_gen": "on-device",
            "recompiles": comp.count}


def bench_engine_dict(line: str, psk: bytes, words: int, label: str,
                      batch: int = None) -> dict:
    """Configs #1/#2: engine end-to-end crack of a known-PSK hashline."""
    batch = batch or min(4096, words)
    dict_words = [b"candidate-%06d" % i for i in range(words - 1)] + [psk]
    engine = M22000Engine([line], batch_size=batch)
    # Warm the jit caches (PBKDF2 + verify kernels) on a no-match slice so
    # the timed run measures steady-state throughput, as hashcat reports it.
    engine.crack_batch([b"warmup-%06d" % i for i in range(batch)])
    with TRACER.span(f"bench:{label}") as sp:
        founds = engine.crack(dict_words)
    dt = sp.seconds
    assert founds and founds[0].psk == psk, f"{label}: engine missed the known PSK"
    return {"label": label, "words": words, "seconds": dt, "pmk_per_s": words / dt}


def bench_rules_dict(words: int) -> dict:
    """Config #3: a SMALL rules work unit through the client's pass-2
    path (engine.crack_rules — on-device mangling, the route
    client/main.py process_work takes since r5), overhead-dominated like
    the pmkid/eapol small-unit configs.

    A representative rule set (case/append/prepend/truncate families, the
    op classes bestWPA.rule uses); throughput counts expanded candidates.
    """
    from dwpa_tpu.rules import parse_rules

    rules = parse_rules([":", "u", "c", "$1", "^w", "r", "T0", "$1 $2 $3"])
    base = [b"benchword%04d" % i for i in range(words)]
    # The planted PSK is the LAST base word through the LAST rule — the
    # final expanded candidate — so the engine's early exit on the find
    # cannot shrink the work that the candidates/second figure counts.
    expanded_psk = b"benchword%04d123" % (words - 1)
    engine = M22000Engine(
        [T.make_pmkid_line(expanded_psk, b"bench-essid", seed="rules")],
        batch_size=min(4096, words),
    )
    engine.crack_rules([b"warm-%06d" % i for i in range(engine.batch_size)],
                       [rules[0], rules[-1]])
    with TRACER.span("bench:rules_dict") as sp:
        founds = engine.crack_rules(base, rules)
    dt = sp.seconds
    assert founds and founds[0].psk == expanded_psk, "rules config missed the PSK"
    n = words * len(rules)
    return {"label": "rules_dict", "candidates": n, "seconds": dt,
            "cand_per_s": n / dt}


def bench_rules_device(batch: int, n_rules: int = 8,
                       n_flush: int = 6) -> dict:
    """Rules attack with ON-DEVICE mangling (rules/device.py): each base
    batch uploads once and every rule expands on device, so candidate
    H2D amortizes over the rule count.  The proof point for VERDICT r3
    #3: a rules attack must sustain the dict-path rate (host expansion
    at ~1M cand/s can't feed even one chip at the kernel rate).

    ``n_flush`` base batches stream through the engine pipeline — the
    client's steady-state shape (a dictionary is many engine batches),
    where the next batch's host work (simulate_lens, pack, H2D) hides
    behind the previous chunk's device compute exactly like dict_steady's
    pipelined batches.  A single-flush run serializes that host work
    against an idle device and understates the attack by ~9%; at 6
    flushes the recorded rate (~264k cand/s) matches the MASK path —
    candidate H2D amortized to 1/n_rules per candidate is effectively
    free, which is the whole point of the on-device rule engine.
    """
    from dwpa_tpu.rules import parse_rules

    rules = parse_rules([":", "u", "c", "$1", "^w", "t", "T0", "$1 $2 $3"])
    assert len(rules) == n_rules
    base = [b"devrule%07d" % i for i in range(batch * n_flush)]
    # Planted PSK = LAST base word through the LAST rule, so the find
    # cannot shrink the counted work.
    psk = rules[-1].apply(base[-1])
    engine = M22000Engine(
        [T.make_pmkid_line(psk, b"bench-essid", seed="rulesdev")],
        batch_size=batch,
    )
    # Warm the fused rules step (its step bucket is the ruleset's: 4 for
    # both rule lists here) + the crack step, so the timed run measures
    # steady state, not one-time XLA compiles.
    engine.crack_rules([b"warm%07d" % i for i in range(batch)],
                       [rules[0], rules[-1]])
    # Best of 2 (fresh engine per rep so the find doesn't shrink rep 2),
    # as in bench_dict_steady.
    dts = []
    for _ in range(2):
        eng = M22000Engine(
            [T.make_pmkid_line(psk, b"bench-essid", seed="rulesdev")],
            batch_size=batch,
        )
        founds = []
        dts.append(_timed(lambda: founds.extend(eng.crack_rules(base, rules)),
                          "bench:rules_device"))
        assert founds and founds[0].psk == psk, "rules_device missed the PSK"
    dt = min(dts)
    n = len(base) * len(rules)
    return {"label": "rules_device", "candidates": n, "rules": len(rules),
            "batches": n_flush, "seconds": dt, "cand_per_s": n / dt}


def bench_multi_bssid(words: int) -> dict:
    """Config #4: multi-BSSID work unit with ESSID-dedup amortization.

    5 nets share one ESSID (one PBKDF2 serves all five, the scheduler's
    grouping trick, get_work.php:96-109) plus 3 distinct-ESSID nets; the
    effective net-checks/s exceeds raw PMK/s by the sharing factor.
    """
    psk = b"benchpass4"
    lines = [T.make_eapol_line(psk, b"bench-shared", keyver=2, seed=f"mb{i}")
             for i in range(4)]
    lines.append(T.make_pmkid_line(psk, b"bench-shared", seed="mb4"))
    lines += [T.make_pmkid_line(psk, b"bench-solo-%d" % i, seed=f"ms{i}")
              for i in range(3)]
    n_nets, n_essids = len(lines), 4
    dict_words = [b"candidate-%06d" % i for i in range(words - 1)] + [psk]
    engine = M22000Engine(lines, batch_size=min(4096, words))
    engine.crack_batch([b"warm-%06d" % i for i in range(engine.batch_size)])
    with TRACER.span("bench:multi_bssid") as sp:
        founds = engine.crack(dict_words)
    dt = sp.seconds
    assert len(founds) == n_nets, f"multi-bssid: {len(founds)}/{n_nets} cracked"
    return {"label": "multi_bssid", "nets": n_nets, "essids": n_essids,
            "seconds": dt, "pmk_per_s": words * n_essids / dt,
            "net_checks_per_s": words * n_nets / dt}


def bench_dict_steady(batch: int, batches: int = 8) -> dict:
    """Engine product path at full batch: streaming dict crack with the
    three-deep pipeline (pack + H2D + hits-gate overlapped with compute).
    The gap to mask_pbkdf2 is the end-to-end overhead the engine fails
    to hide.  Best of 2, so a steady-state figure does not record a
    one-off transfer stall (seen on an earlier remote setup; unmeasured
    on a local chip)."""
    engine = M22000Engine(
        [T.make_pmkid_line(b"steadypass9", b"bench-steady", seed="st")],
        batch_size=batch,
    )
    engine.crack_batch([b"warm-%07d" % i for i in range(batch)])
    n = batches * batch
    with watch_compiles() as comp:
        dt = min(_timed(lambda: engine.crack(b"r%d-%08d" % (rep, i)
                                             for i in range(n)),
                        "bench:dict_steady")
                 for rep in range(2))
    return {"label": "dict_steady", "words": n, "seconds": dt,
            "pmk_per_s": n / dt, "recompiles": comp.count}


def bench_feed_overlap(batch: int, batches: int = 8) -> dict:
    """Candidate-feed pipeline overlap (dwpa_tpu/feed): the dict product
    path with host packing moved onto producer threads and H2D staged
    double-buffered — the input-pipeline shape ISSUE 3 built.

    Reports PMK/s next to the STARVE FRACTION: the share of the region's
    wall-clock the consumer spent blocked on an empty feed queue
    (``dwpa_feed_consumer_starve_seconds`` over the span).  ~0 means the
    host pipeline keeps the mesh fed (the feed's point); a fraction
    approaching the gap to mask_pbkdf2 means the host stages are the
    bottleneck — scale --feed-workers or the native packer, not the
    device.  The stall fraction is the mirror (producers blocked on a
    full queue = device-bound, the healthy state).  An isolated registry
    keeps this run's histograms out of the process-wide scrape numbers.
    """
    from dwpa_tpu.feed import CandidateFeed
    from dwpa_tpu.obs import MetricsRegistry

    engine = M22000Engine(
        [T.make_pmkid_line(b"feedpass77", b"bench-feed", seed="fo")],
        batch_size=batch,
    )
    engine.crack_batch([b"warm-%07d" % i for i in range(batch)])
    n = batches * batch
    reg = MetricsRegistry()
    feed = CandidateFeed((b"feed-%08d" % i for i in range(n)),
                         batch_size=batch, depth=2, producers=1,
                         prepack=engine.host_packer(), registry=reg,
                         name="bench")
    with watch_compiles() as comp:
        with TRACER.span("bench:feed_overlap") as sp:
            engine.crack_blocks(feed)
        dt = sp.seconds
    feed.close()
    snap = reg.snapshot()

    def _hist(nm):
        s = snap.get(nm, {}).get("samples") or [{}]
        return float(s[0].get("sum", 0.0))

    starve = _hist("dwpa_feed_consumer_starve_seconds")
    stall = _hist("dwpa_feed_producer_stall_seconds")
    return {"label": "feed_overlap", "words": n, "seconds": dt,
            "pmk_per_s": n / dt,
            "starve_fraction": starve / dt, "stall_fraction": stall / dt,
            "queue_depth": 2, "producers": 1, "recompiles": comp.count}


def bench_pmkstore(batch: int, batches: int = 4, overlap: float = 0.875) -> dict:
    """Persistent PMK store (dwpa_tpu/pmkstore): cold-vs-warm PMK/s on an
    overlapping dictionary pair.

    The cold pass cracks dictionary A with an empty store — every block
    is all-miss (plain-path shapes) and its PMKs write back after the
    device fetch.  The warm pass cracks dictionary B, which shares
    ``overlap`` of A's words SPREAD UNIFORMLY through the stream (every
    8th word is fresh at the default 7/8), so every block takes the
    mixed hit/miss path: PBKDF2 runs only on the compacted miss
    sub-batch (bucketed to <= 3 static widths — ``recompiles_warm``
    proves the bound holds) while cached PMKs are gathered in around it.
    The speedup ceiling is 1/(1-overlap); the measured ratio is how much
    of the skipped PBKDF2 the store actually returns.  ``hit_ratio``
    comes from the same isolated registry the store records to, so the
    headline and the live telemetry cannot disagree.
    """
    import tempfile

    from dwpa_tpu.feed import CandidateFeed
    from dwpa_tpu.obs import MetricsRegistry
    from dwpa_tpu.pmkstore import PMKStore

    n = batches * batch
    reg = MetricsRegistry()
    line = T.make_pmkid_line(b"not-in-either-dict", b"bench-store", seed="pks")
    # Warm the plain crack-step shapes first (18-char words, like the
    # dict below) so the COLD pass measures PBKDF2, not XLA compiles;
    # the store-specific shapes compile inside the warm pass, where the
    # sentinel counts them.
    warm_eng = M22000Engine([line], batch_size=batch)
    warm_eng.crack_batch([b"storewarm-%08d" % i for i in range(batch)])

    def run(words, label):
        eng = M22000Engine([line], batch_size=batch, pmk_store=store)
        feed = CandidateFeed(iter(words), batch_size=batch, depth=2,
                             producers=1, prepack=eng.host_packer(),
                             registry=MetricsRegistry(), name=label)
        with TRACER.span(f"bench:{label}") as sp:
            eng.crack_blocks(feed)
        feed.close()
        return sp.seconds

    with tempfile.TemporaryDirectory() as td:
        store = PMKStore(td, registry=reg)
        dict_a = [b"storeword-%08d" % i for i in range(n)]
        period = max(2, round(1 / (1 - overlap)))
        dict_b = [dict_a[i] if i % period else b"freshword-%08d" % i
                  for i in range(n)]
        # One-time mixed-shape warmup at the warm pass's hit ratio: one
        # block whose hits are seeded host-side (hashlib IS the oracle
        # PMK) compiles the bucketed miss-PBKDF2 + mix-gather shapes
        # outside the timed region; the sentinel around it records the
        # mixed path's bounded compile count (the <= 3 acceptance bound),
        # and the timed warm pass below must then add ZERO.
        import hashlib

        mixwarm = [b"mixwarm-%010d" % i for i in range(batch)]
        seeded = [w for i, w in enumerate(mixwarm) if i % period]
        store.put(b"bench-store", seeded,
                  [hashlib.pbkdf2_hmac("sha1", w, b"bench-store", 4096, 32)
                   for w in seeded])
        with watch_compiles() as mixed_comp:
            run(mixwarm, "pmkstore_mixwarm")
        cold_s = run(dict_a, "pmkstore_cold")
        with watch_compiles() as comp:
            warm_s = run(dict_b, "pmkstore_warm")
        hit_ratio = reg.value("dwpa_pmkstore_hit_ratio") or 0.0
    return {"label": "pmkstore", "words": n, "batch": batch,
            "overlap": 1 - 1 / period,
            "cold_seconds": cold_s, "warm_seconds": warm_s,
            "cold_pmk_per_s": n / cold_s, "warm_pmk_per_s": n / warm_s,
            "warm_speedup": cold_s / warm_s, "hit_ratio": hit_ratio,
            "mixed_compiles": mixed_comp.count, "recompiles_warm": comp.count}


def bench_dict_cache(batch: int, feed_words: int = 200_000,
                     batches: int = 2) -> dict:
    """bench:dict_cache — the packed-dict-cache acceptance measurement.

    Feed-only legs: one ~200k-word gz dict drained through
    ``DictFeedSource`` + ``CandidateFeed`` cold (gunzip + native pack +
    the cache write riding along) and then warm (mmap'd packed chunks,
    zero gunzip, zero per-word packing; the prep materialization memcpy
    IS counted — it is the warm path's real per-block cost).  The
    headline ``warm_speedup`` is warm/cold words/s: the host-side
    feed-rate multiplier an 8-chip mesh's repeat passes see.

    E2E legs: a planted-PSK dict cracked cold then warm through the
    engine's pre-packed bypass (``host_packer(pre=...)``) — the found
    list and per-batch consumed counts must be IDENTICAL, a mid-stream
    resume skip must account identically, and the warm pass must add
    zero XLA compiles (``recompiles_warm``).
    """
    import gzip
    import tempfile

    from dwpa_tpu.feed import CandidateFeed, DictCache, DictFeedSource
    from dwpa_tpu.gen.dicts import md5_file
    from dwpa_tpu.obs import MetricsRegistry

    def write_dict(td, ws, name):
        path = os.path.join(td, name + ".gz")
        with open(path, "wb") as f:
            f.write(gzip.compress(b"\n".join(ws) + b"\n"))
        return path, md5_file(path)

    def drain(units, cache, prepack=None, skip=0, engine=None,
              on_batch=None):
        src = DictFeedSource(units, batch_size=batch, cache=cache,
                             skip=skip, name="bench_dcache")
        feed = CandidateFeed(None, batch_size=batch, frames=src,
                             producers=2, prepack=prepack,
                             registry=MetricsRegistry(), name="bench_dcache")
        try:
            if engine is not None:
                return engine.crack_blocks(feed, on_batch=on_batch)
            n = 0
            for blk in feed:
                n += blk.count
            return n
        finally:
            feed.close()

    out = {"label": "dict_cache", "batch": batch, "feed_words": feed_words}
    with tempfile.TemporaryDirectory() as td:
        ws = [b"dcachebench-%09d" % i for i in range(feed_words)]
        fpath, fh = write_dict(td, ws, "feedleg")
        cache = DictCache(os.path.join(td, "dc"))
        # feed-only spans launch no device work — nothing to sync
        with TRACER.span("bench:dict_cache_cold") as sp:
            n = drain([(fpath, fh)], cache)
        out["cold_words_per_s"] = n / sp.seconds
        with TRACER.span("bench:dict_cache_warm") as sp:
            n = drain([(fpath, fh)], cache)
        out["warm_words_per_s"] = n / sp.seconds
        out["warm_speedup"] = (out["warm_words_per_s"]
                               / out["cold_words_per_s"])
        out["cache_bytes"] = cache._bytes_used()

        # -- e2e: the warm feed composing with the engine's pre-packed
        # bypass; plain crack shapes warm OUTSIDE the timed region
        psk = b"benchpass1"
        n2 = batches * batch
        ws2 = [b"dcache-e2e-%09d" % i for i in range(n2 - 1)] + [psk]
        epath, eh = write_dict(td, ws2, "e2eleg")
        line = T.make_pmkid_line(psk, b"bench-dcache")
        M22000Engine([line], batch_size=batch).crack_batch(
            [b"dcachewarm0-%07d" % i for i in range(batch)])
        ecache = DictCache(os.path.join(td, "dc2"))

        def crack(cache_, skip=0):
            consumed = []
            eng = M22000Engine([line], batch_size=batch)
            founds = drain([(epath, eh)], cache_,
                           prepack=eng.host_packer(), skip=skip,
                           engine=eng,
                           on_batch=lambda c, f: consumed.append(c))
            return [f.psk for f in founds], consumed

        with TRACER.span("bench:dict_cache_e2e_cold") as sp:
            cold_f, cold_c = crack(ecache)    # populates dc2
        e2e_cold = sp.seconds
        with watch_compiles() as comp:
            with TRACER.span("bench:dict_cache_e2e_warm") as sp:
                warm_f, warm_c = crack(ecache)
        e2e_warm = sp.seconds
        assert warm_f == cold_f == [psk], "cold/warm found-list parity"
        assert warm_c == cold_c, "cold/warm consumed parity"
        # resume parity: a mid-stream skip accounts identically whether
        # it replays the gzip prefix or seeks the block index
        skip = n2 // 3
        rf_cold, rc_cold = crack(None, skip=skip)
        rf_warm, rc_warm = crack(ecache, skip=skip)
        assert rf_cold == rf_warm == [psk] and rc_cold == rc_warm, \
            "cold/warm resume parity"
        out.update(e2e_words=n2, e2e_cold_pmk_per_s=n2 / e2e_cold,
                   e2e_warm_pmk_per_s=n2 / e2e_warm,
                   recompiles_warm=comp.count)
    return out


def bench_small_units(nunits: int = 8, words_per_unit: int = 1000,
                      batch: int = None) -> dict:
    """bench:small_units — the unit-fusion acceptance measurement.

    The structural gap this quantifies (see unit_overhead and the
    dict_steady-vs-pmkid_dict ratio): a stream of SMALL ESSID-group x
    dict work units runs each unit alone, padding its ~1k candidates to
    the full compiled batch width — per-unit fixed costs plus dead
    padding lanes, not the PBKDF2 kernel, bound aggregate PMK/s.

    Serial leg: one engine per unit (the client's per-unit loop), each
    cracking its own 1k-word dict at the configured batch.  Fused leg:
    ONE engine over all the units' lines, ``crack_fused`` packing the
    same candidates into one mixed-ESSID batch with per-lane salt
    gather (dwpa_tpu/sched).  Same candidates, same founds — the
    speedup is pure fill.  The compile sentinel around the fused leg
    must read 0: both legs run after same-shaped warmups, so the
    headline ratio is steady-state, not compile noise.
    """
    from dwpa_tpu.sched import fused_width

    batch = batch or 131072
    nmesh = len(jax.devices())

    def make_units(tag):
        units = []
        for i in range(nunits):
            psk = ("fusedpass-%s-%03d" % (tag, i)).encode()
            essid = ("bench-small-%s-%d" % (tag, i)).encode()
            line = T.make_pmkid_line(psk, essid, seed=f"su-{tag}-{i}")
            words = [("su%s%d-%07d" % (tag, i, j)).encode()
                     for j in range(words_per_unit - 1)] + [psk]
            units.append((line, essid, words, psk))
        return units

    # Warm both legs' shapes outside the timed regions: the serial crack
    # step at the full batch, and the fused per-lane step + verify at
    # the width the timed unit mix lands on.
    for line, _, words, _ in make_units("warm-serial")[:1]:
        M22000Engine([line], batch_size=batch).crack(words)
    warm = make_units("warm-fused")
    M22000Engine([u[0] for u in warm], batch_size=batch).crack_fused(
        [(u[1], u[2]) for u in warm], max_units=nunits)

    units = make_units("run")
    n = nunits * words_per_unit
    expected = sorted((e, p) for _, e, _, p in units)

    serial_found = []
    with TRACER.span("bench:small_units_serial") as sp:
        for line, _, words, _ in units:
            for f in M22000Engine([line], batch_size=batch).crack(words):
                serial_found.append((f.line.essid, f.psk))
    serial_s = sp.seconds

    fused_eng = M22000Engine([u[0] for u in units], batch_size=batch)
    fb_stats = []
    with watch_compiles() as comp:
        with TRACER.span("bench:small_units_fused") as sp:
            fused = fused_eng.crack_fused(
                [(u[1], u[2]) for u in units], max_units=nunits,
                on_fused=lambda fb: fb_stats.append((len(fb.units), fb.fill)))
        fused_s = sp.seconds
    fused_found = [(f.line.essid, f.psk) for f in fused]
    assert sorted(serial_found) == expected, "serial leg missed a planted PSK"
    founds_identical = sorted(fused_found) == sorted(serial_found)
    assert founds_identical, "fused leg's founds differ from the serial leg"

    return {"label": "small_units", "units": nunits,
            "words_per_unit": words_per_unit, "batch": batch,
            "fused_width": fused_width(batch, nmesh, n),
            "serial_seconds": serial_s, "fused_seconds": fused_s,
            "serial_pmk_per_s": n / serial_s, "fused_pmk_per_s": n / fused_s,
            "aggregate_speedup": serial_s / fused_s,
            "units_per_batch": max(u for u, _ in fb_stats),
            "fill_fraction": max(f for _, f in fb_stats),
            "founds_identical": founds_identical,
            "recompiles": comp.count}


def bench_device_streams(batch: int = None, batches: int = 12) -> dict:
    """bench:device_streams — lockstep DP dispatch vs per-device streams.

    Leg 1 cracks a framed stream the lockstep way: every block split
    1/ndev across the ``shard_map`` mesh, a psum hits-gate barriering
    all devices per batch.  Leg 2 cracks the SAME stream with the
    device-stream executor (dwpa_tpu/parallel/streams.py): each device
    runs whole blocks on its own single-device engine, pulled from a
    shared queue — identical founds, no cross-device collective.  The
    compile sentinel wraps the warm streams leg at 0.

    The straggler pair quantifies the executor's headline property.
    Run A: all streams crack junk blocks at their natural rate.  Run B:
    stream 0's engine is wrapped to dawdle on every collect.  Because
    streams share nothing but the queue, the other streams' BUSY rate
    (blocks per second not spent waiting on the queue) must hold —
    ``min_retained`` is the worst non-straggler B/A busy-rate ratio and
    the acceptance floor is 0.9.  Under lockstep the same wrap would
    drag every device to the straggler's pace.
    """
    import time as _time

    from dwpa_tpu.feed import frame_blocks
    from dwpa_tpu.parallel import StreamExecutor, default_mesh

    batch = batch or 131072
    # equal device width on both legs: lockstep splits each block over
    # the full mesh, streams give each of the same devices whole blocks
    devices = list(jax.devices())
    nstreams = len(devices)

    def make_lines(tag):
        # three ESSID groups: the forced-host CPU lockstep leg stalls
        # its AllReduce rendezvous when too many collective-bearing
        # steps are in flight (seen from ~7 groups); streams don't care
        return [T.make_pmkid_line(b"streampass-%d" % i,
                                  b"bench-stream-%s-%d" % (tag, i),
                                  seed=f"ds-{tag.decode()}-{i}")
                for i in range(3)]

    n = batch * batches
    words = [b"dsjunk-%08d" % i for i in range(n)]
    for i in range(3):              # plant each PSK in a different block
        words[batch * (i * batches // 3) + 17 + i] = b"streampass-%d" % i

    # Warm both legs' shapes outside the timed regions (junk words so
    # the warm engines never prune).
    warm_words = [b"dswarm-%07d" % i for i in range(batch)]
    M22000Engine(make_lines(b"wl"), batch_size=batch).crack(warm_words)
    M22000Engine(make_lines(b"ws"), batch_size=batch).crack_streams(
        frame_blocks(iter(warm_words * nstreams), batch), devices=devices)

    lock_eng = M22000Engine(make_lines(b"run"), batch_size=batch)
    with TRACER.span("bench:device_streams_lockstep") as sp:
        lock_founds = lock_eng.crack_blocks(
            frame_blocks(iter(words), lock_eng.batch_size))
    lock_s = sp.seconds

    st_eng = M22000Engine(make_lines(b"run"), batch_size=batch)
    with watch_compiles() as comp:
        with TRACER.span("bench:device_streams") as sp:
            st_founds = st_eng.crack_streams(
                frame_blocks(iter(words), st_eng.batch_size),
                devices=devices)
    streams_s = sp.seconds
    founds_identical = (
        sorted((f.line.essid, f.psk) for f in st_founds)
        == sorted((f.line.essid, f.psk) for f in lock_founds))
    assert founds_identical, "streams leg's founds differ from lockstep"
    assert len(st_founds) == 3, "a planted PSK was missed"

    # Straggler pair: same junk workload, run B wraps stream 0's engine.
    drag = max(0.02, lock_s / batches)
    sblocks = 4 * nstreams

    class _Dawdle:
        def __init__(self, eng):
            self._eng = eng

        def __getattr__(self, name):
            return getattr(self._eng, name)

        def _collect(self, disp):
            _time.sleep(drag)
            return self._eng._collect(disp)

    def busy_rates(straggle):
        def factory(device):
            eng = M22000Engine(make_lines(b"st"), batch_size=batch,
                               mesh=default_mesh(devices=[device]))
            if straggle and device is devices[0]:
                return _Dawdle(eng)
            return eng

        ex = StreamExecutor(factory, devices)
        t0 = _time.perf_counter()
        ex.run(frame_blocks(iter(b"stjunk-%08d" % i
                                 for i in range(batch * sblocks)), batch))
        wall = _time.perf_counter() - t0
        return [st.blocks_done / max(1e-9, wall - st.wait_s)
                for st in ex.streams]

    rates_a = busy_rates(False)
    rates_b = busy_rates(True)
    retained = [rates_b[i] / rates_a[i] for i in range(1, nstreams)]

    return {"label": "device_streams", "batch": batch, "batches": batches,
            "streams": nstreams,
            "lockstep_seconds": lock_s, "streams_seconds": streams_s,
            "lockstep_pmk_per_s": n / lock_s,
            "streams_pmk_per_s": n / streams_s,
            "aggregate_speedup": lock_s / streams_s,
            "founds_identical": founds_identical,
            "straggler_drag_s": drag,
            "min_retained": min(retained), "retained": retained,
            "recompiles_warm": comp.count}


def bench_mesh_aggregate(batch: int = None, n_flush: int = 4) -> dict:
    """bench:mesh_aggregate — the mesh-aggregate candidate pipeline
    acceptance measurement (on-device rule expansion as pass 2).

    Three legs over the SAME base-word stream and rule set:

    1. host-feed flat — the pre-mesh-aggregate regime: every (word,
       rule) pair interpreted on the host CPU, the EXPANDED candidates
       packed and shipped (H2D bytes x n_rules), cracked lockstep;
    2. lockstep rules — ``crack_rules_blocks`` on the full mesh: base
       blocks ship compact, expansion is on-device, but every block
       splits 1/ndev with a psum hits-gate barriering the mesh;
    3. mesh aggregate — ``crack_rules_streams``: each device pulls
       whole base blocks from the shared queue and expands rules
       directly ahead of its own PBKDF2 dispatch, no cross-device
       traffic at all.

    Founds must be identical across all three; the compile sentinel
    wraps the warm streams leg at 0.  ``aggregate_speedup`` is leg 2 /
    leg 3 and ``host_expand_ratio`` is leg 1 / leg 3 (how much the
    compact base feed buys over shipping expanded candidates).
    """
    from dwpa_tpu.feed import frame_blocks
    from dwpa_tpu.rules import parse_rules

    batch = batch or 131072
    devices = list(jax.devices())
    rules = parse_rules([":", "u", "c", "$1", "^w", "t", "T0", "$1 $2 $3"])
    base = [b"meshagg%07d" % i for i in range(batch * n_flush)]
    # Planted PSK = LAST base word through the LAST rule, so the find
    # cannot shrink the counted work on any leg.
    psk = rules[-1].apply(base[-1])
    lines = [T.make_pmkid_line(psk, b"bench-essid", seed="meshagg")]
    n = len(base) * len(rules)

    def expanded():
        for w in base:
            for r in rules:
                out = r.apply(w)
                if out is not None:
                    yield out

    # Warm every shape outside the timed regions: the host-feed crack
    # step, the lockstep rules step, and each stream's single-device
    # rules step (junk words so no engine prunes).
    warm = [b"meshwarm%06d" % i for i in range(batch)]
    M22000Engine(lines, batch_size=batch).crack(list(warm))
    M22000Engine(lines, batch_size=batch).crack_rules(
        list(warm), [rules[0], rules[-1]])
    M22000Engine(lines, batch_size=batch).crack_rules_streams(
        frame_blocks(iter(warm * len(devices)), batch),
        [rules[0], rules[-1]], devices=devices)

    host_eng = M22000Engine(lines, batch_size=batch)
    with TRACER.span("bench:mesh_aggregate_hostfeed") as sp:
        host_founds = host_eng.crack(expanded())
    host_s = sp.seconds

    lock_eng = M22000Engine(lines, batch_size=batch)
    with TRACER.span("bench:mesh_aggregate_lockstep") as sp:
        lock_founds = lock_eng.crack_rules_blocks(
            frame_blocks(iter(base), batch), rules)
    lock_s = sp.seconds

    st_eng = M22000Engine(lines, batch_size=batch)
    with watch_compiles() as comp:
        with TRACER.span("bench:mesh_aggregate") as sp:
            st_founds = st_eng.crack_rules_streams(
                frame_blocks(iter(base), batch), rules, devices=devices)
    st_s = sp.seconds

    founds_identical = (
        sorted((f.line.essid, f.psk) for f in st_founds)
        == sorted((f.line.essid, f.psk) for f in lock_founds)
        == sorted((f.line.essid, f.psk) for f in host_founds))
    assert founds_identical, "mesh-aggregate legs disagree on founds"
    assert st_founds and st_founds[0].psk == psk, "planted PSK missed"

    return {"label": "mesh_aggregate", "batch": batch, "rules": len(rules),
            "candidates": n, "streams": len(devices),
            "hostfeed_seconds": host_s, "lockstep_seconds": lock_s,
            "aggregate_seconds": st_s,
            "hostfeed_pmk_per_s": n / host_s,
            "lockstep_pmk_per_s": n / lock_s,
            "aggregate_pmk_per_s": n / st_s,
            "aggregate_speedup": lock_s / st_s,
            "host_expand_ratio": host_s / st_s,
            "founds_identical": founds_identical,
            "recompiles_warm": comp.count}


def bench_resilience(batch: int = None, words: int = 20_000,
                     fault_rate: float = 0.10, seed: int = 10) -> dict:
    """Crack-loop throughput under transport faults (resilient transport
    + found outbox).

    Three loopback work units over the same dict geometry: a warmup leg
    (pays the compiles), a fault-free reference leg, and a leg under a
    seeded ``fault_rate`` schedule (drop/timeout/http_5xx/slow) plus a
    forced put_work reject redriven through the found outbox.  Backoff
    and circuit cooldowns run on the chaos VirtualClock, so the faulted
    leg's wall time is crack work plus fault *handling* only — the
    degraded loop must never park the devices behind a real backoff
    sleep.  Tracks ``retention`` (faulted PMK/s over clean PMK/s;
    acceptance floor 0.8) and ``recompiles_faulted`` (must stay 0:
    fault handling is host logic and must not perturb device shapes).
    """
    import gzip as _gzip
    import hashlib as _hashlib
    import random as _random
    import tempfile

    from dwpa_tpu.chaos import (ChaosTransport, FaultPlan, VirtualClock,
                                WsgiTransport)
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient
    from dwpa_tpu.client.protocol import CircuitBreaker, ServerAPI
    from dwpa_tpu.server import Database, ServerCore, make_wsgi_app

    if batch is None:
        batch = 131072
    batch = min(batch, max(256, words // 4))
    psk = b"benchpass-res1"
    wordlist = [b"resword%07d" % i for i in range(words - 1)] + [psk]
    blob = _gzip.compress(b"\n".join(wordlist) + b"\n")
    dhash = _hashlib.md5(blob).hexdigest()

    def build_server(td):
        core = ServerCore(Database(":memory:"),
                          dictdir=os.path.join(td, "dicts"),
                          capdir=os.path.join(td, "caps"))
        core.add_hashlines([T.make_pmkid_line(psk, b"bench-res",
                                              seed="res1")])
        core.db.x("UPDATE nets SET algo = ''")
        os.makedirs(core.dictdir, exist_ok=True)
        with open(os.path.join(core.dictdir, "res.txt.gz"), "wb") as f:
            f.write(blob)
        core.add_dict("dict/res.txt.gz", "res.txt.gz", dhash,
                      len(wordlist), rules=None)
        return core

    def run_leg(td, plan, span):
        """One full work unit (get_work -> crack -> submit) under
        ``plan``; returns (result, seconds, client, clock)."""
        clock = VirtualClock()
        api = ServerAPI("http://loopback/", max_tries=0, backoff=2.0,
                        sleep=clock.sleep, rng=_random.Random(seed),
                        breaker=CircuitBreaker(threshold=5, cooldown=4.0,
                                               clock=clock.now))
        api.retry.clock = clock.now
        api._transport = ChaosTransport(
            WsgiTransport(make_wsgi_app(build_server(td))), plan,
            sleep=clock.sleep)
        cfg = ClientConfig(base_url="http://loopback/",
                           workdir=os.path.join(td, "work"),
                           batch_size=batch, dictcount=1,
                           device_streams="off")
        client = TpuCrackClient(cfg, api=api, log=lambda *a, **k: None)
        work = client.api.get_work(1)
        box = {}
        s = _timed(lambda: box.setdefault("res", client.process_work(work)),
                   span)
        return box["res"], s, client, clock

    with tempfile.TemporaryDirectory() as td:
        run_leg(os.path.join(td, "warm"), FaultPlan(seed),
                "bench:resilience_warmup")
        res0, clean_s, _, _ = run_leg(os.path.join(td, "clean"),
                                      FaultPlan(seed),
                                      "bench:resilience_clean")
        plan = FaultPlan(seed, rate=fault_rate,
                         kinds=("drop", "timeout", "http_5xx", "slow"))
        plan.force("put_work", "reject")
        with watch_compiles() as comp:
            res1, fault_s, client1, clock1 = run_leg(
                os.path.join(td, "chaos"), plan, "bench:resilience")
        # The rejected submission sits in the outbox; redrive until the
        # seeded schedule lets a clean exchange through.
        for _ in range(25):
            if not client1.outbox.pending_count():
                break
            clock1.sleep(client1.api.breaker.cooldown)
            try:
                client1._drain_outbox()
            except ConnectionError:
                continue

    n = res0.candidates_tried
    faults = [k for _, _, k in plan.schedule() if k is not None]
    return {"label": "resilience", "words": words, "batch": batch,
            "fault_rate": fault_rate,
            "clean_seconds": clean_s, "faulted_seconds": fault_s,
            "clean_pmk_per_s": n / clean_s,
            "faulted_pmk_per_s": res1.candidates_tried / fault_s,
            "retention": (res1.candidates_tried / fault_s) / (n / clean_s),
            "faults_injected": len(faults),
            "founds_delivered": bool(res0.founds) and bool(res1.founds)
            and client1.outbox.pending_count() == 0,
            "recompiles_faulted": comp.count}


def bench_server_load(sessions: int = 2000, threads: int = 16,
                      nets: int = 200, dicts: int = 20) -> dict:
    """Server core under a loopback client storm (epoch-leased scheduler
    + admission control, PR: crash-safe server core).

    ``sessions`` client sessions (each a get_work -> put_work release
    pair over ``chaos.WsgiTransport``, naps on a VirtualClock) are driven
    by ``threads`` workers against two same-geometry servers: the legacy
    per-request scheduling scan (``use_queue=False``) and the
    precomputed issuable-unit queue.  Reports issues/s, accepts/s and
    the server-side p99 request latency from the
    ``dwpa_http_request_seconds`` histogram; ``queue_speedup`` is the
    issues/s ratio (queue over scan — the pop path must win).
    """
    import json as _json
    import threading as _threading

    from dwpa_tpu.chaos import VirtualClock, WsgiTransport
    from dwpa_tpu.obs import MetricsRegistry
    from dwpa_tpu.server import Database, ServerCore, make_wsgi_app

    # capacity: nets x dicts issuable units must cover the sessions
    assert nets * dicts >= 2 * sessions, "geometry too small for sessions"

    def build_server(use_queue):
        reg = MetricsRegistry()
        core = ServerCore(Database(":memory:"), registry=reg,
                          use_queue=use_queue, max_inflight=0)
        lines = [T.make_pmkid_line(b"load-psk-%04d" % i,
                                   b"LoadNet%04d" % i, seed=f"load{i}")
                 for i in range(nets)]
        core.add_hashlines(lines)
        core.db.x("UPDATE nets SET algo = ''")
        for i in range(dicts):
            core.add_dict(f"dict/load{i}.txt.gz", f"load{i}",
                          "0" * 32, 1000 + i)
        return core, make_wsgi_app(core)

    def p99(reg):
        fam = reg.histogram("dwpa_http_request_seconds")
        counts = [0] * (len(fam.bucket_bounds) + 1)
        total = 0
        for child in list(fam._children.values()):
            total += child.value
            for i, c in enumerate(child.buckets):
                counts[i] += c
        if not total:
            return 0.0
        need, acc = 0.99 * total, 0
        for i, c in enumerate(counts):
            acc += c
            if acc >= need:
                return fam.bucket_bounds[i] if i < len(fam.bucket_bounds) \
                    else float("inf")
        return float("inf")

    def run_leg(use_queue, span):
        core, app = build_server(use_queue)
        issued = [0] * threads
        accepted = [0] * threads
        clock = VirtualClock()

        def worker(w):
            wsgi = WsgiTransport(app)
            body = _json.dumps({"dictcount": 1}).encode()
            for _ in range(sessions // threads):
                try:
                    raw = wsgi("http://loop/?get_work=2.2.0", body,
                               {"Content-Type": "application/json"})
                except Exception:
                    clock.sleep(0.01)  # 429/503: virtual nap, retry next
                    continue
                if raw in (b"No nets", b"Version"):
                    continue
                work = _json.loads(raw)
                issued[w] += 1
                sub = _json.dumps({"hkey": work["hkey"],
                                   "epoch": work["epoch"],
                                   "cand": []}).encode()
                try:
                    if wsgi("http://loop/?put_work", sub,
                            {"Content-Type": "application/json"}) == b"OK":
                        accepted[w] += 1
                except Exception:
                    clock.sleep(0.01)

        ts = [_threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        s = _timed(lambda: [[t.start() for t in ts],
                            [t.join() for t in ts]], span)
        return {"issued": sum(issued), "accepted": sum(accepted),
                "issues_per_s": sum(issued) / s,
                "accepts_per_s": sum(accepted) / s,
                "p99_request_s": p99(core.registry), "seconds": s}

    scan = run_leg(False, "bench:server_load_scan")
    queue = run_leg(True, "bench:server_load_queue")
    return {"label": "server_load", "sessions": sessions,
            "threads": threads, "nets": nets, "dicts": dicts,
            "scan": scan, "queue": queue,
            "queue_speedup": (queue["issues_per_s"]
                              / max(scan["issues_per_s"], 1e-9))}


def bench_server_precrack(nets: int = 48, group: int = 16,
                          vendor_words: int = 256, imei_words: int = 32,
                          batch: int = 2048) -> dict:
    """Batched server-side pre-crack vs the scalar per-candidate sweep
    (PR: batched pre-crack).

    ``nets`` synthetic PMKID nets in ``nets // group`` sibling groups
    share an ESSID, mirroring the war-driving capture shape the fused
    sweep exists for: the scalar loop pays one PBKDF2 per (net,
    candidate) while the fused wave dedups every shared (essid, word)
    pair to a single derivation.  Candidate mix per net: vendor pack +
    IMEI sweep + Single/Pattern mutations, plus replay/dict rows fed by
    one pre-cracked seed per group.  One net per group carries a
    last-vendor-word PSK so each leg must scan the full pack before its
    hit; the rest are misses (full sweep).  Reports candidates/s for
    both legs, whether they cracked the exact same free-found set, and
    the warm-path recompile count (must be 0).
    """
    from dwpa_tpu.models import hashline as hl
    from dwpa_tpu.obs import MetricsRegistry
    from dwpa_tpu.oracle import m22000 as oracle
    from dwpa_tpu.server import Database, ServerCore
    from dwpa_tpu.server.core import SERVER_NC
    from dwpa_tpu.server.db import long2mac
    from dwpa_tpu.server.precrack import PrecrackEngine

    groups = nets // group

    def essid_of(i):
        return b"PrecrackBench%02d" % (i % groups)

    def psk_of(i):
        if i % group == 0:  # group seed: cracked before either sweep
            return b"benchsecret-%02d!" % (i % groups)
        if i % group == 1:  # hit on the LAST vendor word: full pack scan
            return essid_of(i).lower() + b"-key-%03d" % (vendor_words - 1)
        return b"bench-miss-%04d" % i  # unmatchable: full sweep

    gens = [
        lambda bssid, ssid: [("BenchVendor",
                              ssid.lower() + b"-key-%03d" % k)
                             for k in range(vendor_words)],
        lambda bssid, ssid: [("IMEI", b"3526%011d" % k)
                             for k in range(imei_words)],
    ]

    def build_server():
        core = ServerCore(Database(":memory:"), registry=MetricsRegistry())
        core.add_hashlines([T.make_pmkid_line(psk_of(i), essid_of(i),
                                              seed=f"pcb{i}")
                            for i in range(nets)])
        rows = core.db.q("SELECT * FROM nets ORDER BY net_id")
        for i in range(0, nets, group):  # crack the group seeds
            core._try_accept(rows[i], psk_of(i))
        core.db.x("UPDATE nets SET algo = 'Manual' "
                  "WHERE n_state = 1 AND algo IS NULL")
        return core

    def scalar_sweep(core):
        # the per-candidate loop the engine supersedes (keygen_precompute
        # shape): same candidate stream, same per-net tx, but one full
        # PBKDF2 per check_key_m22000 call
        eng = PrecrackEngine(core, device="off", batch=batch,
                             generators=gens)
        db = core.db
        corpus = eng._dict_corpus()
        plan = []
        for net in db.q("SELECT * FROM nets WHERE algo IS NULL "
                        "AND n_state = 0 ORDER BY net_id"):
            h = hl.parse(net["struct"])
            plan.append((net, h, eng._collect(net, h,
                                              long2mac(net["bssid"]),
                                              corpus)))
        found = total = 0
        for net, h, cands in plan:
            total += len(cands)
            tried, hit = [], None
            for _, algo, cand in cands:
                tried.append((algo, cand))
                r = oracle.check_key_m22000(h, [cand], nc=SERVER_NC)
                if r:
                    hit = (algo, cand, r)
                    break
            with core._getwork_lock:
                with db.tx():
                    for algo, cand in tried:
                        db.x("INSERT INTO rkg(net_id, algo, pass) "
                             "VALUES (?, ?, ?)",
                             (net["net_id"], algo, cand))
                    if hit:
                        _, cand, r = hit
                        core._mark_cracked(net["net_id"], r[0], r[3],
                                           r[1] or 0, r[2] or "")
                        db.x("UPDATE rkg SET n_state = 1 "
                             "WHERE net_id = ? AND pass = ?",
                             (net["net_id"], cand))
                        found += 1
                    db.x("UPDATE nets SET algo = ? WHERE net_id = ?",
                         (hit[0] if hit else "", net["net_id"]))
        return {"cracked": found, "candidates": total}

    def founds(core):
        return {(r["ssid"], r["pass"]) for r in core.db.q(
            "SELECT ssid, pass FROM nets WHERE n_state = 1")}

    # compile the fused widths off the clock
    PrecrackEngine(build_server(), device="auto", batch=batch,
                   generators=gens).run(limit=nets)

    sc, fc = build_server(), build_server()
    box = {}
    s_scalar = _timed(lambda: box.update(scalar=scalar_sweep(sc)),
                      "bench:server_precrack_scalar")
    feng = PrecrackEngine(fc, device="auto", batch=batch, generators=gens)
    with watch_compiles() as comp:
        s_fused = _timed(lambda: box.update(fused=feng.run(limit=nets)),
                         "bench:server_precrack_fused")
    cands = box["scalar"]["candidates"]
    out = {"label": "server_precrack", "nets": nets, "groups": groups,
           "candidates": cands,
           "scalar_seconds": s_scalar, "fused_seconds": s_fused,
           "scalar_cands_per_s": cands / max(s_scalar, 1e-9),
           "fused_cands_per_s": cands / max(s_fused, 1e-9),
           "speedup": s_scalar / max(s_fused, 1e-9),
           "free_founds": box["fused"]["cracked"],
           "found_parity": (founds(sc) == founds(fc)
                            and box["scalar"]["cracked"]
                            == box["fused"]["cracked"] == groups),
           "recompiles_warm": comp.count}
    # Attached-device leg: the recurring sweep as operators run it on a
    # TPU host — device derivations forced on, same candidate stream,
    # same found set, warm shapes already paid by the auto leg above.
    dc = build_server()
    deng = PrecrackEngine(dc, device="on", batch=batch, generators=gens)
    with watch_compiles() as dcomp:
        s_dev = _timed(lambda: box.update(dev=deng.run(limit=nets)),
                       "bench:server_precrack_device")
    out.update(device_seconds=s_dev,
               device_cands_per_s=cands / max(s_dev, 1e-9),
               device_found_parity=(founds(dc) == founds(fc)
                                    and box["dev"]["cracked"] == groups),
               device_recompiles_warm=dcomp.count)
    return out


def bench_mask_shards(batch: int = None, words: int = 20_000,
                      ceiling_pmk_per_s: float = None) -> dict:
    """bench:mask_shards — server-issued mask-shard unit vs the same
    keyspace pre-materialized as a dictionary (smart-keyspace vertical).

    Two loopback servers over the SAME 20k-word keyspace
    ``^benchm[01]\\d{4}$``: the mask leg holds only a ks row, so
    get_work hands the client a ``dicts: []`` unit whose candidates are
    generated ON DEVICE from ``(mask, custom, skip, limit)`` alone; the
    dict leg ships the identical words (odometer order) as a gzipped
    wordlist.  The PSK is the LAST keyspace word, so both legs sweep
    the full range before their hit.  Both legs run the full
    get_work -> crack -> put_work exchange through a byte-counting
    WSGI transport: ``mask_wire_bytes_per_cand`` must be ~0 (the
    unit's JSON framing only) while the dict leg pays the wordlist
    download.  Tracks found parity, the mask leg's rate against the
    dict leg and against the raw ``bench_mask_pbkdf2`` ceiling
    (``vs_mask_ceiling``; acceptance floor 0.9), and the warm-path
    recompile count (must be 0).
    """
    import gzip as _gzip
    import hashlib as _hashlib
    import tempfile

    from dwpa_tpu.chaos import WsgiTransport
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient
    from dwpa_tpu.client.protocol import ServerAPI
    from dwpa_tpu.gen.mask import mask_words
    from dwpa_tpu.server import Database, ServerCore, make_wsgi_app

    if batch is None:
        batch = 131072
    # keyspace = 2 * 10^digits: snap ``words`` to the nearest such size
    digits = max(1, len(str(max(words, 20) // 2)) - 1)
    words = 2 * 10 ** digits
    batch = min(batch, max(256, words // 4))
    essid = b"bench-maskks"
    pass_re = r"^benchm[01]\d{%d}$" % digits
    # the dict leg's wordlist IS the compiled keyspace in odometer order
    wordlist = list(mask_words("benchm?1" + "?d" * digits, {"1": b"01"}))
    assert len(wordlist) == words
    psk = wordlist[-1]
    blob = _gzip.compress(b"\n".join(wordlist) + b"\n")
    dhash = _hashlib.md5(blob).hexdigest()

    def build_server(td, leg):
        core = ServerCore(Database(":memory:"),
                          dictdir=os.path.join(td, "dicts"),
                          capdir=os.path.join(td, "caps"))
        core.add_hashlines([T.make_pmkid_line(psk, essid, seed="maskks1")])
        core.db.x("UPDATE nets SET algo = ''")
        if leg == "mask":
            core.ks_add(r"^bench-maskks$", pass_re)
        else:
            os.makedirs(core.dictdir, exist_ok=True)
            with open(os.path.join(core.dictdir, "ks.txt.gz"), "wb") as f:
                f.write(blob)
            core.add_dict("dict/ks.txt.gz", "ks.txt.gz", dhash,
                          len(wordlist), rules=None)
        return core

    class CountingTransport(WsgiTransport):
        """WsgiTransport that meters both wire directions."""

        def __init__(self, app):
            super().__init__(app)
            self.wire_bytes = 0

        def __call__(self, url, body=None, headers=None):
            self.wire_bytes += len(url) + len(body or b"")
            data = super().__call__(url, body, headers)
            self.wire_bytes += len(data)
            return data

    def run_leg(td, leg, span):
        core = build_server(td, leg)
        api = ServerAPI("http://loopback/", max_tries=1,
                        sleep=lambda s: None)
        api._transport = transport = CountingTransport(make_wsgi_app(core))
        cfg = ClientConfig(base_url="http://loopback/",
                           workdir=os.path.join(td, "work"),
                           batch_size=batch, dictcount=1,
                           device_streams="off")
        client = TpuCrackClient(cfg, api=api, log=lambda *a, **k: None)
        work = client.api.get_work(1)
        assert (work["dicts"] == []) == (leg == "mask")
        box = {}
        s = _timed(lambda: box.setdefault("res", client.process_work(work)),
                   span)
        return box["res"], s, transport.wire_bytes, core

    with tempfile.TemporaryDirectory() as td:
        # warm both trace families off the clock: the on-device mask
        # generator and the host-packed dict feed
        run_leg(os.path.join(td, "wm"), "mask", "bench:mask_shards_warmup")
        run_leg(os.path.join(td, "wd"), "dict", "bench:mask_shards_warmup")
        with watch_compiles() as comp:
            mres, mask_s, mask_wire, mcore = run_leg(
                os.path.join(td, "mask"), "mask", "bench:mask_shards")
        dres, dict_s, dict_wire, dcore = run_leg(
            os.path.join(td, "dict"), "dict", "bench:mask_shards_dict")

    mask_rate = mres.candidates_tried / max(mask_s, 1e-9)
    # both legs also sweep the client's pass-1 SSID-targeted host
    # candidates (same ESSID -> same count), so tried is words + a few
    # dozen on each side; parity demands the counts MATCH, not == words
    parity = ([f.psk for f in mres.founds] == [f.psk for f in dres.founds]
              == [psk]
              and mres.candidates_tried == dres.candidates_tried >= words
              and mcore.db.q1("SELECT n_state FROM nets")["n_state"] == 1
              and dcore.db.q1("SELECT n_state FROM nets")["n_state"] == 1)
    out = {"label": "mask_shards", "words": words, "batch": batch,
           "mask_seconds": mask_s, "dict_seconds": dict_s,
           "mask_cands_per_s": mask_rate,
           "dict_cands_per_s": dres.candidates_tried / max(dict_s, 1e-9),
           "rate_vs_dict": dict_s / max(mask_s, 1e-9),
           "mask_wire_bytes": mask_wire, "dict_wire_bytes": dict_wire,
           "mask_wire_bytes_per_cand": mask_wire / words,
           "dict_wire_bytes_per_cand": dict_wire / words,
           "found_parity": parity,
           "recompiles_warm": comp.count}
    if ceiling_pmk_per_s:
        out["vs_mask_ceiling"] = mask_rate / ceiling_pmk_per_s
    return out


def _timed(fn, name: str = "bench:timed") -> float:
    """One rep as a span: the body must sync its own device work (every
    caller passes an engine crack* call, which does)."""
    with TRACER.span(name) as sp:
        fn()
    return sp.seconds


def bench_host_feed(words: int = 200_000) -> dict:
    """Host candidate pipeline (SURVEY §7.3.3 "keeping the device fed").

    Tracks the rates BASELINE.md's host-pipeline table quotes so they
    cannot rot invisibly: rule expansion (serial and pooled),
    the C++ candidate packer, and the gzip DictStream reader.
    """
    import gzip
    import os
    import tempfile

    from dwpa_tpu.gen import DictStream
    from dwpa_tpu.rules import apply_rules, parse_rules
    from dwpa_tpu.native import pack_candidates_fast

    rules = parse_rules([":", "u", "c", "$1", "^w", "r", "T0", "$1 $2 $3"])
    base = [b"feedword%07d" % i for i in range(words // len(rules))]
    out = {"label": "host_feed"}

    with TRACER.span("bench:host_feed.rules_serial") as sp:
        n = sum(1 for _ in apply_rules(rules, base))
    out["rules_serial_cand_per_s"] = n / sp.seconds

    # Warm the worker pool first: spawning 2 interpreters costs ~10 s
    # once per process, amortized over a whole work unit in production.
    # force_pool bypasses the few-cores guard — the point here is to
    # track the true pooled rate even on hosts where the guard trips.
    sum(1 for _ in apply_rules(rules, base[:64], workers=2, force_pool=True))
    with TRACER.span("bench:host_feed.rules_pooled2") as sp:
        n = sum(1 for _ in apply_rules(rules, base, workers=2,
                                       force_pool=True))
    out["rules_pooled2_cand_per_s"] = n / sp.seconds

    cands = [b"packword%07d" % i for i in range(words)]
    with TRACER.span("bench:host_feed.pack_fast") as sp:
        pack_candidates_fast(cands, 8, 63, words)
    out["pack_fast_cand_per_s"] = words / sp.seconds

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "feed.txt.gz")
        with open(path, "wb") as f:
            f.write(gzip.compress(b"\n".join(cands) + b"\n"))
        with TRACER.span("bench:host_feed.dictstream") as sp:
            n = sum(1 for _ in DictStream(path))
        out["dictstream_words_per_s"] = n / sp.seconds
    return out


def bench_unit_overhead(pmkid_small: dict) -> dict:
    """Decompose the fixed per-unit overhead configs #1/#2 are bound by.

    Two engine runs at the SAME batch size but different word counts
    give ``t = overhead + words / rate``; solving the pair isolates the
    constant (compile-cache hits, host pack, hits-gate sync) from the
    marginal per-word rate at that batch size — so a regression in
    either is visible.  (``rate`` here is the small-batch slope, NOT
    the full-batch kernel rate — see dict_steady for that.)
    """
    psk = b"benchpass1"
    w1 = pmkid_small["words"]
    cfg_big = bench_engine_dict(
        T.make_pmkid_line(psk, b"bench-essid"), psk, 16 * w1, "pmkid_big",
        batch=min(4096, w1),
    )
    t1 = pmkid_small["seconds"]
    w2, t2 = cfg_big["words"], cfg_big["seconds"]
    rate = (w2 - w1) / max(t2 - t1, 1e-9)
    # The two-point fit can come out negative (timing noise on two
    # sub-second runs); the clamp keeps the headline sane, but the RAW
    # value is reported alongside — a run where fixed_overhead_s reads
    # 0.0 exactly is a clamped fit, not a free engine, and a real
    # per-unit overhead regression must not hide behind the clamp.
    raw = t1 - w1 / rate
    return {"label": "unit_overhead", "small_words": w1, "big_words": w2,
            "batch": min(4096, w1),
            "smallbatch_pmk_per_s": rate, "fixed_overhead_s": max(0.0, raw),
            "fixed_overhead_raw_s": raw}


def _round(cfg: dict) -> dict:
    return {k: round(v, 4) if isinstance(v, float) else v for k, v in cfg.items()}


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures the TPU only; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    # Persistent compilation cache (JAX_COMPILATION_CACHE_DIR, else the
    # fixed <repo>/.xla_cache): the PBKDF2 first-compile is paid once
    # per machine, not once per bench run.
    from dwpa_tpu.utils.compcache import enable_compilation_cache

    enable_compilation_cache()
    batch = 131072
    words = 1000

    selftest = tpu_selftest()
    mask = bench_mask_pbkdf2(batch)
    psk = b"benchpass1"
    pmkid = bench_engine_dict(
        T.make_pmkid_line(psk, b"bench-essid"), psk, words, "pmkid_dict"
    )
    eapol = bench_engine_dict(
        T.make_eapol_line(psk, b"bench-essid", keyver=2), psk, words, "eapol_dict"
    )
    rules = bench_rules_dict(words)
    rules_dev = bench_rules_device(batch)
    multi = bench_multi_bssid(words)
    steady = bench_dict_steady(batch)
    feed = bench_host_feed()
    feed_ov = bench_feed_overlap(batch)
    pmkstore = bench_pmkstore(batch)
    dcache = bench_dict_cache(batch)
    small_units = bench_small_units()
    streams = bench_device_streams()
    mesh_agg = bench_mesh_aggregate()
    overhead = bench_unit_overhead(pmkid)
    resilience = bench_resilience(batch)
    server_load = bench_server_load()
    server_precrack = bench_server_precrack(batch=batch)
    mask_shards = bench_mask_shards(batch, ceiling_pmk_per_s=mask["pmk_per_s"])

    value = mask["pmk_per_s"]
    print(
        json.dumps(
            {
                "metric": "PMK/s per chip (m22000 PBKDF2, ?d x8 mask, config #5)",
                "value": round(value),
                "unit": "PMK/s",
                "vs_baseline": round(value / PER_CHIP_TARGET, 4),
                "platform": jax.devices()[0].device_kind,
                "configs": {
                    "tpu_selftest": _round(selftest),
                    "mask_pbkdf2": _round(mask),
                    "pmkid_dict": _round(pmkid),
                    "eapol_dict": _round(eapol),
                    "rules_dict": _round(rules),
                    "rules_device": _round(rules_dev),
                    "multi_bssid": _round(multi),
                    "dict_steady": _round(steady),
                    "host_feed": _round(feed),
                    "feed_overlap": _round(feed_ov),
                    "pmkstore": _round(pmkstore),
                    "dict_cache": _round(dcache),
                    "small_units": _round(small_units),
                    "device_streams": _round(streams),
                    "mesh_aggregate": _round(mesh_agg),
                    "unit_overhead": _round(overhead),
                    "resilience": _round(resilience),
                    "server_load": _round(server_load),
                    "server_precrack": _round(server_precrack),
                    "mask_shards": _round(mask_shards),
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
