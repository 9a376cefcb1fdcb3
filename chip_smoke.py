#!/usr/bin/env python3
"""Smoke run of the volunteer crack loop on the TPU — NOT a benchmark.

One process drives the system's main path once, through the entry points
a volunteer and an operator use:

- the real server (``ServerCore`` + ``make_wsgi_app`` behind the threaded
  server of ``python -m dwpa_tpu.server serve``) on a loopback socket, in
  a thread of this process, with the ingestion pre-crack wired as
  ``serve --precrack-device off`` wires it;
- captures and hashlines ingested through ``server.api.submit_capture``:
  two ESSID groups covering PMKID and EAPOL keyver 1, 2 and 3, one of
  them a multi-BSSID upload (2 x keyver 2, 2 x keyver 3, 1 PMKID);
- two dictionaries published through the ``pack-dict`` path: A, 1,000,000
  plain words, and B, 20,000 words with the bundled ``rules/wpa.rule``
  attached (about 2.1M candidates expanded on the device);
- ``TpuCrackClient(ClientConfig(...)).run()`` — what ``python -m
  dwpa_tpu.client`` calls — at the chip batch of 131,072: challenge gate,
  prewarm, get_work, dict download, both passes, put_work and the
  server's re-verification.

Then it checks that every planted PSK ended cracked in the server DB,
that the found set equals the host oracle's (``oracle/m22000.py``) over
the planted candidates and a seeded sample of the rest, that the client
retried no work unit in-process (``_process_with_recovery`` would hide a
device error or a compiler refusal behind a smaller batch), and that the
server's lease/coverage invariants hold.

``--chips 4`` runs the same units on the 4-chip mesh twice, with device
streams on and off, checks both against the oracle and each other, and
runs no other phase.  Lockstep splits the client's batch over the mesh,
so it gets the one-chip batch times the device count: every chip sees
131,072 candidates per batch in both modes.  To keep two runs inside a
four-chip call, dict B carries 8 rules of wpa.rule (one rules chunk, the
same step bucket), not all 105; the units are the same, only shorter.

The last stdout line is one JSON object.  Off the TPU, or when any check
fails, it says ``"ok": false`` and the exit code is non-zero.  The rates
printed on earlier lines are a smoke run's, not benchmark numbers.
"""

import argparse
import json
import os
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: The client's batch on the chip: bench.py's per-chip batch.  The CLI
#: default (16,384) under-fills a v5e chip; it is left as it is.
CHIP_BATCH = 131072

ESSID_1 = b"SmokeCorp-Guest"   # multi-BSSID upload: 2x k2, 2x k3, PMKID
ESSID_2 = b"SmokeHome-5G"      # EAPOL keyver 1 (one with an NC delta) + PMKID

#: The rule of wpa.rule that turns one dict-B word into group 2's PSK.
PLANTED_RULE = "c $2 $0 $2 $4"

#: ``--chips 4``: dict B's rules, one RULES_CHUNK of wpa.rule's lines
#: with PLANTED_RULE's step bucket (8), in place of the whole file.
MESH_RULES = (":", "c", "u", "l", "t", "C", "T0", PLANTED_RULE)


class SmokeFailure(Exception):
    """A phase's check failed; the message says which and why."""


def say(msg: str):
    print(f"smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phase 0: the device
# ---------------------------------------------------------------------------


def probe_device(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"jax {jax.__version__}, device_kind {dev['kind']!r}, "
        f"platform {dev['platform']}, device count {dev['count']}")
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] != chips:
        raise SmokeFailure(f"expected {chips} TPU device(s), JAX found "
                           f"{dev['count']} (pass --chips {dev['count']})")
    return dev


def pbkdf2_compile_check(batch: int) -> dict:
    """Cold-compile the mesh PBKDF2 step at ``batch`` and report whether
    the compiled program holds the Pallas kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dwpa_tpu.parallel import default_mesh
    from dwpa_tpu.parallel.mesh import DP_AXIS
    from dwpa_tpu.parallel.step import pmk_step

    mesh = default_mesh()
    pw = jax.ShapeDtypeStruct((batch, 16), jnp.uint32,
                              sharding=NamedSharding(mesh, P(DP_AXIS, None)))
    salt = jax.ShapeDtypeStruct((16,), jnp.uint32,
                                sharding=NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    compiled = pmk_step(mesh).lower(pw, salt, salt).compile()
    secs = time.perf_counter() - t0
    return {"compile_s": secs,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}


# ---------------------------------------------------------------------------
# Phase 1: the data, made from the seed
# ---------------------------------------------------------------------------


def _random_words(rng, n: int, alphabet: bytes, lo: int, hi: int) -> list:
    import numpy as np

    alpha = np.frombuffer(alphabet, dtype=np.uint8)
    chars = alpha[rng.integers(0, len(alpha), size=(n, hi))]
    lens = rng.integers(lo, hi + 1, size=n)
    return [row[:k].tobytes() for row, k in zip(chars, lens)]


def build_fixture(seed: int = 0, words_a: int = 1_000_000,
                  words_b: int = 20_000, rules_text: str = None) -> dict:
    """Seeded captures, hashlines and dictionaries with planted PSKs.

    Dict A holds group 1's PSK among its last 1,000 words; dict B holds
    the base word that ``PLANTED_RULE`` turns into group 2's PSK.  A's
    words may hold '-', which no rule of wpa.rule writes and no B word
    holds, so no candidate of B can equal group 1's PSK.
    ``rules_text`` defaults to the bundled wpa.rule; a smaller test
    passes a subset that keeps ``PLANTED_RULE``.
    """
    import numpy as np

    from dwpa_tpu import testing as T
    from dwpa_tpu.rules import parse_rule, wpa_rules_text

    rng = np.random.default_rng(seed)
    if rules_text is None:
        rules_text = wpa_rules_text()
    if PLANTED_RULE not in rules_text.splitlines():
        raise ValueError(f"rules must include {PLANTED_RULE!r}")
    dict_a = _random_words(rng, words_a,
                           b"abcdefghijklmnopqrstuvwxyz0123456789-", 8, 12)
    at = words_a - 1 - int(rng.integers(0, min(1000, words_a)))
    psk1 = b"smoke-%06d-a" % int(rng.integers(0, 10 ** 6))
    dict_a[at] = psk1
    dict_b = _random_words(rng, words_b, b"abcdefghijklmnopqrstuvwxyz", 6, 10)
    bt = int(rng.integers(0, words_b))
    dict_b[bt] = b"smokeword"
    psk2 = parse_rule(PLANTED_RULE).apply(dict_b[bt])

    cap1, n1 = T.make_handshake_capture(psk1, ESSID_1, seed=f"{seed}-c1")
    cap2, n2 = T.make_handshake_capture(psk1, ESSID_1, seed=f"{seed}-c2",
                                        with_pmkid=False)
    g1_lines = [T.make_eapol_line(psk1, ESSID_1, keyver=3, seed=f"{seed}-k3{i}")
                for i in range(2)]
    g2_lines = [
        T.make_eapol_line(psk2, ESSID_2, keyver=1, seed=f"{seed}-k1a"),
        T.make_eapol_line(psk2, ESSID_2, keyver=1, nc_delta=3, endian="LE",
                          seed=f"{seed}-k1b"),
        T.make_pmkid_line(psk2, ESSID_2, seed=f"{seed}-p2"),
    ]
    return {
        "uploads": [cap1, cap2, "\n".join(g1_lines).encode() + b"\n",
                    "\n".join(g2_lines).encode() + b"\n"],
        "expect_nets": n1 + n2 + len(g1_lines) + len(g2_lines),
        "dict_a": dict_a, "dict_b": dict_b, "rules_text": rules_text,
        "psk": {ESSID_1: psk1, ESSID_2: psk2},
        "planted": {ESSID_1: [psk1], ESSID_2: [dict_b[bt]]},
        "rng": rng,
    }


# ---------------------------------------------------------------------------
# Phase 2: the server
# ---------------------------------------------------------------------------


class Server:
    """``serve`` in a thread of this process, on a loopback socket."""

    def __init__(self, workdir: str):
        from wsgiref.simple_server import WSGIRequestHandler, make_server

        from dwpa_tpu.server import Database, ServerCore, make_wsgi_app
        from dwpa_tpu.server.__main__ import (ThreadingWSGIServer,
                                              _start_materializer)
        from dwpa_tpu.server.precrack import PrecrackEngine

        class QuietHandler(WSGIRequestHandler):
            def log_message(self, *args):
                pass

        self.core = ServerCore(Database(os.path.join(workdir, "wpa.db")),
                               dictdir=os.path.join(workdir, "dicts"),
                               capdir=os.path.join(workdir, "caps"))
        # serve's ingestion pre-crack, as --precrack-device off sets it:
        # one process holds the chip, and here that is the client's
        self.core.precrack = PrecrackEngine(self.core, device="off")
        self.httpd = make_server("127.0.0.1", 0, make_wsgi_app(self.core),
                                 server_class=ThreadingWSGIServer,
                                 handler_class=QuietHandler)
        self.url = f"http://127.0.0.1:{self.httpd.server_port}/"
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        name="smoke-server", daemon=True)
        self._thread.start()
        self._mat = _start_materializer(self.core)

    def close(self):
        if self._mat is not None:
            thread, stop = self._mat
            stop.set()
            thread.join(timeout=5.0)
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(timeout=5.0)
        self.core.db.close()


def ingest(core, fx: dict) -> int:
    """Every upload through ``submit_capture``; returns nets released."""
    from dwpa_tpu.server.api import submit_capture

    new = sum(submit_capture(core, blob)["new"] for blob in fx["uploads"])
    released = core.db.q1(
        "SELECT COUNT(*) c FROM nets WHERE algo = '' AND n_state = 0")["c"]
    if new != fx["expect_nets"] or released != new:
        raise SmokeFailure(f"ingest: {new} new nets, {released} released, "
                           f"expected {fx['expect_nets']}")
    return new


def publish_dicts(core, fx: dict):
    """Dicts A and B through pack-dict's code path (B with the rules)."""
    from dwpa_tpu.server.tools import pack_dict

    pack_dict(core, fx["dict_a"], "smoke-a", rules=None)
    pack_dict(core, fx["dict_b"], "smoke-b", rules=fx["rules_text"])


def seed_server(seed_dir: str, fx: dict):
    """Ingest and publish once into ``seed_dir``, whose DB and dicts each
    client run starts from (a copy: the server restarts on them, as
    ``serve`` does).  Returns ``(nets released, oracle found set)``."""
    shutil.rmtree(seed_dir, ignore_errors=True)
    os.makedirs(seed_dir)
    srv = Server(seed_dir)
    try:
        nets = ingest(srv.core, fx)
        publish_dicts(srv.core, fx)
        want = oracle_found_set(
            fx, srv.core.db.q("SELECT struct, ssid FROM nets"))
    finally:
        srv.close()
    return nets, want


# ---------------------------------------------------------------------------
# Phase 3: the client
# ---------------------------------------------------------------------------


def run_client(url: str, workdir: str, batch_size: int, units: int,
               device_streams: str = "auto"):
    """``TpuCrackClient.run()`` for ``units`` work units; returns the
    client (its ``results``, ``warm_s`` and ``registry`` are read by the
    checks)."""
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient

    class SmokeClient(TpuCrackClient):
        """The CLI's client, recording what the checks read.  Its
        transport may not sleep: every sleep in ``run()`` is a NoNets
        nap or a retry backoff, which on a loopback smoke means a unit
        was lost — fail at once instead of hanging."""

        def __init__(self, cfg):
            super().__init__(cfg)
            self.results = []
            self.warm_s = None
            self.api.sleep = self._no_sleep

        @staticmethod
        def _no_sleep(seconds):
            raise SmokeFailure(f"client asked to sleep {seconds:.0f}s: a "
                               "work unit was lost or the server refused")

        def challenge(self):
            t0 = time.perf_counter()
            ok = super().challenge()
            self.warm_s = time.perf_counter() - t0
            return ok

        def process_work(self, work):
            res = super().process_work(work)
            self.results.append(res)
            return res

    # pace_target=0 pins dictcount at 1, so each unit carries one dict
    # (the 900 s autotune would otherwise merge B's rules onto A)
    client = SmokeClient(ClientConfig(
        base_url=url, workdir=os.path.join(workdir, "client"),
        batch_size=batch_size, max_work_units=units, pace_target=0.0,
        device_streams=device_streams))
    done = client.run()
    if done != units:
        raise SmokeFailure(f"client ran {done} of {units} units")
    return client


# ---------------------------------------------------------------------------
# Phase 4: the checks
# ---------------------------------------------------------------------------


def oracle_found_set(fx: dict, nets, sample: int = 32) -> set:
    """The oracle's found set for ``nets`` (rows with ``struct``/``ssid``):
    every candidate a planted word yields (itself, or all of its rule
    expansions for dict B) plus a seeded sample of other candidates of
    both dicts.  Every other word is random, so a full oracle sweep
    could only add a 2**-128 coincidence."""
    from dwpa_tpu.models.m22000 import DEFAULT_NC
    from dwpa_tpu.oracle import m22000 as oracle
    from dwpa_tpu.rules import apply_rules, parse_rules

    rules = parse_rules(fx["rules_text"].splitlines())
    rng = fx["rng"]
    a, b = fx["dict_a"], fx["dict_b"]
    extra = [a[int(i)] for i in rng.integers(0, len(a), sample)]
    extra += list(apply_rules(
        rules, [b[int(i)] for i in rng.integers(0, len(b), 2)]))[:sample]
    found = set()
    for net in nets:
        essid = bytes(net["ssid"])
        cands = list(apply_rules(rules, fx["planted"][ESSID_2]))
        cands += fx["planted"][ESSID_1] + extra
        cands = [c for c in cands if 8 <= len(c) <= 63]
        # the client searches NC deltas up to its nc (default 8)
        hit = oracle.check_key_m22000(net["struct"], cands, nc=DEFAULT_NC)
        if hit is not None:
            found.add((net["struct"], hit[0]))
        elif essid in fx["psk"]:
            raise SmokeFailure(f"oracle: no candidate cracks a net of "
                               f"{essid!r}; the fixture is wrong")
    return found


def check_run(core, client, fx: dict, want: set) -> dict:
    """Server DB, oracle (``want``), retries and invariants after one
    client run."""
    from dwpa_tpu.chaos.dbfault import sweep_invariants

    nets = core.db.q('SELECT struct, ssid, keyver, n_state, "pass" FROM nets')
    cracked = {(n["struct"], bytes(n["pass"])) for n in nets
               if n["n_state"] == 1}
    missing = [(bytes(n["ssid"]), n["keyver"]) for n in nets
               if n["n_state"] != 1
               or bytes(n["pass"]) != fx["psk"][bytes(n["ssid"])]]
    if missing:
        raise SmokeFailure(f"planted PSKs not accepted by the server: "
                           f"{missing}")
    if cracked != want:
        raise SmokeFailure(f"found set != oracle: {len(cracked)} cracked, "
                           f"{len(want)} from the oracle")
    retries = client.registry.value("dwpa_client_engine_retries_total") or 0
    if retries:
        raise SmokeFailure(f"{retries:g} engine retries: a device error "
                           "was hidden behind a smaller batch")
    if not all(r.accepted for r in client.results):
        raise SmokeFailure("a put_work was not accepted")
    bad = sweep_invariants(core.db)
    if bad:
        raise SmokeFailure(f"server invariants: {bad}")
    kinds = {}
    for n in nets:
        k = "pmkid" if n["keyver"] == 100 else f"k{n['keyver']}"
        kinds[k] = kinds.get(k, 0) + 1
    on_device = client.registry.value("dwpa_rules_device_expanded_total")
    if not on_device:
        raise SmokeFailure("the device-rules pass expanded no candidate")
    return {"found": cracked, "kinds": kinds,
            "rules_on_device": int(on_device)}


# ---------------------------------------------------------------------------
# One run, and main
# ---------------------------------------------------------------------------

#: Units the server issues with dictcount pinned at 1: group 1 x B (no
#: find), group 2 x B (cracked, so never offered A) and group 1 x A.
UNITS = 3


def run_modes(count: int) -> list:
    """``(tag, device_streams, client batch)`` per client run on
    ``count`` chips.  Lockstep splits its batch over the mesh, so it is
    given ``count`` times the one-chip batch; a stream owns one chip and
    takes the one-chip batch whole."""
    if count == 1:
        return [("1 chip", "auto", CHIP_BATCH)]
    return [(f"{count} chips, device_streams=on", "on", CHIP_BATCH),
            (f"{count} chips, device_streams=off", "off",
             CHIP_BATCH * count)]


def smoke_run(workdir: str, seed_dir: str, fx: dict, want: set,
              batch_size: int, device_streams: str = "auto") -> dict:
    """Server up on a copy of the seeded DB and dicts, client run,
    checks, server down."""
    shutil.rmtree(workdir, ignore_errors=True)
    shutil.copytree(seed_dir, workdir)
    srv = Server(workdir)
    try:
        t0 = time.perf_counter()
        client = run_client(srv.url, workdir, batch_size, UNITS,
                            device_streams=device_streams)
        wall = time.perf_counter() - t0
        out = check_run(srv.core, client, fx, want)
    finally:
        srv.close()
    out.update(wall_s=wall, warm_s=client.warm_s,
               unit_s=[r.elapsed for r in client.results],
               pmks=sum(r.candidates_tried for r in client.results))
    return out


def _report(tag: str, r: dict):
    say(f"[{tag}] {r['nets']} nets by verify kind {r['kinds']}; all "
        f"planted PSKs accepted, found set == oracle, 0 engine retries, "
        f"server invariants clean")
    say(f"[{tag}] challenge+prewarm (cold compiles) {r['warm_s']:.1f} s; "
        f"unit seconds {[round(s, 2) for s in r['unit_s']]}; candidates "
        f"{r['pmks']} ({r['rules_on_device']} expanded by device rules); "
        f"client wall {r['wall_s']:.1f} s — smoke timings, not a benchmark")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: the main path on one chip (default); 4: the "
                        "same units on the 4-chip mesh, device streams "
                        "on and off, and nothing else")
    args = p.parse_args(argv)
    workdir = os.path.join(REPO, ".smoke_work")
    device = None
    try:
        import dwpa_tpu  # noqa: F401  (fails outside a checkout)
        from dwpa_tpu.utils.compcache import enable_compilation_cache

        device = probe_device(args.chips)
        enable_compilation_cache()
        fx = build_fixture(rules_text=None if args.chips == 1
                           else "\n".join(MESH_RULES))
        if args.chips == 1:
            chk = pbkdf2_compile_check(CHIP_BATCH)
            say(f"PBKDF2 step at B={CHIP_BATCH}: cold compile "
                f"{chk['compile_s']:.1f} s, tpu_custom_call "
                f"{chk['tpu_custom_call']}")
            if not chk["tpu_custom_call"]:
                raise SmokeFailure("the PBKDF2 step holds no Pallas kernel")
        seed_dir = os.path.join(workdir, "seed")
        nets, want = seed_server(seed_dir, fx)
        found = []
        for tag, streams, batch in run_modes(args.chips):
            r = smoke_run(os.path.join(workdir, "run"), seed_dir, fx, want,
                          batch, device_streams=streams)
            r["nets"] = nets
            _report(f"{tag}, batch {batch}", r)
            found.append(r["found"])
        if any(f != found[0] for f in found):
            raise SmokeFailure("device_streams on/off found sets differ")
        if args.chips > 1:
            say(f"{args.chips} chips: device_streams on and off found the "
                f"same set")
    except Exception as e:  # the boundary: report every failure as ok=false
        say(f"FAILED: {type(e).__name__}: {e}")
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}",
                          "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
