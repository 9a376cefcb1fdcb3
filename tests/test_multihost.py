"""True multi-process mesh validation (SURVEY §5.8).

Launches two worker processes that join one jax.distributed cluster
(4 virtual CPU devices each -> an 8-device global dp mesh — standing in
for two TPU hosts of one slice), each feeding its host-local candidate
shard through ``shard_candidates``'s multi-process branch.  The planted
PSK lives on process 1, so process 0 only sees the hit through the
cross-host psum — the collective the whole multi-host design rides on.
"""

import gzip
import hashlib
import os
import socket
import subprocess
import sys
import threading

WORKER = os.path.join(os.path.dirname(__file__), "mh_worker.py")
CLIENT_WORKER = os.path.join(os.path.dirname(__file__), "mh_client_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _communicate_all(procs, timeout):
    """communicate() on every worker, killing ALL of them if any hangs:
    a collective desync (the bug class these tests exist to catch) parks
    the workers in a jax collective forever — they must not outlive the
    test holding CPUs and the coordinator port."""
    try:
        return [p.communicate(timeout=timeout) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)
        raise


def test_two_process_mesh_crack_step():
    port = str(_free_port())
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(pid), port],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in (0, 1)
    ]
    outs = _communicate_all(procs, timeout=480)
    assert all(p.returncode == 0 for p in procs), \
        [(p.returncode, o[1][-800:]) for p, o in zip(procs, outs)]
    outs = [o[0] for o in outs]
    for pid, out in enumerate(outs):
        assert f"RESULT {pid} hits=1" in out, (pid, out)
        # the planted find decodes on BOTH hosts — including process 0,
        # which never held the candidate bytes (ADVICE r2: the find path
        # must work when the hit lives on a non-addressable shard)
        assert f"ENGINE {pid} finds=1 psk=multihost99 pruned=True" in out, \
            (pid, out)
        # mask path: the hit word is materialized from the global
        # keyspace column on both hosts (no candidate exchange)
        assert f"MASK {pid} finds=1 psk=12345607" in out, (pid, out)
        # partial final batch: in-window word found, padding column
        # beyond the limit never reported
        assert f"MASKPART {pid} finds=12345605" in out, (pid, out)
        # an all-invalid shard on one host must not desync the slice:
        # the other host's find still lands on both
        assert f"PAD {pid} finds=1 psk=padlock-psk7" in out, (pid, out)
        # device-rules across processes: the 'u' find (process 1's rows)
        # decodes from the replicated bitmask on both hosts, and the
        # host-tail '@b' find (process 0's block) crosses hosts through
        # the candidate exchange
        assert f"RULES {pid} finds=RULEBASE19X,rulease02x" in out, (pid, out)
        # every verify kind (PMKID + keyver 1/2/3) through the mixed
        # group assembly, each find decoded cross-host
        assert f"MIXED {pid} finds=4 keyvers=1,2,3,100" in out, (pid, out)
        # more owned hits than the per-round exchange cap: two
        # fixed-shape candidate-exchange rounds, no hit dropped
        assert f"DENSE {pid} finds=1 psk=densepsk77 rounds=2" in out, \
            (pid, out)


def test_mixed_version_slice_refuses_to_start(tmp_path):
    """A slice whose hosts run different client builds must exit with a
    clear error on EVERY host before any work — stream order is
    version-dependent, so proceeding would desync the collectives."""
    coord = str(_free_port())
    procs = [
        subprocess.Popen(
            [sys.executable, CLIENT_WORKER, str(pid), coord, "1",
             str(tmp_path)] + (["0.0.0-mixed"] if pid == 1 else []),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in (0, 1)
    ]
    outs = _communicate_all(procs, timeout=240)
    for pid, (p, (out, err)) in enumerate(zip(procs, outs)):
        assert p.returncode != 0, (pid, out, err)
        assert "mixed client versions" in err, (pid, err[-800:])


def test_two_process_client_single_volunteer(tmp_path):
    """The full CLIENT as one multi-host volunteer: a real socket server
    in this process, two client processes spanning one jax.distributed
    mesh.  Process 0 makes every server call exactly once (update probe,
    get_work, put_work); process 1 receives the unit only through the
    client's broadcast layer; the PSK is reachable only via a device
    rule, so pass 2 runs the sharded fused rules step across both
    hosts' devices — and the net ends cracked server-side."""
    from wsgiref.simple_server import WSGIServer, make_server
    import socketserver

    from dwpa_tpu import testing as tfx
    from dwpa_tpu.rules import parse_rule
    from dwpa_tpu.server import Database, ServerCore, make_wsgi_app

    core = ServerCore(Database(str(tmp_path / "wpa.db")),
                      dictdir=str(tmp_path / "dicts"),
                      capdir=str(tmp_path / "caps"))
    os.makedirs(core.dictdir, exist_ok=True)
    base = [b"mhcword%03d" % i for i in range(40)]
    psk = parse_rule("u").apply(base[23])  # only a device rule reaches it
    core.add_hashlines([tfx.make_pmkid_line(psk, b"MhcNet", seed="mhc")])
    blob = gzip.compress(b"\n".join(base) + b"\n")
    path = os.path.join(core.dictdir, "mhc.txt.gz")
    open(path, "wb").write(blob)
    core.add_dict("dict/mhc.txt.gz", "mhc.txt.gz",
                  hashlib.md5(blob).hexdigest(), len(base), rules="u\n$Z")
    core.db.x("UPDATE nets SET algo = ''")

    hits = {"get_work": 0, "put_work": 0}
    app = make_wsgi_app(core)

    def counting_app(environ, start_response):
        q = environ.get("QUERY_STRING", "")
        for k in hits:
            if k in q:
                hits[k] += 1
        return app(environ, start_response)

    class TS(socketserver.ThreadingMixIn, WSGIServer):
        daemon_threads = True

    srv = make_server("127.0.0.1", 0, counting_app, server_class=TS)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        coord = str(_free_port())
        procs = [
            subprocess.Popen(
                [sys.executable, CLIENT_WORKER, str(pid), coord,
                 str(srv.server_address[1]), str(tmp_path)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for pid in (0, 1)
        ]
        outs = _communicate_all(procs, timeout=540)
    finally:
        srv.shutdown()
    assert all(p.returncode == 0 for p in procs), \
        [(p.returncode, o[1][-1500:]) for p, o in zip(procs, outs)]
    for pid, (out, _err) in enumerate(outs):
        assert f"MHCLIENT {pid} done=1 pot=yes" in out, (pid, out)
    row = core.db.q1("SELECT n_state, pass FROM nets")
    assert row["n_state"] == 1 and row["pass"] == psk
    # one volunteer, one conversation: process 0 only
    assert hits == {"get_work": 1, "put_work": 1}, hits
