"""The system under test, wired as a volunteer runs it.

``Server`` is ``python -m dwpa_tpu.server serve`` in a thread of this
process on a loopback socket (a copy of ``chip_smoke.Server``).
``BenchClient`` is the CLI's ``TpuCrackClient`` with the benchmark's
recording around the calls into each layer: ``get_work``, the dict
download, engine construction, ``put_work`` and every span of the
client's own tracer are written into the profiler's trace as
``bench:<name>`` annotations, so idle gaps on the device can be put
down to what the host was doing.  Nothing here changes what the
client does.
"""

import contextlib
import os
import sys
import threading
import time

import jax

from dwpa_tpu.client import main as client_main
from dwpa_tpu.client.main import ClientConfig, TpuCrackClient
from dwpa_tpu.obs.metrics import MetricsRegistry


class WindowClosed(Exception):
    """Raised at the first ``get_work`` after the measured window."""


class LoopFailure(Exception):
    """The loop asked for something a closed loopback run never needs."""


class Server:
    """``serve`` in a thread of this process, on a loopback socket.

    Ingestion takes ``serve --no-precrack-ingest``'s path: the ingest
    pre-crack sweep is host PBKDF2 over vendor, pattern and corpus
    candidates (about 70 ms a net on the sandbox CPU), and on this
    traffic it finds nothing by construction (random ESSIDs, PSKs drawn
    from the dictionary or holding a letter no candidate holds).
    ``release`` then does what the sweep does for a net it cannot
    crack: it releases the net to volunteers (``algo = ''``).
    """

    def __init__(self, workdir: str):
        from wsgiref.simple_server import WSGIRequestHandler, make_server

        from dwpa_tpu.server import Database, ServerCore, make_wsgi_app
        from dwpa_tpu.server.__main__ import (ThreadingWSGIServer,
                                              _start_materializer)

        class QuietHandler(WSGIRequestHandler):
            def log_message(self, *args):
                pass

        os.makedirs(workdir, exist_ok=True)
        self.core = ServerCore(Database(os.path.join(workdir, "wpa.db")),
                               dictdir=os.path.join(workdir, "dicts"),
                               capdir=os.path.join(workdir, "caps"))
        self.httpd = make_server("127.0.0.1", 0, make_wsgi_app(self.core),
                                 server_class=ThreadingWSGIServer,
                                 handler_class=QuietHandler)
        self.url = f"http://127.0.0.1:{self.httpd.server_port}/"
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        name="bench-server", daemon=True)
        self._thread.start()
        self._mat = _start_materializer(self.core)

    def publish(self, words: list, name: str):
        from dwpa_tpu.server.tools import pack_dict

        pack_dict(self.core, words, name, rules=None)

    def ingest(self, groups: list) -> int:
        """Every group's lines as one hashline upload; returns nets added."""
        from dwpa_tpu.server.api import submit_capture

        lines = [ln for g in groups for ln in g.lines]
        new = submit_capture(self.core,
                             ("\n".join(lines) + "\n").encode())["new"]
        if new != len(lines):
            raise LoopFailure(f"ingest: {new} of {len(lines)} nets added")
        return new

    def release(self, essid: bytes = None):
        """Release every held net, or those of one ESSID group."""
        q, args = ("UPDATE nets SET algo = '' "
                   "WHERE algo IS NULL AND n_state = 0"), ()
        if essid is not None:
            q, args = q + " AND ssid = ?", (essid,)
        with self.core.db.tx():
            self.core.db.x(q, args)

    def nets(self) -> dict:
        """struct -> (n_state, pass) for every net."""
        return {r["struct"]: (r["n_state"],
                              None if r["pass"] is None else bytes(r["pass"]))
                for r in self.core.db.q(
                    'SELECT struct, n_state, "pass" FROM nets')}

    def close(self):
        if self._mat is not None:
            thread, stop = self._mat
            stop.set()
            thread.join(timeout=5.0)
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(timeout=5.0)
        self.core.db.close()


class Unit:
    """One work unit as the benchmark saw it, on the host clock."""

    def __init__(self, t_get: float):
        self.t_get = t_get           # get_work requested
        self.t_ack = None            # put_work acknowledged
        self.hkey = None
        self.hashes = []
        self.result = None           # the client's WorkResult
        self.done = 0                # candidates whose results were fetched


class Recorder:
    """What a run records: units and per-batch progress.
    Written from the client's loop thread; one lock guards every write
    (the repo's concurrency lint, DW302, asks for it)."""

    def __init__(self):
        self.units = []
        self.progress = []           # (t, candidates, essids) per batch
        self.current = None
        self._mu = threading.Lock()

    def begin_unit(self) -> Unit:
        unit = Unit(time.perf_counter())
        with self._mu:
            self.current = unit
            self.units.append(unit)
        return unit

    def fetched(self, done: int, essids: int):
        """The current unit's results are fetched up to ``done``."""
        with self._mu:
            unit = self.current
            if unit is not None and done > unit.done:
                self.progress.append(
                    (time.perf_counter(), done - unit.done, essids))
                unit.done = done

    def acked(self):
        with self._mu:
            if self.current is not None:
                self.current.t_ack = time.perf_counter()

    def finished(self, result):
        with self._mu:
            if self.current is not None:
                self.current.result = result

    @staticmethod
    def span(name: str):
        """A host phase, written into the profiler's trace."""
        return jax.profiler.TraceAnnotation(f"bench:{name}")


class BenchClient(TpuCrackClient):
    """The CLI's client, recorded.  ``gate(unit_index)`` runs before each
    ``get_work`` (the harness's window control); the transport may not
    sleep, since every sleep in ``run()`` is a NoNets nap or a retry
    backoff, which a loopback run never needs."""

    def __init__(self, cfg: ClientConfig, rec: Recorder, gate, log=None):
        super().__init__(cfg, registry=MetricsRegistry(), log=log)
        self.rec = rec
        self.gate = gate
        api = self.api
        api.sleep = self._no_sleep
        self._api_get_work = api.get_work
        self._api_put_work = api.put_work
        self._api_download = api.download
        api.get_work = self._get_work
        api.put_work = self._put_work
        api.download = self._download
        tracer_span = self.tracer.span

        @contextlib.contextmanager
        def annotated(name, *args, **kwargs):
            with jax.profiler.TraceAnnotation(f"bench:{name}"), \
                    tracer_span(name, *args, **kwargs) as sp:
                yield sp

        self.tracer.span = annotated

    @staticmethod
    def _no_sleep(seconds):
        raise LoopFailure(f"client asked to sleep {seconds:.0f}s: the "
                          "server ran out of groups or refused a call")

    def _get_work(self, *args, **kwargs):
        self.gate(len(self.rec.units))
        unit = self.rec.begin_unit()
        with self.rec.span("get_work"):
            work = self._api_get_work(*args, **kwargs)
        unit.hkey = work["hkey"]
        unit.hashes = list(work["hashes"])
        return work

    def _put_work(self, *args, **kwargs):
        with self.rec.span("put_work"):
            ok = self._api_put_work(*args, **kwargs)
        self.rec.acked()
        return ok

    def _download(self, *args, **kwargs):
        with self.rec.span("dict_download"):
            return self._api_download(*args, **kwargs)

    def process_work(self, work):
        res = super().process_work(work)
        self.rec.finished(res)
        return res

    def _write_resume(self, work):
        # the per-batch checkpoint: "done" is the unit's count of
        # candidates whose device results the client has fetched
        prog = work.get("_progress")
        if prog is not None:
            essids = len({h.split("*")[5] for h in work["hashes"]}) or 1
            self.rec.fetched(int(prog.get("done", 0))
                             + int(prog.get("mask_done", 0)), essids)
        super()._write_resume(work)


@contextlib.contextmanager
def timed_engine(rec: Recorder):
    """Time every ``M22000Engine`` the client builds as ``engine_init``."""
    base = client_main.M22000Engine

    class TimedEngine(base):
        def __init__(self, *args, **kwargs):
            with rec.span("engine_init"):
                super().__init__(*args, **kwargs)

    client_main.M22000Engine = TimedEngine
    try:
        yield
    finally:
        client_main.M22000Engine = base


def stderr_log(msg: str):
    print(f"client: {msg}", file=sys.stderr, flush=True)
