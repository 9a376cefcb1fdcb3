"""AST linter for the repo's JAX contracts (the rules tier-1 runs).

Every rule here encodes a hazard that has actually bitten this codebase
or its reference lineage: silent XLA recompiles, Python control flow
over tracers, host↔device syncs on the feed path, dtype promotions off
the uint32 crypto lattice, and benchmark timings that stop the clock
before the device finishes.  The type system sees none of these; they
surface as throughput collapses or mid-cron crashes on real hardware.

Rule codes (stable — referenced by baseline.json and the docs):

- **DW101 traced-python-branch** — Python ``if``/``while``/ternary/
  ``assert`` over a traced value, or a ``for`` loop iterating a tracer,
  inside a function handed to a trace entry point (``jax.jit``,
  ``shard_map``, ``vmap``, ``lax.scan``/``cond``/..., or the repo's
  ``_shard`` wrapper).  Branching on a tracer either raises a
  ConcretizationTypeError at runtime or — worse — silently bakes one
  branch into the compiled program.
- **DW102 uncached-jit** — ``jax.jit(...)`` whose compiled artifact
  cannot be reused: immediately invoked (``jax.jit(f)(x)``), or created
  inside a loop without being stored in a cache (subscript/attribute
  target).  Each fresh jit object owns a fresh compile cache, so these
  patterns recompile on every call — the exact failure the repo's
  ``_STEP_CACHE`` idiom exists to prevent.
- **DW103 off-lattice-dtype** — a float/int64/complex dtype reference
  inside ``ops/``.  The crypto kernels are uint32-lane by design
  (SHA/MD5/AES schedules); a float or 64-bit promotion silently
  doubles register pressure or truncates on TPU (where x64 is off).
- **DW104 host-sync-in-hot-path** — ``.item()``, dtype-less
  ``np.asarray(...)``, or ``jax.device_get`` in the engine hot-path
  modules (``parallel/step.py``, ``models/m22000.py``).  Each is a
  device→host sync that serializes the pipeline; intentional ones
  (the hits-gate, the rare-find decode) live in the baseline.
- **DW105 unsynced-timed-section** — a ``time.perf_counter()`` span in
  ``bench.py`` that launches device work but never forces completion
  (``block_until_ready``, ``np.asarray``, or an engine ``crack*`` call,
  which sync internally) before the clock stops.  Dispatch returns
  before the device finishes, so such a span measures the enqueue and
  overstates throughput (see bench.py's timing notes).
- **DW107 feed-thread-discipline** — the candidate-feed contract
  (``dwpa_tpu/feed``), two shapes: (a) a blocking synchronization call
  (``queue.get``/``queue.put``/``join``/``acquire``/``wait`` on a
  queue/lock/event-named receiver) inside a function under a JAX trace
  — a traced region that blocks on host synchronization either fails
  on a tracer or, worse, bakes a one-time value into the compiled
  program while serializing the pipeline it was supposed to overlap;
  (b) a feed producer function (``*produce*`` in ``dwpa_tpu/feed/``)
  touching a jax/jnp device API other than ``device_put``/
  ``shard_candidates`` — producer threads run pure host stages; any
  other device call from a thread races the consumer's dispatch order
  (fatal on a multi-process mesh, where enqueue order is a collective
  contract).
- **DW108 pmkstore-discipline** — the PMK-store contract
  (``dwpa_tpu/pmkstore``), two shapes: (a) store I/O — a ``lookup``/
  ``put``/``flush``/``close`` call on a store-named receiver, or an
  ``mmap`` segment mapping — inside a function under a JAX
  trace: store reads are host mmap/dict work and a traced region that
  touches them either fails on a tracer or bakes one lookup's result
  into the compiled program; (b) a write-back ``<store>.put(...)``
  outside the consumer thread's allowed set (``pmkstore/`` itself and
  the engine's post-fetch write-back in ``models/m22000.py``) — a
  producer-thread or client-side put would race the consumer's append
  ordering and could serialize a traced region on disk I/O.
- **DW109 fused-pad-width** — a ``np.zeros``/``np.empty`` ``[W, 16]``
  row-buffer allocation in the fused-batch packers (``sched/fuse.py``,
  ``pmkstore/stage.py``) whose width does not come from the static
  fused-width pad table (``fused_width``/``miss_width`` or a value
  derived from them).  Per-lane salt/candidate rows entering
  ``pmk_kernel`` at a data-dependent width would retrace the PBKDF2
  step per unit combination — the compile-per-work-unit failure the
  width tables exist to prevent (recompile-sentinel proof in tests).
- **DW110 stream-isolation** — the device-stream contract
  (``parallel/streams.py``, see ``STREAM_FILES``), three shapes: (a) a
  cross-device collective (``psum``/``all_gather``/...) anywhere in the
  file — a stream owns exactly one device, and a collective would
  barrier it against its siblings, reintroducing the lockstep coupling
  streams exist to remove (and deadlocking outright when streams run
  different block counts); (b) a blocking device→host fetch
  (``jax.device_get``/``block_until_ready``) inside a ``for``/``while``
  loop — the per-stream dispatch loop must stay async, its only sync
  being the engine's own hits-gate inside ``_collect``; (c) a
  ``device_put`` without an explicit device/sharding argument — a bare
  put lands on the default device, silently stacking every stream's
  arrays onto device 0 instead of the stream's own chip.
- **DW106 telemetry-discipline** — the obs-layer contract, two shapes:
  (a) a metric/span emission call (``.inc()``/``.dec()``/``.set()``/
  ``.observe()``, excluding jnp's ``x.at[i].set(v)`` functional update)
  inside a function under a JAX trace — telemetry is host-side by
  design, and an emission in traced code either fails on a tracer or
  silently bakes a stale value into the compiled program; (b) an obs
  span (``with tracer.span(...):`` body, or a ``.start(...)``/
  ``.stop()`` pair) in the instrumented files (``SPAN_FILES``) that
  launches device work without forcing completion before the clock
  stops — DW105's device-sync rule, ported to the span API.
- **DW111 dictcache-discipline** — the packed-dictionary-cache contract
  (``dwpa_tpu/feed/dictcache``), two shapes: (a) a dict-cache I/O call
  (``reader``/``writer``/``add_many``/``commit``/``abort``/``chunks``/
  ``evict`` on a cache-named receiver) inside a function under a JAX
  trace — cache reads are host mmap/file work and a traced region that
  touches them either fails on a tracer or bakes one chunk's bytes into
  the compiled program; (b) the same call anywhere outside the feed
  subsystem (``dwpa_tpu/feed/``) — dict-cache reads/writes belong to
  feed producer threads (``DictFeedSource`` drives them under the
  feed's source lock), the same seam discipline as DW107/DW108; client
  or engine code touching the cache directly would put file I/O on the
  consumer's dispatch path.
- **DW112 client-transport-confinement** — the resilient-transport
  contract (``dwpa_tpu/client/``, every file except ``protocol.py``):
  (a) no ``urllib`` import — a raw HTTP exchange outside ``ServerAPI``
  bypasses error classification, retry backoff, the circuit breaker
  and the outbox-backed submission path; (b) no bare ``time.sleep``
  call (nor ``from time import sleep``) — every nap must go through
  the injected ``api.sleep`` so chaos runs drive a virtual clock and
  the degraded-mode crack loop can never be parked on a hidden
  blocking sleep (``time.perf_counter`` and friends stay fine).
- **DW113 rules-device-expansion** — the mesh-aggregate feed contract
  (``STREAM_FILES`` plus the feed subsystem, ``FEED_DIRS``): no
  ``apply_rules(...)`` call or import, and no ``.apply(...)`` on a
  rule-valued receiver.  Device-eligible rules expand ON DEVICE via
  ``build_rules_step`` out of the engine's ``_rules_flush`` seam; a
  host interpreter call on a stream or feed-producer thread would
  re-serialize the expansion the mesh-aggregate path exists to remove
  (the host ships compact base blocks, not expanded candidates).  The
  engine's own host tail (``@``-purge rules, length-overflow pairs)
  lives in ``models/m22000.py``, outside this scope by design.
- **DW114 server-db-atomicity** — the server persistence contract
  (``dwpa_tpu/server/``): two or more ``db.x(...)`` write sites in one
  function body, outside a ``with db.tx():`` block, are a torn-write
  hazard — a crash (or an injected ``chaos.dbfault``) between them
  leaves the ledger half-updated.  Multi-statement sequences belong
  inside ``Database.tx()``; a SINGLE lexical write site is fine even
  in a loop (per-row autocommit around network calls, e.g. geolocate,
  is a deliberate pattern, not a tear).
- **DW115 precrack-scalar-verify** — a per-candidate
  ``check_key_m22000(h, [single_key], ...)`` call inside a ``for``/
  ``while`` loop in server code (``dwpa_tpu/server/``, excluding the
  sanctioned host-oracle fallback seam, ``server/precrack.py``).  Each
  such call pays a full PBKDF2-HMAC-SHA1 (4096 iterations, ~99% of an
  m22000 verdict) on the request/cron thread, once per candidate.
  Candidate sweeps belong behind ``server.precrack`` (``verify_batch``
  / ``PmkBatcher.prewarm``): PMKs derive once per fused mixed-ESSID
  batch, verdicts still finish through the same oracle call — bit-
  identical results, batch-width fewer PBKDF2 runs per sweep.
- **DW116 mask-block-seam** — the framed-mask dispatch contract
  (``STREAM_FILES`` + ``FEED_DIRS`` + the client crack loop and the
  scheduling layers, ``MASK_SEAM_FILES``/``MASK_SEAM_DIRS``): no
  ``mask_words``/``device_mask_words`` import or call, and no direct
  ``MaskPrep(...)`` construction.  Mask keyspace slices travel ONLY as
  the framed blocks ``gen.mask.mask_blocks`` emits — it derives every
  block's ``(offset, count)`` from the ``mask_keyspace``-bounded total,
  so skip/limit resume stays in hashcat ``-s`` coordinates and a
  hand-rolled enumerator can never silently walk past a shard's
  ``limit`` or host-materialize candidates the device generator exists
  to absorb.  ``models/m22000.py`` (the engine's ``_prepare_block``
  device-generation seam and its scalar probe) and the low-volume
  targeted host generators (``client/targeted.py``) are outside the
  scope by design.

The linter is repo-native, not general-purpose: rules are scoped to the
paths where the hazard matters (see ``HOT_PATH_FILES``/``BENCH_FILES``/
``OPS_DIRS``) so the baseline stays small and every entry is a real,
individually-accepted sync or compile.
"""

import ast
import dataclasses
import os
import re

#: files whose host↔device syncs DW104 polices (repo-relative, posix)
HOT_PATH_FILES = ("dwpa_tpu/parallel/step.py", "dwpa_tpu/models/m22000.py")
#: files whose timed sections DW105 polices
BENCH_FILES = ("bench.py",)
#: directories whose dtype lattice DW103 polices
OPS_DIRS = ("dwpa_tpu/ops",)
#: files whose obs spans DW106 polices for the device-sync rule (the
#: span-instrumented surfaces; the in-trace emission check is global)
SPAN_FILES = ("bench.py", "dwpa_tpu/client/main.py")

#: the package whose transport confinement DW112 polices, and the one
#: file inside it allowed to speak raw HTTP / own the backoff sleeps
CLIENT_DIR = "dwpa_tpu/client/"
CLIENT_TRANSPORT_FILE = "dwpa_tpu/client/protocol.py"

#: the package whose multi-statement write atomicity DW114 polices
SERVER_DIR = "dwpa_tpu/server/"
#: the one server file allowed to run per-candidate oracle calls in a
#: loop (DW115): the pre-crack module's own host fallback — the seam
#: every other server-side candidate sweep is routed through
PRECRACK_FALLBACK_FILES = ("dwpa_tpu/server/precrack.py",)

#: metric-emission methods DW106 bans inside traced functions
OBS_EMIT_METHODS = {"inc", "dec", "observe", "set"}

#: PMK-store method calls DW108(a) bans inside traced regions, and the
#: receiver names that mark the call as store I/O (so ``cfg.lookup``
#: stays clean while ``pmk_store.lookup`` / ``self._store.put`` flag)
PMKSTORE_IO_METHODS = {"lookup", "lookup_digests", "put", "flush", "close"}
_PMKSTORE_RECV = re.compile(r"(?i)(pmk_?store$|^store$|^_store$)")
#: the consumer-thread write-back set: the only files allowed to call a
#: store's ``.put`` (DW108(b)) — the store itself and the engine's
#: post-device-fetch write-back seam
PMKSTORE_WRITEBACK_FILES = ("dwpa_tpu/pmkstore/", "dwpa_tpu/models/m22000.py",
                            "dwpa_tpu/server/precrack.py")

#: directories whose producer-thread discipline DW107(b) polices
FEED_DIRS = ("dwpa_tpu/feed",)
#: dict-cache I/O methods DW111 polices, and the receiver names that
#: mark the call as cache I/O (so ``csv.writer(...)``/``conn.commit()``
#: stay clean while ``dict_cache.reader`` / ``self._dcache.evict`` flag)
DICTCACHE_IO_METHODS = {"reader", "writer", "add_many", "commit",
                        "abort", "chunks", "evict"}
_DICTCACHE_RECV = re.compile(r"(?i)(dict_?cache$|^_?cache$|^_?dcache$)")
#: the only files allowed to perform dict-cache I/O (DW111(b)) — the
#: feed subsystem, whose producer threads own the cache seam
DICTCACHE_FEED_FILES = ("dwpa_tpu/feed/",)
#: jax calls a feed producer thread MAY make (H2D staging only)
FEED_PRODUCER_ALLOWED = {"device_put", "shard_candidates"}
#: blocking-sync methods DW107(a) bans inside traced regions, and the
#: receiver names that mark the call as a queue/lock primitive (so
#: ``cfg.get(...)``/``", ".join(...)``/``os.path.join`` stay clean)
BLOCKING_SYNC_METHODS = {"get", "put", "join", "acquire", "wait"}
_BLOCKING_RECV = re.compile(r"(?i)(queue|lock|sem|cond|cv|event|^q|_q)$")

#: callables that put their function argument under a JAX trace
TRACE_ENTRYPOINTS = {
    "jit", "pjit", "vmap", "pmap", "shard_map", "scan", "fori_loop",
    "while_loop", "cond", "switch", "checkpoint", "remat", "grad",
    "value_and_grad", "custom_jvp", "custom_vjp",
    # repo-specific wrappers (parallel/step.py)
    "_shard",
}

#: dtypes allowed in ops/ — the uint32 crypto lattice plus the small
#: integer types the packers use (int32 only as gather/index dtype)
OPS_DTYPE_LATTICE = {
    "uint8", "uint16", "uint32", "uint64", "int32", "bool_", "bool",
}
_BAD_DTYPES = {
    "float16", "float32", "float64", "bfloat16", "float_",
    "int64", "complex64", "complex128",
}

#: calls that force device completion (or are documented to sync
#: internally, like the engine's crack loop via its hits gate)
SYNC_MARKERS = {
    "block_until_ready", "asarray", "item", "array",
    "crack", "crack_batch", "crack_rules", "crack_mask", "crack_blocks",
    "crack_fused", "crack_streams", "run_blocks",
    # rules device-expansion entries: both drain the collect pipeline
    # (the hits gate) before returning, same as crack_rules
    "crack_rules_blocks", "crack_rules_streams",
}

#: files holding per-device stream executors DW110 polices — a stream
#: owns ONE device, so nothing in it may span devices or barrier
STREAM_FILES = ("dwpa_tpu/parallel/streams.py",)
#: cross-device collectives DW110 bans anywhere in STREAM_FILES
STREAM_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "psum_scatter",
}
#: blocking device→host fetches DW110 bans inside a stream's
#: dispatch/pull loops (the only allowed sync is the engine's own
#: hits-gate inside ``_collect``)
STREAM_BLOCKING_FETCHES = {"device_get", "block_until_ready"}

#: receiver names DW113 treats as rule-valued (so ``rule.apply(w)`` /
#: ``rr.apply(...)`` flag while ``df.apply(...)``/``pool.apply(...)``
#: stay clean); the rules-feed scope is STREAM_FILES + FEED_DIRS
_RULE_RECV = re.compile(r"(?i)(rule|^rr?$)")

#: the framed-mask dispatch scope DW116 polices beyond STREAM_FILES and
#: FEED_DIRS: the client crack loop and the scheduling layers — every
#: surface where a mask shard travels as a work unit rather than as the
#: engine's own device-generation seam
MASK_SEAM_FILES = ("dwpa_tpu/client/main.py",)
MASK_SEAM_DIRS = ("dwpa_tpu/sched", "dwpa_tpu/keyspace")
#: raw enumerators DW116 bans off the mask_blocks seam (import or call)
MASK_ENUM_NAMES = {"mask_words", "device_mask_words"}

#: files whose [W, 16] row-buffer allocations DW109 polices — the
#: fused/mixed batch packers that feed per-lane rows to pmk_kernel
FUSED_PAD_FILES = ("dwpa_tpu/sched/fuse.py", "dwpa_tpu/pmkstore/stage.py")
#: width-producing calls DW109 accepts (the static pad tables)
FUSED_WIDTH_SOURCES = {"fused_width", "miss_width"}
#: table-returning calls whose subscript DW109 also accepts
FUSED_WIDTH_TABLES = {"fused_widths", "miss_widths"}


@dataclasses.dataclass(frozen=True)
class Violation:
    code: str     # DWxxx
    path: str     # repo-relative posix path
    line: int
    detail: str   # human message
    snippet: str  # stripped offending source line (baseline fingerprint)

    def fingerprint(self) -> tuple:
        """Baseline identity: survives line-number drift (code moving
        around a file must not churn the baseline), dies with the code
        itself (editing the offending line forces a baseline decision)."""
        return (self.code, self.path, self.snippet)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.detail}"


def _line(src_lines, node) -> str:
    try:
        return src_lines[node.lineno - 1].strip()
    except IndexError:  # pragma: no cover - malformed lineno
        return ""


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _is_np_attr(node, attr: str) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == attr
            and isinstance(node.value, ast.Name)
            and node.value.id in ("np", "numpy"))


class _TaintScrubber(ast.NodeTransformer):
    """Drop subtrees that are static at trace time (shape/dtype/len of a
    tracer is a Python value), so taint checks don't flag branches on
    them."""

    _STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

    def visit_Attribute(self, node):
        if node.attr in self._STATIC_ATTRS:
            return ast.copy_location(ast.Constant(value=0), node)
        return self.generic_visit(node)

    def visit_Call(self, node):
        if isinstance(node.func, ast.Name) and node.func.id in ("len", "range"):
            return ast.copy_location(ast.Constant(value=0), node)
        return self.generic_visit(node)


def _tainted_names(expr, tainted: set) -> set:
    """Names from ``tainted`` that the expression's value can depend on,
    ignoring trace-static subtrees (shapes, dtypes, len())."""
    try:
        scrubbed = _TaintScrubber().visit(ast.fix_missing_locations(
            ast.parse(ast.unparse(expr), mode="eval")))
    except (SyntaxError, ValueError):  # unparsable fragment: be conservative
        scrubbed = expr
    return _names_in(scrubbed) & tainted


def _is_jaxlike_call(call: ast.Call) -> bool:
    """Strict device-value producer (taint source): a call rooted at the
    jnp/jax/lax namespaces."""
    f = call.func
    root = f
    while isinstance(root, ast.Attribute):
        root = root.value
    return isinstance(root, ast.Name) and root.id in ("jnp", "jax", "lax")


def _is_devicework_call(call: ast.Call) -> bool:
    """Loose device-work launcher (bench timed-section heuristic): jax
    namespaces, engine crack* methods, or kernel-named helpers."""
    if _is_jaxlike_call(call):
        return True
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr.startswith("crack"):
        return True
    if isinstance(f, ast.Name) and ("pallas" in f.id or "pbkdf2" in f.id
                                    or f.id.startswith("crack")):
        return True
    return False


# ---------------------------------------------------------------------------
# traced-function discovery + DW101/DW104-in-trace
# ---------------------------------------------------------------------------


def _static_params(call) -> tuple:
    """(names, nums) declared static on a jit-style call: taint must not
    cover them — branching on a static arg is the supported idiom."""
    names, nums = set(), set()
    if not isinstance(call, ast.Call):
        return names, nums
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = (kw.value.elts if isinstance(kw.value, ast.Tuple)
                    else [kw.value])
            names |= {v.value for v in vals
                      if isinstance(v, ast.Constant)
                      and isinstance(v.value, str)}
        elif kw.arg == "static_argnums":
            vals = (kw.value.elts if isinstance(kw.value, ast.Tuple)
                    else [kw.value])
            nums |= {v.value for v in vals
                     if isinstance(v, ast.Constant)
                     and isinstance(v.value, int)}
    return names, nums


def _traced_functions(tree: ast.Module):
    """Yield (funcdef, how, static_names, static_nums) for every function
    the module demonstrably puts under a JAX trace: decorated with a
    trace entry point, or passed (by name or as an inline lambda) to
    one.  static_* carry the entry's static_argnames/argnums so the
    taint analysis exempts those parameters."""
    by_name = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, node)

    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = (target.attr if isinstance(target, ast.Attribute)
                        else getattr(target, "id", ""))
                if name in TRACE_ENTRYPOINTS and id(node) not in seen:
                    seen.add(id(node))
                    snames, snums = _static_params(
                        dec if isinstance(dec, ast.Call) else None)
                    yield node, f"@{name}", snames, snums
        elif isinstance(node, ast.Call):
            entry = _call_name(node)
            if entry not in TRACE_ENTRYPOINTS:
                continue
            snames, snums = _static_params(node)
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda) and id(arg) not in seen:
                    seen.add(id(arg))
                    yield arg, f"lambda->{entry}", snames, snums
                elif (isinstance(arg, ast.Name) and arg.id in by_name
                      and id(by_name[arg.id]) not in seen):
                    seen.add(id(by_name[arg.id]))
                    yield by_name[arg.id], f"{arg.id}->{entry}", snames, snums


def _is_static_test(test) -> bool:
    """``x is None`` / ``x is not None`` is host-level control flow even
    when x may hold a tracer (a tracer is never None), so it is decided
    at trace time — the accumulate-or-init idiom, not a tracer branch."""
    return (isinstance(test, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops)
            and any(isinstance(c, ast.Constant) and c.value is None
                    for c in [test.left] + test.comparators))


def _check_traced_function(fn, how, static_names, static_nums, path,
                           src_lines, out):
    """DW101 inside one traced function: taint params + jnp/lax results,
    flag Python control flow whose condition depends on the taint."""
    args = fn.args
    positional = args.posonlyargs + args.args
    static = set(static_names)
    static |= {positional[i].arg for i in static_nums
               if i < len(positional)}
    tainted = {a.arg for a in (
        positional + args.kwonlyargs
        + ([args.vararg] if args.vararg else [])
        + ([args.kwarg] if args.kwarg else [])
    ) if a.arg != "self" and a.arg not in static}

    body = fn.body if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
        else [ast.Expr(value=fn.body)]

    for node in [n for stmt in body for n in ast.walk(stmt)]:
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = node.value
            if value is None:
                continue
            dep = bool(_tainted_names(value, tainted)) or any(
                _is_jaxlike_call(c)
                for c in ast.walk(value) if isinstance(c, ast.Call))
            if dep:
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    # a subscript store taints the CONTAINER, never the
                    # index expression (byte_cols[p] = ... must not
                    # taint p)
                    base = t.value if isinstance(t, ast.Subscript) else t
                    for n in ast.walk(base):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
        elif isinstance(node, (ast.If, ast.While)):
            if _is_static_test(node.test):
                continue
            hits = _tainted_names(node.test, tainted)
            if hits:
                out.append(Violation(
                    "DW101", path, node.lineno,
                    f"Python {'if' if isinstance(node, ast.If) else 'while'} "
                    f"over traced value(s) {sorted(hits)} inside "
                    f"traced function ({how}) — branch on a tracer",
                    _line(src_lines, node)))
        elif isinstance(node, ast.IfExp):
            if _is_static_test(node.test):
                continue
            hits = _tainted_names(node.test, tainted)
            if hits:
                out.append(Violation(
                    "DW101", path, node.lineno,
                    f"ternary over traced value(s) {sorted(hits)} inside "
                    f"traced function ({how})", _line(src_lines, node)))
        elif isinstance(node, ast.Assert):
            hits = _tainted_names(node.test, tainted)
            if hits:
                out.append(Violation(
                    "DW101", path, node.lineno,
                    f"assert over traced value(s) {sorted(hits)} inside "
                    f"traced function ({how})", _line(src_lines, node)))
        elif isinstance(node, ast.For):
            # iterating the tracer ITSELF (bare name/attribute) unrolls
            # per element; zip/enumerate over python containers of
            # tracers is static and fine.
            it = node.iter
            if isinstance(it, (ast.Name, ast.Attribute)):
                hits = _names_in(it) & tainted
                if hits:
                    out.append(Violation(
                        "DW101", path, node.lineno,
                        f"for loop iterates traced value {sorted(hits)} "
                        f"inside traced function ({how})",
                        _line(src_lines, node)))
        elif isinstance(node, ast.Call):
            name = _call_name(node)
            if name in ("int", "float", "bool") and node.args:
                hits = _tainted_names(node.args[0], tainted)
                if hits:
                    out.append(Violation(
                        "DW104", path, node.lineno,
                        f"{name}() concretizes traced value(s) "
                        f"{sorted(hits)} inside traced function ({how}) — "
                        "host sync / ConcretizationTypeError",
                        _line(src_lines, node)))
            elif (name in OBS_EMIT_METHODS
                    and isinstance(node.func, ast.Attribute)
                    and not _is_at_update(node.func)):
                out.append(Violation(
                    "DW106", path, node.lineno,
                    f"metric/span emission .{name}() inside traced "
                    f"function ({how}) — telemetry is host-side only; "
                    "record after the device call returns",
                    _line(src_lines, node)))
            elif (name in BLOCKING_SYNC_METHODS
                    and isinstance(node.func, ast.Attribute)
                    and _BLOCKING_RECV.search(_recv_name(node.func))):
                out.append(Violation(
                    "DW107", path, node.lineno,
                    f"blocking .{name}() on "
                    f"'{_recv_name(node.func)}' inside traced function "
                    f"({how}) — queue/lock waits are host-side; a trace "
                    "either fails on it or bakes a one-time value in "
                    "while serializing the pipeline",
                    _line(src_lines, node)))
            elif (name == "mmap"
                    or (name in PMKSTORE_IO_METHODS
                        and isinstance(node.func, ast.Attribute)
                        and _PMKSTORE_RECV.search(_recv_name(node.func)))):
                out.append(Violation(
                    "DW108", path, node.lineno,
                    f"pmkstore I/O {name}() inside traced function "
                    f"({how}) — store reads/writes are host mmap/dict "
                    "work; a trace either fails on them or bakes one "
                    "lookup's result into the compiled program",
                    _line(src_lines, node)))
            elif (name in DICTCACHE_IO_METHODS
                    and isinstance(node.func, ast.Attribute)
                    and _DICTCACHE_RECV.search(_recv_name(node.func))):
                out.append(Violation(
                    "DW111", path, node.lineno,
                    f"dictcache I/O {name}() inside traced function "
                    f"({how}) — packed-dict cache reads/writes are "
                    "producer-thread host work (mmap/file I/O); a trace "
                    "either fails on them or bakes one chunk's bytes "
                    "into the compiled program",
                    _line(src_lines, node)))


def _is_at_update(f: ast.Attribute) -> bool:
    """jnp's functional update ``x.at[i].set(v)`` (or any subscripted
    base) is array code, not telemetry — exempt from the DW106
    emission check."""
    return any(isinstance(n, ast.Subscript) for n in ast.walk(f.value))


def _recv_name(f: ast.Attribute) -> str:
    """Last identifier of a method call's receiver (``self._queue.get``
    -> ``_queue``; ``q.get`` -> ``q``; constants/calls -> "")."""
    base = f.value
    if isinstance(base, ast.Attribute):
        return base.attr
    if isinstance(base, ast.Name):
        return base.id
    return ""


# ---------------------------------------------------------------------------
# DW107(b): feed producer thread discipline
# ---------------------------------------------------------------------------


def _check_feed_producers(tree, path, src_lines, out):
    """In ``dwpa_tpu/feed/``: a producer function (name contains
    "produce" — the subsystem's documented naming convention for code
    that runs on producer threads) may touch NO jax/jnp/lax call beyond
    the allowed H2D staging pair."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if "produce" not in fn.name:
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call) and _is_jaxlike_call(node)):
                continue
            name = _call_name(node)
            if name in FEED_PRODUCER_ALLOWED:
                continue
            out.append(Violation(
                "DW107", path, node.lineno,
                f"feed producer {fn.name}() calls jax device API "
                f"'{name}' — producer threads are pure host stages; "
                "only device_put/shard_candidates (H2D staging) are "
                "allowed off the consumer thread",
                _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW108(b): PMK-store write-back outside the consumer thread's allowed set
# ---------------------------------------------------------------------------


def _check_pmkstore_writeback(tree, path, src_lines, out):
    """Outside ``PMKSTORE_WRITEBACK_FILES``: any ``<store>.put(...)`` is
    a write-back from the wrong seam — producer threads and client code
    must only LOOK UP; appends belong to the engine's consumer-thread
    post-fetch write-back (or the store's own internals)."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and _call_name(node) == "put"
                and isinstance(node.func, ast.Attribute)
                and _PMKSTORE_RECV.search(_recv_name(node.func))):
            out.append(Violation(
                "DW108", path, node.lineno,
                f"pmkstore write-back .put() on "
                f"'{_recv_name(node.func)}' outside the consumer-thread "
                f"allowed set ({', '.join(PMKSTORE_WRITEBACK_FILES)}) — "
                "newly derived PMKs are written back only after the "
                "engine's device fetch", _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW111(b): dict-cache I/O outside the feed subsystem
# ---------------------------------------------------------------------------


def _check_dictcache_io(tree, path, src_lines, out):
    """Outside ``DICTCACHE_FEED_FILES``: any dict-cache I/O call is on
    the wrong seam — the packed-dict cache is read and written by feed
    producer threads (``DictFeedSource``); client/engine code holds a
    ``DictCache`` handle only to pass it INTO the feed."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and _call_name(node) in DICTCACHE_IO_METHODS
                and isinstance(node.func, ast.Attribute)
                and _DICTCACHE_RECV.search(_recv_name(node.func))):
            out.append(Violation(
                "DW111", path, node.lineno,
                f"dictcache I/O .{_call_name(node)}() on "
                f"'{_recv_name(node.func)}' outside the feed subsystem "
                f"({', '.join(DICTCACHE_FEED_FILES)}) — dict-cache "
                "reads/writes belong to feed producer threads",
                _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW102 uncached jit
# ---------------------------------------------------------------------------


def _is_jit_ref(node) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr in ("jit", "pjit")
    if isinstance(node, ast.Name):
        return node.id in ("jit", "pjit")
    return False


def _check_uncached_jit(tree, path, src_lines, out):
    class V(ast.NodeVisitor):
        def __init__(self):
            self.loop_depth = 0
            self.cached_jits = set()  # jit Call nodes stored to a cache

        def _mark_cached(self, value):
            for n in ast.walk(value):
                if isinstance(n, ast.Call) and _is_jit_ref(n.func):
                    self.cached_jits.add(id(n))

        def visit_Assign(self, node):
            if any(isinstance(t, (ast.Subscript, ast.Attribute))
                   for t in node.targets):
                self._mark_cached(node.value)
            self.generic_visit(node)

        def visit_For(self, node):
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_While = visit_For

        def visit_Call(self, node):
            # jax.jit(f)(x): the jit object dies with the statement, so
            # every execution is a fresh trace + compile
            if (isinstance(node.func, ast.Call)
                    and _is_jit_ref(node.func.func)):
                out.append(Violation(
                    "DW102", path, node.lineno,
                    "jit result invoked immediately — fresh compile cache "
                    "per call (store the jitted fn once and reuse it)",
                    _line(src_lines, node)))
            elif (_is_jit_ref(node.func) and self.loop_depth > 0
                    and id(node) not in self.cached_jits):
                out.append(Violation(
                    "DW102", path, node.lineno,
                    "jax.jit(...) created inside a loop without a cache "
                    "(subscript/attribute store) — recompiles every "
                    "iteration", _line(src_lines, node)))
            self.generic_visit(node)

    V().visit(tree)


# ---------------------------------------------------------------------------
# DW103 ops/ dtype lattice
# ---------------------------------------------------------------------------


def _check_ops_dtypes(tree, path, src_lines, out):
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr in _BAD_DTYPES
                and isinstance(node.value, ast.Name)
                and node.value.id in ("np", "numpy", "jnp")):
            out.append(Violation(
                "DW103", path, node.lineno,
                f"dtype {node.value.id}.{node.attr} is off the uint32 "
                f"crypto lattice (allowed: {sorted(OPS_DTYPE_LATTICE)})",
                _line(src_lines, node)))
        elif (isinstance(node, ast.Call) and _call_name(node) == "astype"
                and node.args and isinstance(node.args[0], ast.Constant)
                and str(node.args[0].value) in _BAD_DTYPES):
            out.append(Violation(
                "DW103", path, node.lineno,
                f"astype({node.args[0].value!r}) is off the uint32 crypto "
                "lattice", _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW104 host syncs in hot-path modules
# ---------------------------------------------------------------------------


def _check_hot_path_syncs(tree, path, src_lines, out):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "item" and not node.args:
            out.append(Violation(
                "DW104", path, node.lineno,
                ".item() is a device->host sync on the hot path",
                _line(src_lines, node)))
        elif _is_np_attr(f, "asarray") or _is_np_attr(f, "array"):
            # dtype= marks the host-packing idiom (pure host data);
            # a dtype-less np.asarray of a device value is THE implicit
            # transfer+sync this rule exists for.
            if not any(kw.arg == "dtype" for kw in node.keywords):
                out.append(Violation(
                    "DW104", path, node.lineno,
                    f"np.{f.attr}(...) without dtype= in a hot-path module "
                    "— implicit device->host sync if fed a device value",
                    _line(src_lines, node)))
        elif (isinstance(f, ast.Attribute) and f.attr == "device_get"):
            out.append(Violation(
                "DW104", path, node.lineno,
                "jax.device_get is a device->host sync on the hot path",
                _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW105 bench timed sections
# ---------------------------------------------------------------------------


def _is_clock_call(node) -> bool:
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("perf_counter", "monotonic", "time")
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _check_timed_sections(tree, path, src_lines, out):
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stmts = fn.body
        for i, stmt in enumerate(stmts):
            if not (isinstance(stmt, ast.Assign) and _is_clock_call(stmt.value)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                continue
            t_name = stmt.targets[0].id
            # find the stop: first later statement computing clock() - t_name
            stop = None
            for j in range(i + 1, len(stmts)):
                for n in ast.walk(stmts[j]):
                    if (isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub)
                            and _is_clock_call(n.left)
                            and isinstance(n.right, ast.Name)
                            and n.right.id == t_name):
                        stop = j
                        break
                if stop is not None:
                    break
            if stop is None:
                continue
            region = stmts[i + 1:stop]
            calls = [n for s in region for n in ast.walk(s)
                     if isinstance(n, ast.Call)]
            launches = any(_is_devicework_call(c) for c in calls)
            synced = any(_call_name(c) in SYNC_MARKERS for c in calls)
            if launches and not synced:
                out.append(Violation(
                    "DW105", path, stmt.lineno,
                    f"timed section '{t_name}' in {fn.name}() launches "
                    "device work but never forces completion "
                    "(block_until_ready / np.asarray / engine crack*) "
                    "before the clock stops", _line(src_lines, stmt)))


# ---------------------------------------------------------------------------
# DW106 span device-sync discipline (the obs-layer DW105)
# ---------------------------------------------------------------------------


def _is_span_open(call: ast.Call) -> bool:
    """``<tracer>.span(name...)`` / ``<tracer>.start(name...)`` — the obs
    span API.  The name argument requirement keeps zero-arg ``.start()``
    (threads, servers) out of scope."""
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr in ("span", "start")
            and bool(call.args or call.keywords))


def _has_sync_kwarg(call: ast.Call) -> bool:
    """``span(..., sync=...)`` / ``stop(sync=...)``: the API's built-in
    fetch-before-clock-stop — counts as synced."""
    return any(kw.arg == "sync" and not (isinstance(kw.value, ast.Constant)
                                         and kw.value.value is None)
               for kw in call.keywords)


def _region_sync_violation(region, opener, label, fn_name, path,
                           src_lines, out):
    calls = [n for s in region for n in ast.walk(s)
             if isinstance(n, ast.Call)]
    launches = any(_is_devicework_call(c) for c in calls)
    synced = any(_call_name(c) in SYNC_MARKERS for c in calls)
    if launches and not synced:
        out.append(Violation(
            "DW106", path, opener.lineno,
            f"span '{label}' in {fn_name}() launches device work but "
            "never forces completion (engine crack* / np.asarray / "
            "block_until_ready / sync=) before the clock stops",
            _line(src_lines, opener)))


def _span_label(call: ast.Call) -> str:
    if call.args and isinstance(call.args[0], ast.Constant):
        return str(call.args[0].value)
    return "<dynamic>"


def _check_span_sync(tree, path, src_lines, out):
    seen_withs = set()  # a With in a nested def is walked by both defs
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # with <tracer>.span(...) [as sp]: — the region is the body
        for node in ast.walk(fn):
            if not isinstance(node, ast.With) or id(node) in seen_withs:
                continue
            seen_withs.add(id(node))
            for item in node.items:
                ce = item.context_expr
                if (isinstance(ce, ast.Call) and _is_span_open(ce)
                        and ce.func.attr == "span"
                        and not _has_sync_kwarg(ce)):
                    _region_sync_violation(
                        node.body, node, _span_label(ce), fn.name,
                        path, src_lines, out)
        # sp = <tracer>.start(...) ... sp.stop() — statement-scoped,
        # like DW105's clock pairs
        stmts = fn.body
        for i, stmt in enumerate(stmts):
            if not (isinstance(stmt, ast.Assign)
                    and isinstance(stmt.value, ast.Call)
                    and _is_span_open(stmt.value)
                    and stmt.value.func.attr == "start"
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                continue
            sp_name = stmt.targets[0].id
            stop = stop_call = None
            for j in range(i + 1, len(stmts)):
                for n in ast.walk(stmts[j]):
                    if (isinstance(n, ast.Call)
                            and isinstance(n.func, ast.Attribute)
                            and n.func.attr == "stop"
                            and isinstance(n.func.value, ast.Name)
                            and n.func.value.id == sp_name):
                        stop, stop_call = j, n
                        break
                if stop is not None:
                    break
            if stop is None or _has_sync_kwarg(stop_call):
                continue
            _region_sync_violation(
                stmts[i + 1:stop], stmt, _span_label(stmt.value), fn.name,
                path, src_lines, out)


def _check_fused_pad_widths(tree, path, src_lines, out):
    """DW109: ``[W, 16]`` row buffers in the fused-batch packers must
    take ``W`` from the static fused-width pad table.

    A width expression is accepted when it provably resolves to the
    tables: a constant, a ``fused_width``/``miss_width`` call, a
    subscript of ``fused_widths``/``miss_widths``, a ``max``/``min``
    over accepted values, a conditional whose branches are accepted, or
    a local name every assignment of which is accepted.  Anything else
    (a parameter, ``len(...)``, arithmetic on a count) is a
    data-dependent pad width — each distinct value retraces the fused
    PBKDF2 step, the compile-per-unit-combination failure the tables
    exist to prevent."""
    seen = set()  # nested defs are walked by their enclosing def too
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        assigns = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                assigns.setdefault(node.targets[0].id, []).append(node.value)

        def accepted(expr, trail=()):
            if isinstance(expr, ast.Constant):
                return True
            if isinstance(expr, ast.Call):
                name = _call_name(expr)
                if name in FUSED_WIDTH_SOURCES:
                    return True
                if name in ("max", "min"):
                    return all(accepted(a, trail) for a in expr.args)
                return False
            if isinstance(expr, ast.Subscript):
                return (isinstance(expr.value, ast.Call)
                        and _call_name(expr.value) in FUSED_WIDTH_TABLES)
            if isinstance(expr, ast.IfExp):
                return (accepted(expr.body, trail)
                        and accepted(expr.orelse, trail))
            if isinstance(expr, ast.Name):
                if expr.id in trail:  # assignment cycle: refuse
                    return False
                vals = assigns.get(expr.id)
                return bool(vals) and all(
                    accepted(v, trail + (expr.id,)) for v in vals)
            return False

        for node in ast.walk(fn):
            if (id(node) in seen
                    or not isinstance(node, ast.Call)
                    or not _is_np_attr(node.func, "zeros")
                    and not _is_np_attr(node.func, "empty")):
                continue
            seen.add(id(node))
            if not (node.args and isinstance(node.args[0], ast.Tuple)
                    and len(node.args[0].elts) == 2):
                continue
            w, cols = node.args[0].elts
            if not (isinstance(cols, ast.Constant) and cols.value == 16):
                continue
            if not accepted(w):
                out.append(Violation(
                    "DW109", path, node.lineno,
                    f"[W, 16] row buffer in {fn.name}() has a "
                    "data-dependent width — per-lane rows entering "
                    "pmk_kernel must be padded to the static fused-width "
                    "pad table (fused_width/miss_width)",
                    _line(src_lines, node)))


def _check_stream_discipline(tree, path, src_lines, out):
    """DW110: per-device stream isolation (``STREAM_FILES``).

    (a) no cross-device collective anywhere in the file — a stream owns
    one device, and a ``psum``/``all_gather`` would barrier it against
    its siblings (or deadlock when streams run different block counts);
    (b) no blocking ``jax.device_get``/``block_until_ready`` inside a
    ``for``/``while`` loop — the dispatch/pull loops stay async, the
    only sync being the engine's hits-gate inside ``_collect``; (c)
    every ``device_put`` carries an explicit device/sharding (second
    positional or ``device=``/``sharding=`` kwarg) — a bare put lands
    every stream's arrays on the default device."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name in STREAM_COLLECTIVES:
            out.append(Violation(
                "DW110", path, node.lineno,
                f"cross-device collective {name}() in a device-stream "
                "module — a stream owns one device; a collective "
                "barriers it against its siblings (lockstep coupling, "
                "or deadlock on uneven block counts)",
                _line(src_lines, node)))
        elif name == "device_put":
            explicit = len(node.args) >= 2 or any(
                kw.arg in ("device", "sharding") for kw in node.keywords)
            if not explicit:
                out.append(Violation(
                    "DW110", path, node.lineno,
                    "device_put without an explicit device/sharding — "
                    "a bare put lands on the default device, stacking "
                    "every stream's arrays onto device 0",
                    _line(src_lines, node)))
    seen = set()  # nested loops are walked by their enclosing loop too
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        for node in ast.walk(loop):
            if (id(node) in seen or not isinstance(node, ast.Call)
                    or _call_name(node) not in STREAM_BLOCKING_FETCHES):
                continue
            seen.add(id(node))
            out.append(Violation(
                "DW110", path, node.lineno,
                f"blocking {_call_name(node)}() inside a stream loop — "
                "the per-stream dispatch loop must stay async; the "
                "only allowed sync is the engine's hits-gate inside "
                "_collect",
                _line(src_lines, node)))


def _check_client_transport(tree, path, src_lines, out):
    """DW112: transport confinement in the client package (every file
    under ``CLIENT_DIR`` except ``CLIENT_TRANSPORT_FILE``).

    (a) any ``urllib`` import — raw HTTP outside ``ServerAPI`` bypasses
    error classification, retry backoff, the circuit breaker and the
    outbox-backed submission path; (b) a bare ``time.sleep(...)`` call
    or ``from time import sleep`` — naps must be the injected
    ``api.sleep`` so chaos runs drive a virtual clock and the degraded
    crack loop is never parked on a hidden blocking sleep."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "urllib" for a in node.names):
                out.append(Violation(
                    "DW112", path, node.lineno,
                    "urllib import outside client/protocol.py — raw HTTP "
                    "here bypasses the retry/classification/circuit-"
                    "breaker stack; route the call through ServerAPI",
                    _line(src_lines, node)))
        elif isinstance(node, ast.ImportFrom):
            root_mod = (node.module or "").split(".")[0]
            if root_mod == "urllib":
                out.append(Violation(
                    "DW112", path, node.lineno,
                    "urllib import outside client/protocol.py — raw HTTP "
                    "here bypasses the retry/classification/circuit-"
                    "breaker stack; route the call through ServerAPI",
                    _line(src_lines, node)))
            elif (root_mod == "time"
                  and any(a.name == "sleep" for a in node.names)):
                out.append(Violation(
                    "DW112", path, node.lineno,
                    "time.sleep imported outside client/protocol.py — "
                    "naps must go through the injected api.sleep so the "
                    "chaos harness can drive them off a virtual clock",
                    _line(src_lines, node)))
        elif isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "sleep"
                    and _recv_name(f) == "time"):
                out.append(Violation(
                    "DW112", path, node.lineno,
                    "bare time.sleep() outside client/protocol.py — the "
                    "crack loop must nap through the injected api.sleep "
                    "(virtual-clock testable, and degraded mode is never "
                    "blocked behind a hidden sleep)",
                    _line(src_lines, node)))


def _check_rules_device_expansion(tree, path, src_lines, out):
    """DW113: no host rule interpretation on the mesh-aggregate feed
    path (``STREAM_FILES`` + ``FEED_DIRS``).

    (a) any ``apply_rules(...)`` call or ``apply_rules`` import — the
    host expansion loop re-serializes exactly the work the device
    ``build_rules_step`` path exists to absorb; (b) ``.apply(...)`` on
    a rule-valued receiver (``rule``/``rr``/``*_rule`` names) — a
    single-rule interpreter call is the same hazard one word at a time.
    Purge/overflow fallbacks belong to the engine's ``_rules_flush``
    host tail (``models/m22000.py``), not to streams or feed
    producers."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if any(a.name == "apply_rules" for a in node.names):
                out.append(Violation(
                    "DW113", path, node.lineno,
                    "apply_rules imported on the mesh-aggregate feed "
                    "path — streams and feed producers ship compact "
                    "base-word blocks; rule expansion runs on device "
                    "via the engine's _rules_flush seam",
                    _line(src_lines, node)))
        elif isinstance(node, ast.Call):
            name = _call_name(node)
            if name == "apply_rules":
                out.append(Violation(
                    "DW113", path, node.lineno,
                    "host apply_rules() on the mesh-aggregate feed path "
                    "— device-eligible rules expand on device "
                    "(build_rules_step); host interpretation here "
                    "re-serializes the expansion and re-inflates H2D "
                    "bytes by the rule count",
                    _line(src_lines, node)))
            elif (name == "apply" and isinstance(node.func, ast.Attribute)
                  and _RULE_RECV.search(_recv_name(node.func))):
                out.append(Violation(
                    "DW113", path, node.lineno,
                    f"rule interpreter .apply() on "
                    f"'{_recv_name(node.func)}' in stream/feed-producer "
                    "code — per-word host mangling belongs to the "
                    "engine's purge/overflow tail (models/m22000.py), "
                    "never to the feed path",
                    _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW114: server db write atomicity
# ---------------------------------------------------------------------------


def _is_db_tx_with(node: ast.With) -> bool:
    """True for ``with <db>.tx():`` (receiver named ``db`` — covers
    ``db``, ``self.db``, ``core.db``)."""
    for item in node.items:
        ctx = item.context_expr
        if (isinstance(ctx, ast.Call) and isinstance(ctx.func, ast.Attribute)
                and ctx.func.attr == "tx"
                and _recv_name(ctx.func) == "db"):
            return True
    return False


def _check_server_db_atomicity(tree, path, src_lines, out):
    """DW114: >=2 lexical ``db.x(...)`` write sites in one function,
    outside any ``with db.tx():`` block.

    Counts call SITES, not executions: one ``db.x`` inside a loop is a
    deliberate per-row-autocommit pattern (safe to tear between rows —
    each row is self-contained); two sites mean two statements whose
    combined effect the caller almost certainly assumed atomic.  Nested
    function bodies are analyzed separately so an inner helper's write
    never inflates its parent's count."""

    def visit(node, in_tx, sites):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # nested scope: counted on its own visit
        if isinstance(node, ast.With) and _is_db_tx_with(node):
            in_tx = True
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "x"
                and _recv_name(node.func) == "db" and not in_tx):
            sites.append(node)
        for child in ast.iter_child_nodes(node):
            visit(child, in_tx, sites)

    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        sites = []
        for stmt in node.body:
            visit(stmt, False, sites)
        if len(sites) >= 2:
            first = sites[0]
            out.append(Violation(
                "DW114", path, first.lineno,
                f"{len(sites)} db.x() write sites in {node.name}() outside "
                "Database.tx() — a crash between them tears the ledger; "
                "wrap the sequence in 'with db.tx():' (or self.db.tx())",
                _line(src_lines, first)))


# ---------------------------------------------------------------------------
# DW115: server-side scalar candidate verification
# ---------------------------------------------------------------------------


def _check_precrack_scalar_verify(tree, path, src_lines, out):
    """DW115: ``check_key_m22000(h, [one_key], ...)`` — second argument
    a single-element list literal — lexically inside a ``for``/``while``
    loop, in server code outside the pre-crack fallback seam.

    The single-element-list shape is the scalar tell: a batched call
    passes the whole candidate list (a name or comprehension) and lets
    the oracle scan it, while ``[k]`` in a loop means one full PBKDF2
    derivation per iteration on the request/cron thread.  Matching
    call nodes are deduplicated so nested loops flag each site once."""
    flagged = set()
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        for node in ast.walk(loop):
            if (isinstance(node, ast.Call)
                    and _call_name(node) == "check_key_m22000"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.List)
                    and len(node.args[1].elts) == 1
                    and id(node) not in flagged):
                flagged.add(id(node))
                out.append(Violation(
                    "DW115", path, node.lineno,
                    "per-candidate check_key_m22000(h, [key]) inside a "
                    "loop — one full PBKDF2 per iteration on the server "
                    "thread; route the sweep through server.precrack "
                    "(verify_batch / PmkBatcher.prewarm), which derives "
                    "PMKs once per fused mixed-ESSID batch and finishes "
                    "verdicts through the same oracle",
                    _line(src_lines, node)))


# ---------------------------------------------------------------------------
# DW116: framed-mask dispatch seam
# ---------------------------------------------------------------------------


def _check_mask_block_seam(tree, path, src_lines, out):
    """DW116: in the mask-dispatch scope, keyspace slices travel only as
    the framed blocks ``gen.mask.mask_blocks`` emits.

    (a) ``mask_words``/``device_mask_words`` import or call — a raw
    enumerator on the dispatch path either host-materializes candidates
    the device generator exists to absorb or re-derives block framing by
    hand; (b) direct ``MaskPrep(...)`` construction (or its import) — a
    hand-built prep carries whatever ``start`` the caller typed, while
    ``mask_blocks`` derives every ``(offset, count)`` from the
    ``mask_keyspace``-bounded total, keeping skip/limit resume exact in
    hashcat ``-s`` coordinates."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name in MASK_ENUM_NAMES or a.name == "MaskPrep":
                    out.append(Violation(
                        "DW116", path, node.lineno,
                        f"{a.name} imported on the mask-dispatch path — "
                        "mask shards travel only as mask_blocks' framed "
                        "MaskPrep blocks (mask_keyspace-derived framing, "
                        "hashcat -s/-l resume coordinates)",
                        _line(src_lines, node)))
        elif isinstance(node, ast.Call):
            name = _call_name(node)
            if name in MASK_ENUM_NAMES:
                out.append(Violation(
                    "DW116", path, node.lineno,
                    f"raw mask enumerator {name}() on the mask-dispatch "
                    "path — frame the slice through gen.mask.mask_blocks "
                    "and let the engine's _prepare_block seam generate "
                    "on device", _line(src_lines, node)))
            elif name == "MaskPrep":
                out.append(Violation(
                    "DW116", path, node.lineno,
                    "direct MaskPrep(...) construction outside "
                    "gen/mask.py — a hand-built prep bypasses "
                    "mask_blocks' keyspace-bounded (offset, count) "
                    "framing; resume offsets drift off hashcat -s "
                    "coordinates", _line(src_lines, node)))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_source(src: str, path: str) -> list:
    """Lint one file's source; ``path`` is the repo-relative posix path
    (rule scoping keys off it).  Returns a list of Violations."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Violation("DW100", path, e.lineno or 0,
                          f"syntax error: {e.msg}", "")]
    src_lines = src.splitlines()
    out = []
    for fn, how, snames, snums in _traced_functions(tree):
        _check_traced_function(fn, how, snames, snums, path, src_lines, out)
    _check_uncached_jit(tree, path, src_lines, out)
    if path.startswith(tuple(d + "/" for d in OPS_DIRS)):
        _check_ops_dtypes(tree, path, src_lines, out)
    if path in HOT_PATH_FILES:
        _check_hot_path_syncs(tree, path, src_lines, out)
    if path in BENCH_FILES:
        _check_timed_sections(tree, path, src_lines, out)
    if path in SPAN_FILES:
        _check_span_sync(tree, path, src_lines, out)
    if path.startswith(tuple(d + "/" for d in FEED_DIRS)):
        _check_feed_producers(tree, path, src_lines, out)
    if not path.startswith(PMKSTORE_WRITEBACK_FILES):
        _check_pmkstore_writeback(tree, path, src_lines, out)
    if not path.startswith(DICTCACHE_FEED_FILES):
        _check_dictcache_io(tree, path, src_lines, out)
    if path in FUSED_PAD_FILES:
        _check_fused_pad_widths(tree, path, src_lines, out)
    if path in STREAM_FILES:
        _check_stream_discipline(tree, path, src_lines, out)
    if (path in STREAM_FILES
            or path.startswith(tuple(d + "/" for d in FEED_DIRS))):
        _check_rules_device_expansion(tree, path, src_lines, out)
    if (path in STREAM_FILES or path in MASK_SEAM_FILES
            or path.startswith(tuple(
                d + "/" for d in FEED_DIRS + MASK_SEAM_DIRS))):
        _check_mask_block_seam(tree, path, src_lines, out)
    if path.startswith(CLIENT_DIR) and path != CLIENT_TRANSPORT_FILE:
        _check_client_transport(tree, path, src_lines, out)
    if path.startswith(SERVER_DIR):
        _check_server_db_atomicity(tree, path, src_lines, out)
        if path not in PRECRACK_FALLBACK_FILES:
            _check_precrack_scalar_verify(tree, path, src_lines, out)
    return out


def lint_file(full_path: str, root: str) -> list:
    rel = os.path.relpath(full_path, root).replace(os.sep, "/")
    with open(full_path, encoding="utf-8") as f:
        return lint_source(f.read(), rel)


def lint_tree(root: str) -> list:
    """Lint every tracked .py file under ``root`` (skipping caches,
    hidden dirs and the test tree — tests intentionally seed
    violations)."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames
            if not d.startswith(".") and d not in (
                "__pycache__", "tests", "build", "dist"))
        for name in sorted(filenames):
            if name.endswith(".py"):
                out.append(lint_file(os.path.join(dirpath, name), root))
    return [v for vs in out for v in vs]
