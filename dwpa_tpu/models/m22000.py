"""m22000 (WPA PMKID / EAPOL 4-way) device cracking engine.

The flagship model of the framework: candidate PSKs -> PBKDF2-HMAC-SHA1
-> PMK -> PMKID-HMAC or PRF+MIC verification with nonce-error-correction,
entirely on device as batched uint32-lane JAX ops.

Reference semantics being matched (never copied — see the pure-Python
oracle at dwpa_tpu/oracle/m22000.py for the executable spec):

- server verifier ``check_key_m22000`` (web/common.php:157-307);
- hashcat client invocation ``--nonce-error-corrections=8``
  (help_crack/help_crack.py:773) — the device searches the same +/-NC
  window the GPU cracker does, while wide-NC re-checks stay host-side;
- message_pair gating bits (web/common.php:114-155, and the client's
  BE/LE handling at help_crack/help_crack.py:378-400): bit4 ap-less =>
  exact nonce only; bit5/bit6 restrict the NC search to LE/BE.

TPU-first design:

- The PBKDF2 kernel (ops/pbkdf2.py) takes the ESSID salt blocks as *data*,
  so one XLA compilation serves every ESSID at a given batch size.
- Verification kernels take per-net constants (PRF message variants, padded
  EAPOL blocks, target words) as arrays and ``lax.map`` over the
  NC-variant axis (one variant's hash state live at a time: a vmap over
  all variants needs several GB of HBM per net at B=131072), so
  compilations are shared across nets with the same (keyver,
  n_variants, n_eapol_blocks) signature.  On TPU the crack steps run
  the keyver-1/2 check as a Pallas kernel instead (ops/eapol_pallas.py);
  ``eapol_match`` is its XLA form, for every other platform.
- All byte wrangling happens host-side in numpy; the device only ever sees
  fixed-shape uint32 arrays.
"""

import contextlib
import functools
import struct
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import hmac as hm
from ..ops.aes import aes128_cmac_rolled
from ..ops.common import bswap32, u32
from ..ops.md5 import md5_compress_rolled
from ..ops.sha1 import sha1_compress_rolled
from ..ops.sha256 import sha256_compress_rolled
from ..ops.pbkdf2 import pbkdf2_sha1_pmk
from ..ops.pbkdf2_pallas import pbkdf2_sha1_pmk_pallas
from ..oracle import m22000 as oracle
from ..utils import bytesops as bo
from ..utils.device import on_tpu
from . import hashline as hl

# Minimum/maximum WPA passphrase length (IEEE 802.11i; enforced by the
# reference dict guidance at INSTALL.md:83 and by hashcat itself).
MIN_PSK_LEN = 8
MAX_PSK_LEN = 63

DEFAULT_NC = 8  # client-side hashcat window (help_crack.py:773)


# ---------------------------------------------------------------------------
# Host-side per-net preparation
# ---------------------------------------------------------------------------


def essid_salt_blocks(essid: bytes):
    """The two PBKDF2 single-block salt messages ``essid || INT32_BE(i)``.

    ESSIDs are <= 32 bytes so ``essid + 4`` always fits one padded SHA-1
    block (after the 64-byte HMAC key block).  Returned as uint32[16]
    arrays — *data*, not trace constants, so the PMK kernel compiles once.
    """
    out = []
    for i in (1, 2):
        tail = essid + struct.pack(">I", i)
        blk = bo.padded_blocks(tail, 64 + len(tail))[0]
        out.append(np.asarray(blk, dtype=np.uint32))
    return out[0], out[1]


def essid_salt_lanes(essids):
    """Stacked per-lane salt tables for a mixed-ESSID batch.

    Row ``b`` of each returned uint32[B, 16] array is
    ``essid_salt_blocks(essids[b])`` — the rank-2 salt mode of
    ``pmk_kernel`` (one lane, one ESSID).  Repeated ESSIDs share one
    derivation, so a sibling-heavy server pre-crack wave pays the salt
    padding once per distinct network name.
    """
    cache = {}
    lanes1, lanes2 = [], []
    for essid in essids:
        pair = cache.get(essid)
        if pair is None:
            pair = cache[essid] = essid_salt_blocks(essid)
        lanes1.append(pair[0])
        lanes2.append(pair[1])
    return np.stack(lanes1), np.stack(lanes2)


def _hmac_msg_blocks(data: bytes, little_endian: bool = False) -> np.ndarray:
    """Pad an HMAC inner message (keyed by one 64-byte block) -> [nb, 16]."""
    return np.asarray(
        bo.message_blocks(data, little_endian, prefix_len=64), dtype=np.uint32
    )


def _nc_variants(h: hl.Hashline, nc: int):
    """(last4, delta, endian) list honoring message_pair gating bits."""
    variants = [(h.anonce[28:32], 0, None)]
    if h.message_pair & hl.MP_APLESS:
        return variants  # M1/M2 from the AP's own frame: nonce is exact
    endians = []
    if h.message_pair & hl.MP_LE:
        endians.append("LE")
    if h.message_pair & hl.MP_BE:
        endians.append("BE")
    if not endians:
        endians = ["LE", "BE"]
    last_le = struct.unpack_from("<I", h.anonce, 28)[0]
    last_be = struct.unpack_from(">I", h.anonce, 28)[0]
    for i in range(1, (nc >> 1) + 2):
        for e in endians:
            if e == "LE":
                variants.append((struct.pack("<I", (last_le + i) & 0xFFFFFFFF), i, "LE"))
                variants.append((struct.pack("<I", (last_le - i) & 0xFFFFFFFF), -i, "LE"))
            else:
                variants.append((struct.pack(">I", (last_be + i) & 0xFFFFFFFF), i, "BE"))
                variants.append((struct.pack(">I", (last_be - i) & 0xFFFFFFFF), -i, "BE"))
    return variants


@dataclass
class PreppedNet:
    """Device-ready constants for one hashline."""

    line: hl.Hashline
    keyver: int                      # 1 | 2 | 3 | 100 (PMKID)
    target: np.ndarray               # uint32[4] (PMKID/MIC words; LE for keyver 1)
    # PMKID path
    pmkid_block: np.ndarray = None   # uint32[16]
    # EAPOL path
    variants: tuple = ()             # ((delta, endian), ...) aligned with prf_blocks
    prf_blocks: np.ndarray = None    # uint32[V, 2, 16] PRF inner-message variants
    eapol_blocks: np.ndarray = None  # uint32[E, 16] (keyver 1: LE words, 2: BE)
    # keyver 3 (AES-128-CMAC MIC)
    cmac_full: np.ndarray = None     # uint32[F, 16] byte values
    cmac_last: np.ndarray = None     # uint32[16] byte values (10*-padded)
    cmac_last_complete: bool = False
    cmac_target: np.ndarray = None   # uint32[16] byte values


def prep_net(h: hl.Hashline, nc: int = DEFAULT_NC) -> PreppedNet:
    """Precompute every per-net constant the device kernels need."""
    if h.hash_type == hl.TYPE_PMKID:
        msg = b"PMK Name" + h.mac_ap + h.mac_sta
        return PreppedNet(
            line=h,
            keyver=100,
            target=np.asarray(bo.be_words(h.pmkid_or_mic), dtype=np.uint32),
            pmkid_block=_hmac_msg_blocks(msg)[0],
        )

    keyver = h.keyver
    if keyver not in (1, 2, 3):
        raise ValueError(f"uncrackable key descriptor version {keyver}")
    m, n, ap_off = oracle.nonce_pairs(h)
    variants = _nc_variants(h, nc)
    prf = []
    for last4, _, _ in variants:
        nv = n[: ap_off + 28] + last4 + n[ap_off + 32 :]
        if keyver == 3:
            msg = oracle.PRF_LABEL_V3 + m + nv + b"\x80\x01"
        else:
            msg = oracle.PRF_LABEL_V12 + m + nv + b"\x00"
        prf.append(_hmac_msg_blocks(msg))
    prepped = PreppedNet(
        line=h,
        keyver=keyver,
        target=np.asarray(
            bo.le_words(h.pmkid_or_mic) if keyver == 1 else bo.be_words(h.pmkid_or_mic),
            dtype=np.uint32,
        )[:4],
        variants=tuple((d, e) for _, d, e in variants),
        prf_blocks=np.stack(prf),
    )
    if keyver == 3:
        ep = h.eapol
        nblk = max(1, (len(ep) + 15) // 16)
        complete = len(ep) > 0 and len(ep) % 16 == 0
        last = ep[(nblk - 1) * 16 :]
        if not complete:
            last = last + b"\x80" + b"\x00" * (15 - len(last))
        prepped.cmac_full = np.frombuffer(
            ep[: (nblk - 1) * 16], dtype=np.uint8
        ).reshape(nblk - 1, 16).astype(np.uint32)
        prepped.cmac_last = np.frombuffer(last, dtype=np.uint8).astype(np.uint32)
        prepped.cmac_last_complete = complete
        prepped.cmac_target = np.frombuffer(h.pmkid_or_mic, dtype=np.uint8).astype(
            np.uint32
        )
    else:
        prepped.eapol_blocks = _hmac_msg_blocks(h.eapol, little_endian=(keyver == 1))
    return prepped


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _rows(arr2d, n=None):
    """[R, 16] array -> list of row-lists of traced scalars."""
    r = arr2d.shape[0] if n is None else n
    return [[arr2d[i, j] for j in range(16)] for i in range(r)]


def _pmk_impl(pw_words, salt1, salt2, use_pallas=None):
    """PBKDF2 batch: Pallas register-resident kernel on TPU (~4.8x the
    pure-XLA fori_loop formulation on v5e), XLA path elsewhere.

    ``pw_words`` may arrive column-trimmed ([B, W<16]): the host ships
    only the uint32 columns real candidates occupy (a 12-byte dict word
    does not pay H2D for a 64-byte row; the H2D cost behind this was
    measured on an earlier remote setup and is unmeasured on a local
    chip) and the zero tail of the HMAC key block is reconstituted
    here, on device, where padding is a free fusion.

    ``salt1``/``salt2`` are either uint32[16] (one ESSID for the whole
    batch — the scalar-salt fast path every mask/steady dispatch keeps)
    or uint32[B, 16] (PER-LANE salts: lane b hashes its own ESSID — the
    mixed-ESSID fused batch path, ``parallel.step.fused_pmk_step``).
    jit keys on the salt rank, so the two modes never share or thrash a
    cache entry; per-lane widths must come from the static fused-width
    pad table (lint rule DW109) so the 2-D entries stay bounded too.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if pw_words.shape[1] < 16:
        pw_words = jnp.pad(pw_words, ((0, 0), (0, 16 - pw_words.shape[1])))
    if use_pallas:
        return pbkdf2_sha1_pmk_pallas(pw_words, salt1, salt2)
    pw = [pw_words[:, i] for i in range(16)]
    if salt1.ndim == 2:
        s1 = [salt1[:, i] for i in range(16)]
        s2 = [salt2[:, i] for i in range(16)]
    else:
        s1 = [salt1[i] for i in range(16)]
        s2 = [salt2[i] for i in range(16)]
    return jnp.stack(pbkdf2_sha1_pmk(pw, s1, s2))


#: pmk_kernel(pw_words[B,16], salt1[16]|[B,16], salt2 likewise) -> uint32[8, B]
pmk_kernel = jax.jit(_pmk_impl, static_argnames=("use_pallas",))


def _pmk_key_block(pmk):
    return [pmk[i] for i in range(8)] + [0] * 8


def _eq4(out, target):
    m = out[0] == target[0]
    for i in range(1, 4):
        m = m & (out[i] == target[i])
    return m


def _pmkid_impl(pmk, msg_block, target):
    shape = pmk.shape[1:]
    ist, ost = hm.hmac_sha1_precompute(
        _pmk_key_block(pmk), shape, compress=sha1_compress_rolled
    )
    out = hm.hmac_sha1_blocks(
        ist, ost, [[msg_block[i] for i in range(16)]], compress=sha1_compress_rolled
    )
    return _eq4(out, target)




def eapol_match(pmk, prf_blocks, eapol_blocks, target, *, keyver):
    """MIC match for keyver 1/2 over all NC variants (the XLA form; on
    TPU ``parallel/step.py`` runs ``ops/eapol_pallas.py`` instead).

    ``pmk``: uint32[8, B]; ``prf_blocks``: uint32[V, 2, 16];
    ``eapol_blocks``: uint32[E, 16]; ``target``: uint32[4].
    Returns bool[V, B].
    """
    shape = pmk.shape[1:]
    ist, ost = hm.hmac_sha1_precompute(
        _pmk_key_block(pmk), shape, compress=sha1_compress_rolled
    )
    eap = _rows(eapol_blocks)

    def per_variant(blk2):
        prf = hm.hmac_sha1_blocks(ist, ost, _rows(blk2, 2), compress=sha1_compress_rolled)
        kck = list(prf[:4])
        if keyver == 1:
            kb = [bswap32(w) for w in kck] + [0] * 12
            ii, oo = hm.hmac_md5_precompute(kb, shape, compress=md5_compress_rolled)
            out = hm.hmac_md5_blocks(ii, oo, eap, compress=md5_compress_rolled)
        else:
            kb = kck + [0] * 12
            ii, oo = hm.hmac_sha1_precompute(kb, shape, compress=sha1_compress_rolled)
            out = hm.hmac_sha1_blocks(ii, oo, eap, compress=sha1_compress_rolled)
        return _eq4(out, target)

    return jax.lax.map(per_variant, prf_blocks)




def eapol_cmac_match(pmk, prf_blocks, cmac_full, cmac_last, target, *, last_complete):
    """AES-128-CMAC MIC match (keyver 3, WPA2 802.11w) -> bool[V, B]."""
    shape = pmk.shape[1:]
    ist, ost = hm.hmac_sha256_precompute(
        _pmk_key_block(pmk), shape, compress=sha256_compress_rolled
    )

    def per_variant(blk2):
        prf = hm.hmac_sha256_blocks(
            ist, ost, _rows(blk2, 2), compress=sha256_compress_rolled
        )
        kck_bytes = []
        for w in prf[:4]:
            kck_bytes += [
                (w >> 24) & u32(0xFF),
                (w >> 16) & u32(0xFF),
                (w >> 8) & u32(0xFF),
                w & u32(0xFF),
            ]
        mac = aes128_cmac_rolled(
            jnp.stack(kck_bytes), cmac_full, cmac_last, last_complete
        )
        return jnp.all(mac == target[:, None], axis=0)

    return jax.lax.map(per_variant, prf_blocks)




def net_match(pmk, net: PreppedNet):
    """Trace-time dispatch of one prepped net -> bool[V, B] (composable)."""
    if net.keyver == 100:
        m = _pmkid_impl(pmk, jnp.asarray(net.pmkid_block), jnp.asarray(net.target))
        return m[None, :]
    if net.keyver == 3:
        return eapol_cmac_match(
            pmk,
            jnp.asarray(net.prf_blocks),
            jnp.asarray(net.cmac_full),
            jnp.asarray(net.cmac_last),
            jnp.asarray(net.cmac_target),
            last_complete=net.cmac_last_complete,
        )
    return eapol_match(
        pmk,
        jnp.asarray(net.prf_blocks),
        jnp.asarray(net.eapol_blocks),
        jnp.asarray(net.target),
        keyver=net.keyver,
    )


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Found:
    """One cracked net, shaped like the reference's verifier return value
    ``[PSK, NC, BE/LE, PMK]`` (web/common.php:152-155)."""

    line: hl.Hashline
    psk: bytes
    nc: int            # signed NC delta (0 = exact)
    endian: str        # "LE" | "BE" | "" (exact / PMKID)
    pmk: bytes


def _trim_cols(max_len: int) -> int:
    """uint32 columns to ship for a batch whose longest word is
    ``max_len`` bytes, bucketed to {4, 8, 16} so jit sees at most three
    width signatures.  The device pads back to the full 16-word HMAC
    key block (see _pmk_impl); for typical dicts (words <= 16 chars)
    this cuts candidate H2D traffic 4x (what that buys on a local chip
    is unmeasured).

    Multi-process meshes always ship full rows: every host must enter
    the shard_map with identical shapes, and hosts can't agree on a
    width without a collective that would cost more than it saves."""
    if jax.process_count() > 1:
        return 16
    need = -(-max_len // 4)
    for w in (4, 8):
        if need <= w:
            return w
    return 16


class _PackedWords:
    """Lazy pws view over native-packed rows: ``[b]`` reconstructs the
    decoded candidate bytes from its packed key block + length, so the
    word is only materialized for the rare hit columns."""

    __slots__ = ("words", "lens")

    def __init__(self, words, lens):
        self.words = words
        self.lens = lens

    def __getitem__(self, b):
        return bo.words_to_bytes_be(self.words[b])[: int(self.lens[b])]


class _RuleWords:
    """pws view for a device-mangled batch: column ``b`` decodes by
    applying the host rule to the base word — the executable spec — so
    hit decode never trusts the device transform."""

    __slots__ = ("base", "rule")

    def __init__(self, base, rule):
        self.base = base
        self.rule = rule

    def __getitem__(self, b):
        out = self.rule.apply(self.base[b])
        if out is None or not MIN_PSK_LEN <= len(out) <= MAX_PSK_LEN:
            return None  # rejected/out-of-range: column was zeroed on device
        return out


class _ShiftedWords:
    """pws view for one unit's lane window inside a fused batch: batch
    column ``b`` maps to the unit's own candidate list at ``b - lo``;
    columns outside the window (other units' lanes, padding) decode to
    None so ``_decode`` skips them even if a demux mask ever slipped."""

    __slots__ = ("words", "lo")

    def __init__(self, words, lo):
        self.words = words
        self.lo = lo

    def __getitem__(self, b):
        i = b - self.lo
        return self.words[i] if 0 <= i < len(self.words) else None


class _BaseWords:
    """Lazy base-word list over packed rows + lengths (the warm rules
    cache keeps bases in packed device layout; the fallback split
    guarantees they are HEX-free, so a packed row round-trips
    losslessly).  Supports ``len``/indexing/iteration like the raw word
    list it replaces, materializing bytes only on demand."""

    __slots__ = ("rows", "lens", "n")

    def __init__(self, rows, lens, n):
        self.rows = rows
        self.lens = lens
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, b):
        if not 0 <= b < self.n:
            raise IndexError(b)
        return bo.words_to_bytes_be(self.rows[b])[: int(self.lens[b])]


class _MaskWords:
    """pws stand-in for on-device mask generation: index -> word bytes,
    computed on demand from the keyspace position.

    Indexed by GLOBAL batch column (a pure function of the keyspace
    index) — on a multi-process mesh every host can materialize any
    column, so the find decode skips the candidate exchange (see
    ``_gather_find_data``)."""

    __slots__ = ("mask", "custom", "start")

    global_cols = True

    def __init__(self, mask, custom, start):
        self.mask = mask
        self.custom = custom
        self.start = start

    def __getitem__(self, b):
        from ..gen.mask import mask_words

        return next(mask_words(self.mask, self.custom,
                               skip=self.start + b, limit=1))


class _RulesCtx:
    """Shared per-attack context for the device-expansion seam
    (``M22000Engine._rules_flush``): the split rule sets, the expanded
    stream's rule count, and the attack's telemetry.  One ctx serves
    every rules dispatch path — serial ``crack_rules``, block-framed
    ``crack_rules_blocks`` and the per-device stream adapter — so the
    fallback routing, resume accounting and metrics cannot drift
    between executors."""

    def __init__(self, rules, registry=None, tracer=None):
        from ..obs.metrics import default_registry
        from ..obs.spans import SpanTracer, default_tracer
        from ..rules.device import device_supported, encode_rule, step_bucket

        self.rules = list(rules)
        self.dev_rules = [(r, encode_rule(r)) for r in self.rules
                          if device_supported(r)]
        # one step bucket for every chunk of the ruleset (stack_rules)
        self.n_steps = step_bucket(max(
            (s.shape[0] for _, s in self.dev_rules), default=1))
        self.host_rules = [r for r in self.rules
                           if not device_supported(r)]
        self.n_rules = len(self.rules)
        reg = registry if registry is not None else default_registry()
        if tracer is None:
            tracer = default_tracer() if registry is None \
                else SpanTracer(registry)
        self.tracer = tracer
        self.m_device = reg.counter(
            "dwpa_rules_device_expanded_total",
            "(word, rule) pairs expanded on device by the rules seam")
        fb = reg.counter(
            "dwpa_rules_host_fallback_total",
            "(word, rule) pairs routed to the host rule interpreter, "
            "by reason (purge = unsupported op, overflow = length/HEX)")
        self.m_purge = fb.labels(reason="purge")
        self.m_overflow = fb.labels(reason="overflow")

    def span(self, name: str):
        return self.tracer.span(name)


class _BlockAgg:
    """Demux per-sub-batch pipeline events back into per-BLOCK reports.

    A rules block expands into several dispatched sub-batches (fused
    rule chunks + the host-expanded tail); ``_Pipeline`` fires its
    callback once per sub-batch, in stream order, but block callers
    (``crack_rules_blocks`` and the client resume checkpoint behind it)
    need exactly one ``on_batch(consumed, founds)`` per base block.
    ``begin``/``emit``/``close`` bracket each block's emissions;
    ``record`` (installed as the pipeline callback) attributes every
    event to the oldest incompletely-fired block — emission order IS
    event order because the pipeline is FIFO — and a block fires once
    closed and fully collected.  A block that emitted nothing (wholly
    inside the resume prefix, or nothing dispatchable) reports
    nothing, matching ``crack_rules``'s skip semantics."""

    def __init__(self, on_batch):
        import collections

        self.on_batch = on_batch
        self.blocks = collections.deque()
        self.cur = None

    def begin(self):
        self.cur = {"emitted": 0, "fired": 0, "got": 0,
                    "founds": [], "closed": False}
        self.blocks.append(self.cur)

    def emit(self):
        self.cur["emitted"] += 1

    def record(self, raw, new):
        for b in self.blocks:
            if b["fired"] < b["emitted"]:
                b["fired"] += 1
                b["got"] += raw
                b["founds"].extend(new)
                break
        self._fire()

    def close(self):
        self.cur["closed"] = True
        self.cur = None
        self._fire()

    def _fire(self):
        while self.blocks:
            b = self.blocks[0]
            if not b["closed"] or b["fired"] < b["emitted"]:
                return
            self.blocks.popleft()
            if b["emitted"] and self.on_batch is not None:
                self.on_batch(b["got"], b["founds"])


class _Pipeline:
    """Shared dispatch/sync pipeline for the engine's crack paths.

    Holds up to ``engine.PIPELINE_DEPTH`` dispatched batches; ``push``
    finishes the oldest once the depth is exceeded, so the hits-gate
    sync always trails the dispatch frontier.  ``on_batch`` fires in
    stream order — crack() and crack_mask() share these semantics by
    construction instead of re-implementing them (they had already
    drifted on effective depth once).
    """

    def __init__(self, engine, on_batch=None):
        import collections

        self.engine = engine
        self.on_batch = on_batch
        self.pending = collections.deque()  # (dispatched, raw), oldest first
        self.founds = []

    @property
    def active(self) -> bool:
        return bool(self.pending)

    def push(self, dispatched, raw: int):
        self.pending.append((dispatched, raw))
        if len(self.pending) > self.engine.PIPELINE_DEPTH:
            self.finish_one()

    def skip(self, raw: int):
        """A consumed-but-undispatchable batch: drain first so the
        report keeps stream order (resume skip-by-count depends on it)."""
        self.drain()
        if self.on_batch is not None:
            self.on_batch(raw, [])

    def finish_one(self):
        dispatched, raw = self.pending.popleft()
        new = self.engine._collect(dispatched)
        self.founds.extend(new)
        if self.on_batch is not None:
            self.on_batch(raw, new)

    def drain(self):
        while self.pending:
            self.finish_one()


def _staged(stage: str):
    """Run a method as one ``engine:<stage>`` span of the engine's tracer
    (``M22000Engine._stage``)."""

    def deco(fn):
        @functools.wraps(fn)
        def staged(self, *args, **kwargs):
            with self._stage(stage):
                return fn(self, *args, **kwargs)

        return staged

    return deco


class M22000Engine:
    """Crack a set of m22000 hashlines with batches of candidate PSKs.

    ESSID grouping mirrors the reference scheduler's amortization trick
    (web/content/get_work.php:96-109): one PBKDF2 per (candidate, ESSID)
    feeds the PMKID/MIC checks of every net sharing that ESSID.

    The product path is the mesh-sharded crack step (parallel/step.py):
    candidates split over the "dp" axis, PBKDF2+verify per shard, and a
    psum'd scalar hit count fetched as the only per-batch host sync — the
    full match matrix and PMKs cross to the host only on the rare batch
    that actually contains a find.  ``mesh="auto"`` spans every local
    device; a 1-device mesh degenerates to the single-chip path.
    """

    def __init__(self, lines, nc: int = DEFAULT_NC, batch_size: int = 4096,
                 verify_with_oracle: bool = True, mesh="auto",
                 pmk_store=None, tracer=None):
        from ..obs.spans import default_tracer
        from ..parallel import default_mesh

        if mesh == "auto":
            mesh = default_mesh()
        self.mesh = mesh
        # Optional persistent PBKDF2 cache (dwpa_tpu.pmkstore): the feed
        # packer splits blocks into cache hits/misses on the producer
        # threads, the mixed dispatch computes only the misses, and
        # _collect writes newly derived PMKs back after the device fetch.
        self.pmk_store = pmk_store
        # Pad batches to a multiple of the mesh size (shard_map needs the
        # candidate axis evenly split).
        n = mesh.size
        self.batch_size = -(-int(batch_size) // n) * n
        self.nc = nc
        self.verify_with_oracle = verify_with_oracle
        self.groups = {}  # essid -> list[PreppedNet] (live/uncracked view)
        self.skipped = []
        # Steps are built once per ESSID group over its FULL original
        # membership and reused for the engine's lifetime: a find masks
        # its net host-side in _collect instead of shrinking the step's
        # shapes, which would move it to a different jit-cache entry.
        # (Compilations themselves are shared process-wide by shape
        # signature — parallel/step.py — so building a step is cheap.)
        self._full = {}   # essid -> original list[PreppedNet]
        self._steps = {}  # essid -> crack step (parallel.build_crack_step)
        self._rules_steps = {}  # essid -> fused rules step (build_rules_step)
        # Per-stage wall-clock sums (SURVEY.md §5.1) of the engine's
        # ``engine:prepare`` (host pack + H2D enqueue), ``engine:dispatch``
        # (step launch: host time to enqueue, compiles included) and
        # ``engine:collect`` (sync + decode) spans on ``tracer`` (the
        # client passes its own).  "collect" is where device compute
        # surfaces under the async runtime; the device's own time is in
        # the profiler's device trace, not here.
        # Keys are API (the client's stage log and tests read them).
        # Since the candidate feed (dwpa_tpu/feed) moved packing onto
        # producer threads, "prepare" counts only the RESIDUAL on-thread
        # work — device staging for prepacked blocks, or the full pack
        # for non-feed callers; producer-side pack time lives in the
        # feed's ``feed:produce`` spans instead, so the two are never
        # double-counted.
        self.stage_times = {"prepare": 0.0, "dispatch": 0.0, "collect": 0.0}
        self._stage_lock = threading.Lock()
        self.tracer = tracer or default_tracer()
        for line in lines:
            try:
                h = line if isinstance(line, hl.Hashline) else hl.parse(line)
                net = prep_net(h, nc=nc)
            except ValueError:
                self.skipped.append(line)
                continue
            self.groups.setdefault(h.essid, []).append(net)
        self._full = {e: list(g) for e, g in self.groups.items()}
        self._salts = {e: essid_salt_blocks(e) for e in self.groups}

    @property
    def nets(self):
        return [n for group in self.groups.values() for n in group]

    @contextlib.contextmanager
    def _stage(self, stage: str):
        """One ``engine:<stage>`` span, whose seconds ``stage_times``
        sums.  These are host spans: ``engine:dispatch`` is the time to
        enqueue the steps (and to compile a new shape), never the
        device's time, which the profiler's device trace holds."""
        with self.tracer.span("engine:" + stage) as sp:
            yield sp
        with self._stage_lock:
            self.stage_times[stage] += sp.seconds

    def remove(self, found: Found):
        """Drop a cracked net (and empty groups) from further batches."""
        group = self.groups.get(found.line.essid)
        if not group:
            return
        group[:] = [n for n in group if n.line is not found.line]
        if not group:
            del self.groups[found.line.essid]
            del self._salts[found.line.essid]
            self._steps.pop(found.line.essid, None)
            self._rules_steps.pop(found.line.essid, None)
            self._full.pop(found.line.essid, None)

    def _step_for(self, essid: bytes):
        """The mesh crack step for one ESSID group, built once over the
        group's full original membership (see __init__)."""
        from ..parallel import build_crack_step

        step = self._steps.get(essid)
        if step is None:
            s1, s2 = self._salts[essid]
            step = build_crack_step(self.mesh, list(self._full[essid]), s1, s2)
            self._steps[essid] = step
        return step

    def _rules_step_for(self, essid: bytes):
        """The fused expand+crack step (build_rules_step) for one ESSID
        group — same full-membership / lifetime contract as _step_for."""
        from ..parallel.step import build_rules_step

        step = self._rules_steps.get(essid)
        if step is None:
            s1, s2 = self._salts[essid]
            step = build_rules_step(self.mesh, list(self._full[essid]), s1, s2)
            self._rules_steps[essid] = step
        return step

    @_staged("prepare")
    def _prepare(self, passwords):
        """Host stage: decode, filter, pad, pack, and start the async H2D.

        Returns ``(pws, nvalid, pw_words)`` or None if nothing valid.  The
        device_put is asynchronous, so calling this while a previous
        batch's steps are still executing overlaps the transfer with
        compute (see ``crack``).
        """
        from ..parallel import shard_candidates

        plist = passwords if isinstance(passwords, list) else list(passwords)
        if not plist:
            # Multi-process: an empty local block must still dispatch
            # padding or the peers' shard_map collectives hang (see
            # _padding_prep; returns None single-process).
            return self._padding_prep()
        # Pad to batch_size (or, for an oversize caller-supplied batch, up
        # to the next mesh-size multiple so the shard_map split stays even).
        cap = max(self.batch_size,
                  -(-len(plist) // self.mesh.size) * self.mesh.size)
        # Native fast path: $HEX decode + length filter + pack fused in
        # one C pass (native/pack_fast.cpp) — the host feed must outrun
        # a mesh, not one chip.  Falls back to the Python pipeline when
        # the library is unavailable or the batch isn't plain bytes.
        from ..native import pack_candidates_fast

        fast = pack_candidates_fast(plist, MIN_PSK_LEN, MAX_PSK_LEN,
                                    capacity=cap)
        if fast is not None:
            packed, lens, nvalid = fast
            if nvalid == 0:
                return self._padding_prep()
            # Size the device batch from the post-filter count, exactly
            # like the fallback: an oversize batch full of invalid words
            # must not inflate the shape (extra zero-row PBKDF2s and a
            # fresh jit entry).
            target = max(self.batch_size,
                         -(-nvalid // self.mesh.size) * self.mesh.size)
            w = _trim_cols(int(lens.max()) if nvalid else MIN_PSK_LEN)
            pw_words = shard_candidates(
                self.mesh, np.ascontiguousarray(packed[:target, :w])
            )
            return _PackedWords(packed, lens), nvalid, pw_words

        # $HEX[...] notation decodes to raw bytes before hashing, matching
        # the server's candidate handling (hc_unhex, web/common.php:3-25).
        pws = [oracle.hc_unhex(p) for p in plist]
        pws = [p for p in pws if MIN_PSK_LEN <= len(p) <= MAX_PSK_LEN]
        if not pws:
            return self._padding_prep()
        nvalid = len(pws)
        target = max(self.batch_size, -(-nvalid // self.mesh.size) * self.mesh.size)
        w = _trim_cols(max(len(p) for p in pws))
        if nvalid < target:
            pws = pws + [b"\x00" * MIN_PSK_LEN] * (target - nvalid)
        pw_words = shard_candidates(
            self.mesh, np.ascontiguousarray(bo.pack_passwords_be(pws)[:, :w])
        )
        return pws, nvalid, pw_words

    def host_packer(self):
        """Pure-host packing closure for feed producer threads.

        Captures the batch geometry as plain ints so the closure touches
        no engine/jax state from the thread (lint rule DW107: producer
        threads may not touch jax device APIs) — decode, filter and pack
        only; the consumer thread stages the result via
        ``_prepare_staged``.  Returns None when the native packer is
        unavailable (the block then takes the full ``_prepare`` path
        on-thread, unchanged semantics).  ``pack(words, pre=...)``
        accepts an already-packed ``(rows, lens, nvalid)`` from the
        dict cache's warm path and skips the packer (the feed detects
        this via ``pack.supports_pre``); the store split below still
        applies, so warm blocks compose with the PMK-store hit/miss
        dispatch.

        With a ``pmk_store`` attached the closure additionally splits the
        packed block into per-ESSID cache hits and misses
        (``pmkstore.stage.split_block`` — store lookups are mmap/dict
        reads, still pure host work) and returns a ``MixedPrep`` the
        engine's mixed dispatch consumes.  Single-process only: on a
        multi-host slice the per-host miss counts would pick different
        static widths and desync the shard_map shapes, so the split
        would need a width-agreement collective the producer thread must
        not run — multi-host engines keep the plain path (each host's
        store still accumulates its own framed slice via write-back).
        """
        from ..native import pack_candidates_fast

        bs, n = self.batch_size, self.mesh.size
        store = self.pmk_store if jax.process_count() == 1 else None
        essids = list(self._salts) if store is not None else None

        def pack(words, pre=None):
            # ``pre``: an already-packed (rows, lens, nvalid) from the
            # dict cache's warm path (feed.dictcache) — identical to
            # what pack_candidates_fast would return for ``words``, so
            # the packer is bypassed entirely and only the PMK-store
            # split (when attached) still runs
            if pre is not None:
                fast = pre
            else:
                cap = max(bs, -(-len(words) // n) * n)
                fast = pack_candidates_fast(words, MIN_PSK_LEN, MAX_PSK_LEN,
                                            capacity=cap)
            if fast is None or store is None:
                return fast
            packed, lens, nvalid = fast
            if nvalid == 0:
                return fast
            from ..pmkstore.stage import split_block

            return split_block(store, essids, packed, lens, nvalid, bs, n)

        pack.supports_pre = True
        return pack

    @_staged("prepare")
    def _prepare_staged(self, packed, lens, nvalid):
        """Consumer-side residual of ``_prepare`` for a feed-prepacked
        block: only the device staging (column trim + async H2D) — the
        packing already happened on a producer thread and is accounted
        to the feed's ``feed:produce`` spans, so ``stage_times["prepare"]``
        accumulates just this residual (see the stage_times comment).
        """
        from ..parallel import shard_candidates

        if nvalid == 0:
            return self._padding_prep()
        target = max(self.batch_size,
                     -(-nvalid // self.mesh.size) * self.mesh.size)
        w = _trim_cols(int(lens.max()))
        pw_words = shard_candidates(
            self.mesh, np.ascontiguousarray(packed[:target, :w])
        )
        return _PackedWords(packed, lens), nvalid, pw_words

    def _prepare_block(self, block):
        """Prep one feed block (``dwpa_tpu.feed.framing.Block``):
        store-split mixed path when the producer looked the block up in
        the PMK cache, staged fast path when it merely prepacked it,
        full ``_prepare`` otherwise."""
        prep = getattr(block, "prep", None)
        if prep is None:
            return self._prepare(block.words)
        if hasattr(prep, "mask_gen"):
            # on-device mask generation (gen.mask.MaskPrep): no host
            # bytes at all — generate the block's keyspace slice
            # directly under this engine's mesh sharding (a 1-device
            # stream engine generates exactly its own candidates)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..gen.mask import device_mask_words
            from ..parallel.mesh import DP_AXIS

            n = block.count
            gen = -(-n // self.mesh.size) * self.mesh.size
            with self._stage("prepare"):
                pw_words = device_mask_words(
                    prep.mask, prep.start, gen, prep.custom,
                    sharding=NamedSharding(self.mesh, P(DP_AXIS, None)),
                )
            return _MaskWords(prep.mask, prep.custom, prep.start), n, pw_words
        if hasattr(prep, "materialize"):
            # a lazy dict-cache prep (framing.PackedSlices) normally
            # materializes on the feed's producer threads; blocks
            # consumed without a feed (direct frame_packed iteration)
            # materialize here instead — pure host array copies, not
            # cache file I/O (the mmap was opened producer-side)
            prep = prep.materialize()
        from ..pmkstore.stage import MixedPrep

        if isinstance(prep, MixedPrep):
            return self._prepare_mixed(prep)
        return self._prepare_staged(*prep)

    @_staged("prepare")
    def _prepare_mixed(self, mp):
        """Consumer-side staging of a store-split block: start the async
        H2D of each group's compacted miss sub-batch (column-trimmed
        like ``_prepare_staged``); the cached-PMK matrices stay host
        arrays until dispatch.  Same ``stage_times["prepare"]``
        accounting as the staged path — the split itself ran on a
        producer thread and lives in ``feed:produce`` spans."""
        from ..parallel import shard_candidates

        for ent in mp.entries.values():
            if ent.nmiss:
                w = _trim_cols(int(ent.miss_lens.max()))
                ent.miss_dev = shard_candidates(
                    self.mesh, np.ascontiguousarray(ent.miss_rows[:, :w]))
        return _PackedWords(mp.packed, mp.lens), mp.nvalid, mp

    def _padding_prep(self):
        """All-padding batch for a shard that contributed no valid words.

        On a multi-process mesh every host must enter the shard_map
        collective in lockstep: if this host returned None (skip) while
        its peers dispatched, their devices would wait forever.  A
        batch_size block of zero rows keeps the step shapes identical
        everywhere; nvalid=0 masks every column at decode, so the only
        cost is one batch of wasted PBKDF2 on this host's shard — paid
        on the rare all-invalid shard, never on the common path.
        Single-process engines keep the cheap skip instead.
        """
        from ..parallel import shard_candidates

        if jax.process_count() <= 1:
            return None
        pw_words = shard_candidates(
            self.mesh, np.zeros((self.batch_size, _trim_cols(MIN_PSK_LEN)),
                                np.uint32)
        )
        return [], 0, pw_words

    @_staged("dispatch")
    def _dispatch(self, prep):
        """Launch the crack step for every live ESSID group (no host sync).

        The step always runs over the group's full original membership
        (cracked nets included — their extra MIC checks are noise next to
        the shared PBKDF2); _collect masks the dead rows.
        """
        pws, nvalid, pw_words = prep
        from ..pmkstore.stage import MixedPrep

        if isinstance(pw_words, MixedPrep):
            return self._dispatch_mixed(pws, nvalid, pw_words)
        outs = []
        for essid in list(self.groups):
            step = self._step_for(essid)
            outs.append((self._full[essid], step(pw_words)))
        return pws, nvalid, outs

    def _dispatch_mixed(self, pws, nvalid, mp):
        """Mixed hit/miss dispatch (PMK store): per group, PBKDF2 runs
        only on the compacted miss sub-batch, cached PMKs are gathered
        around the computed ones into the full ``uint32[8, B]`` matrix
        (``parallel.step.mix_step``), and the group's verify kernels run
        unchanged on that matrix — an all-hit block dispatches ZERO
        PBKDF2 work.  The returned record carries the write-back list
        (miss PMK device arrays + their words) that ``_collect`` flushes
        to the store AFTER its device fetch, on the consumer thread
        (lint rule DW108: write-back never runs in a producer or traced
        region)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.mesh import DP_AXIS
        from ..parallel.step import mix_step

        pmk_sharding = getattr(self, "_pmk_sharding", None)
        if pmk_sharding is None:
            pmk_sharding = self._pmk_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, DP_AXIS))
        outs, writeback = [], []
        for essid in list(self.groups):
            step = self._step_for(essid)
            ent = mp.entries[essid]
            if ent.nmiss == 0:
                pmk = jax.device_put(ent.cached, pmk_sharding)
            else:
                pmk_miss = step.compute_pmk(ent.miss_dev)
                writeback.append(
                    (essid, pmk_miss, ent.miss_words, ent.nmiss))
                pmk = (pmk_miss if ent.nhit == 0 else
                       mix_step(self.mesh)(pmk_miss, ent.cached, ent.idx))
            outs.append((self._full[essid], step.verify(pmk)))
        return pws, nvalid, outs, None, writeback

    #: Per-host cap on hit columns exchanged in one multi-process batch
    #: (a fixed-size allgather keeps the exchange shape static; real
    #: crack batches see hits at ~1e-6 rates, so 128 is generous).
    MAX_FINDS_PER_BATCH = 128

    #: Merge the hits-gate and find-decode fetches into ONE device_get
    #: when a batch's whole output payload fits under this byte count:
    #: for small batches the gate + decode pair is then one D2H call
    #: instead of two.  The threshold was sized against per-call D2H
    #: latencies of an earlier remote setup; its rationale is unmeasured
    #: on a local chip.  Big batches keep the scalar gate: their dense
    #: matrices are MBs.
    SMALL_FETCH_BYTES = 600_000

    #: A find batch's PMK matrix up to this size is fetched whole with
    #: its match matrix; a larger one gives up only its hit columns
    #: (``parallel.step.gather_step``, ``jit_find_gather``).
    SMALL_PMK_BYTES = 1 << 21

    def _replicated(self, x):
        """Reshard a batch-sharded step output to fully replicated.

        On a multi-process mesh the raw outputs live partly on
        non-addressable devices, which ``np.asarray`` rejects; this jitted
        identity with a replicated out-sharding compiles to an all_gather
        that every process enters in lockstep (the psum hits-gate already
        agreed the batch has a hit, so control flow cannot diverge).
        One jit object per engine so only the first find per shape pays a
        compilation."""
        fn = getattr(self, "_replicate_jit", None)
        if fn is None:
            from jax.sharding import NamedSharding, PartitionSpec

            fn = jax.jit(
                lambda a: a,
                out_shardings=NamedSharding(self.mesh, PartitionSpec()),
            )
            self._replicate_jit = fn
        return fn(x)

    def _gather_find_data(self, found_dev, pmk_dev, pws, nvalid):
        """Multi-process hit decode (rare path).

        Returns ``(found, pmk_host, psk_by_col)``: the replicated find
        matrix/PMKs with every host's local padding columns masked, plus
        a global-column -> candidate-bytes map assembled by a fixed-size
        allgather — the candidate bytes exist only on the host that fed
        that shard (shard_candidates' process-local contract), while
        every host must decode identical founds so the engine's pruning
        (and the later compiled-step dispatch) stays in SPMD lockstep.
        """
        from jax.experimental import multihost_utils

        found = np.array(self._replicated(found_dev))
        pmk_host = np.asarray(self._replicated(pmk_dev))
        nproc = jax.process_count()
        pid = jax.process_index()
        tgt = found.shape[2] // nproc  # equal local batches (see _prepare)
        if getattr(pws, "global_cols", False):
            # Mask path: nvalid counts GLOBAL columns (crack_mask's n)
            # and candidates are a pure function of the global keyspace
            # index (_LazyWords), so mask the tail globally and let every
            # host materialize the hit words locally — identical bytes,
            # no exchange needed.  (The per-process masking below would
            # leave wrap/out-of-limit columns live on a partial batch.)
            found[:, :, nvalid:] = False
            hit_cols = [int(b) for b in np.flatnonzero(found.any(axis=(0, 1)))]
            return found, pmk_host, {b: pws[b] for b in hit_cols}
        nvalids = np.asarray(
            multihost_utils.process_allgather(np.array([nvalid]))
        ).reshape(-1)
        for p in range(nproc):
            found[:, :, p * tgt + int(nvalids[p]):(p + 1) * tgt] = False
        hit_cols = [int(b) for b in np.flatnonzero(found.any(axis=(0, 1)))]
        # Dict path: the candidate bytes exist only on the host that fed
        # that shard (shard_candidates' process-local contract), while
        # every host must decode identical founds so the engine's pruning
        # (and the later compiled-step dispatch) stays in SPMD lockstep.
        # Fixed-shape candidate exchange: [used(1) col(4) len(1) psk(63)]
        # rows, MAX_FINDS_PER_BATCH per round.  Every host derives every
        # host's owned-hit count from the (replicated) find matrix, so
        # all agree on the round count with no extra collective — and no
        # hit is ever dropped, however dense the batch.
        owned = {p: [b for b in hit_cols if b // tgt == p]
                 for p in range(nproc)}
        rounds = max(
            1, -(-max(len(c) for c in owned.values()) // self.MAX_FINDS_PER_BATCH)
        )
        mine = owned[pid]
        psk_by_col = {}
        for r in range(rounds):
            ex = np.zeros((self.MAX_FINDS_PER_BATCH, 6 + MAX_PSK_LEN), np.uint8)
            chunk = mine[r * self.MAX_FINDS_PER_BATCH:
                         (r + 1) * self.MAX_FINDS_PER_BATCH]
            for k, b in enumerate(chunk):
                psk = pws[b - pid * tgt]
                ex[k, 0] = 1
                ex[k, 1:5] = np.frombuffer(struct.pack("<I", b), np.uint8)
                ex[k, 5] = len(psk)
                ex[k, 6:6 + len(psk)] = np.frombuffer(psk, np.uint8)
            allex = np.asarray(multihost_utils.process_allgather(ex))
            allex = allex.reshape(-1, ex.shape[1])
            psk_by_col.update({
                int(struct.unpack("<I", row[1:5].tobytes())[0]):
                    row[6:6 + int(row[5])].tobytes()
                for row in allex if row[0]
            })
        return found, pmk_host, psk_by_col

    def _decode(self, group, found, pmk_col, pws, psk_by_col, live) -> list:
        """Decode one found matrix ([N, V_max, B]) into Found records.

        ``pmk_col(b) -> uint32[8]`` resolves a column's PMK words (a
        dense host matrix or the sparse gathered view — see _collect).
        ``live`` is a mutable id-set shared across a batch's decodes (a
        chunked rules dispatch carries several matrices for the same
        group — a net cracked by rule r must not re-report for r+1).
        """
        founds = []
        for ni, net in enumerate(group):
            if id(net.line) not in live:
                continue  # already cracked; the step still computes it
            nf = found[ni]  # [V_max, B]
            hit_cols = np.flatnonzero(nf.any(axis=0))
            for b in hit_cols:
                if psk_by_col is None:
                    psk = pws[b]
                    if psk is None:
                        continue  # zeroed rule column (see _RuleWords)
                else:
                    psk = psk_by_col.get(int(b))
                    if psk is None:
                        continue  # defensive: every hit col is exchanged
                delta, endian = (0, None)
                if net.keyver != 100:
                    delta, endian = net.variants[int(nf[:, b].argmax())]
                pmk_bytes = bo.words_to_bytes_be(pmk_col(int(b)))
                if self.verify_with_oracle:
                    chk = oracle.check_key_m22000(net.line, [psk], nc=self.nc)
                    if chk is None:
                        continue  # device false positive: reject like the server would
                founds.append(
                    Found(
                        line=net.line,
                        psk=psk,
                        nc=delta,
                        endian=endian or "",
                        pmk=pmk_bytes,
                    )
                )
                live.discard(id(net.line))
                break  # one PSK per net is enough
        return founds

    def _decode_rules(self, group, bits_dev, pws, nvalid, b_local, live) -> list:
        """Decode a fused rules chunk's bit-packed found-any mask.

        ``bits_dev``: uint32[R, B/32], bit b of word b>>5 = column b
        matched SOME net (build_rules_step).  The dense per-net matrix
        and PMKs never leave the device (~tens of MB per chunk); for
        each set bit the host re-derives which net, the NC delta/endian
        and the PMK by running the ORACLE on the decoded candidate —
        finds are rare and the oracle is the executable spec, so this
        is both cheap and authoritative (regardless of
        verify_with_oracle, which exists to double-check *device*
        claims; here the claim IS the oracle's).

        ``b_local`` is the dispatch's per-shard column count
        (``cap // mesh.size``), carried through the pipeline record from
        the ONE place that padded the batch — re-deriving it here from
        ``nvalid`` once silently sliced off every hit in a partial batch
        (``nvalid < batch_size`` pads to ``batch_size``, not to
        ``ceil(nvalid/n)*n``).
        """
        founds = []
        if jax.process_count() > 1:
            # Partly non-addressable on a multi-process mesh: the jitted
            # replicate (an all_gather every host enters in lockstep —
            # the hits-gate already agreed this batch has a find) hands
            # every host the identical global mask, and the global plain
            # list (see crack_rules' multi-process contract) lets each
            # decode every column locally — no candidate exchange.
            bits = np.asarray(self._replicated(bits_dev))
        else:
            bits = np.asarray(jax.device_get(bits_dev))
        # bits: [R, shards*ceil(b_local/32)].  Per-shard layout: each
        # device packs its local columns into ceil(b_local/32) words
        # (32-padded), and the dp out-sharding concatenates the shards —
        # undo both to recover global columns.
        n = self.mesh.size
        assert b_local * n >= nvalid, (b_local, n, nvalid)
        wpb = bits.shape[1] // n
        for r in range(bits.shape[0]):
            if pws[r] is None or not bits[r].any():
                continue  # chunk-padding rule, or no hits for this rule
            # ascontiguousarray: a device_get may hand back
            # non-C-contiguous rows, which .view(uint8) rejects.
            hit = np.unpackbits(
                np.ascontiguousarray(bits[r].reshape(n, wpb)).view(np.uint8),
                axis=1, bitorder="little",
            )[:, :b_local].reshape(-1)
            for b in np.flatnonzero(hit[:nvalid]):
                psk = pws[r][int(b)]
                if psk is None:
                    continue  # zeroed column (reject/overflow)
                for net in group:
                    if id(net.line) not in live:
                        continue
                    chk = oracle.check_key_m22000(net.line, [psk], nc=self.nc)
                    if chk is None:
                        continue  # device false positive for this net
                    _, delta, endian, pmk = chk
                    founds.append(
                        Found(line=net.line, psk=psk, nc=delta or 0,
                              endian=endian or "", pmk=pmk)
                    )
                    live.discard(id(net.line))
        return founds

    @_staged("collect")
    def _collect(self, dispatched) -> list:
        """Sync stage: gate on hits, decode founds, prune cracked nets."""
        pws, nvalid, outs = dispatched[:3]
        # Rules records carry the dispatch's per-shard width as a 4th
        # element (see _decode_rules on why it cannot be re-derived);
        # mixed-block records carry the PMK-store write-back list as a
        # 5th (see _dispatch_mixed).
        b_shard = dispatched[3] if len(dispatched) > 3 else None
        writeback = dispatched[4] if len(dispatched) > 4 else None
        multiproc = jax.process_count() > 1
        founds = []
        live = {id(n.line) for g in self.groups.values() for n in g}
        fetched = None
        if not multiproc and outs:
            payload = sum(int(a.nbytes) for _, out in outs for a in out[1:])
            if payload <= self.SMALL_FETCH_BYTES:
                # Small batch: ONE merged round trip for every group's
                # (hits, find data) — see SMALL_FETCH_BYTES.  The
                # downstream branches are payload-agnostic (device_get
                # on a host array is a no-op).
                fetched = jax.device_get([out for _, out in outs])
        for i, (group, out) in enumerate(outs):
            if fetched is not None:
                out = fetched[i]
            # The psum hits-gate: one replicated scalar is the only
            # device->host sync on the (overwhelmingly common) all-miss
            # batch; the [N, V, B] matrix and PMKs stay on device.
            if int(np.asarray(out[0])) == 0:
                continue
            if len(out) == 2:  # fused rules chunk: (hits, packed found-any)
                founds += self._decode_rules(group, out[1], pws, nvalid,
                                             b_shard, live)
                continue
            hits, found_dev, pmk_dev = out
            if multiproc:
                found, pmk_host, psk_by_col = self._gather_find_data(
                    found_dev, pmk_dev, pws, nvalid
                )
                founds += self._decode(group, found,
                                       lambda b: pmk_host[:, b], pws,
                                       psk_by_col, live)
                continue
            if pmk_dev.nbytes <= self.SMALL_PMK_BYTES:
                # Small batch: one merged fetch of both arrays (one D2H
                # call instead of two; this path is in every small work
                # unit's constant overhead).
                found, pmk_host = jax.device_get((found_dev, pmk_dev))
                found = np.array(found)
                pmk_col = lambda b: pmk_host[:, b]
            else:
                # Big batch: the dense PMK matrix is MBs while real find
                # batches carry a handful of hits.  Fetch the bool matrix alone, then
                # gather ONLY the hit columns' PMKs on device (fixed
                # 128-slot shape, one extra dispatch on find batches).
                found = np.array(jax.device_get(found_dev))
                found[:, :, nvalid:] = False
                cols = np.flatnonzero(found.any(axis=(0, 1)))
                if len(cols) <= self.MAX_FINDS_PER_BATCH:
                    from ..parallel.step import gather_step

                    gather = gather_step(self.mesh)
                    pad = np.zeros(self.MAX_FINDS_PER_BATCH, np.int32)
                    pad[: len(cols)] = cols
                    pmk_cols = np.asarray(gather(pmk_dev, pad))
                    slot = {int(b): i for i, b in enumerate(cols)}
                    pmk_col = lambda b: pmk_cols[:, slot[b]]
                else:  # pathological hit density: dense fallback
                    pmk_host = np.asarray(jax.device_get(pmk_dev))
                    pmk_col = lambda b: pmk_host[:, b]
            found[:, :, nvalid:] = False
            founds += self._decode(group, found, pmk_col, pws, None, live)
        for f in founds:
            self.remove(f)
        if writeback and self.pmk_store is not None:
            # PMK-store write-back: the one place newly derived PMKs
            # leave the device outside a find.  Runs on the consumer
            # thread after the hits-gate fetch (DW108's allowed seam);
            # the [8, width] miss matrix is an intentional per-batch
            # D2H — it is what turns the NEXT unit's repeats into hits.
            for essid, pmk_dev, miss_words, nmiss in writeback:
                pmk_host = jax.device_get(pmk_dev)
                self.pmk_store.put(essid, miss_words, pmk_host[:, :nmiss])
        return founds

    def warm_groups(self):
        """Compile each live group's verify and the find gather on this
        engine's mesh, by one run on an all-zero PMK matrix, where this
        process has not run them there yet.  A device stream calls it
        before its first block, so a group shape that one stream meets
        first is ready on every stream (``parallel/streams.py``)."""
        from ..parallel.step import warm_verify

        for essid in list(self.groups):
            warm_verify(self._step_for(essid), self.batch_size,
                        self.MAX_FINDS_PER_BATCH)

    def crack_batch(self, passwords) -> list:
        """One fixed-size batch of candidate byte-strings -> list[Found]."""
        prep = self._prepare(passwords)
        if prep is None:
            return []
        return self._collect(self._dispatch(prep))

    #: In-flight batches kept queued on the device ahead of the sync
    #: point.  3 = a four-deep pipeline: while batch N is fetched and
    #: decoded, N+1/N+2 are computing and N+3's H2D is in flight, so
    #: both the hits-gate round trip AND the (column-trimmed, ~2 MB)
    #: candidate upload hide behind PBKDF2 compute.  The depth was tuned
    #: on an earlier remote setup; its rationale is unmeasured on a local
    #: chip.  The extra slot costs only one more batch of at-least-once
    #: replay after a crash (see crack()).
    PIPELINE_DEPTH = 3

    def crack(self, candidates, on_batch=None) -> list:
        """Stream candidates in engine-sized batches until exhausted.

        Software pipeline (``_Pipeline``), ``PIPELINE_DEPTH + 1`` deep:
        while the device crunches batch N, the host packs and uploads
        the next ``PIPELINE_DEPTH`` batches, and the hits-gate sync
        always trails the dispatch frontier by ``PIPELINE_DEPTH``
        batches — the double-buffering SURVEY.md §7.3.3 calls for,
        deeper to also hide the device->host gate latency (see the
        PIPELINE_DEPTH comment for the measured depth choice).

        ``on_batch(consumed, founds)`` is invoked after each batch
        completes, in stream order (consumed = raw candidates in that
        batch, founds = its Found list) — the checkpoint seam the
        client's intra-unit resume hangs off (the hashcat ``--session``
        analog, help_crack.py:773).  At-least-once: up to
        ``PIPELINE_DEPTH`` dispatched-but-unreported batches replay
        after a crash.

        Multi-process contract: every host must feed the SAME NUMBER of
        same-sized batches (each host passing its local shard of a
        globally-agreed stream, as the multihost client does) — batch
        COUNT divergence would desync the shard_map collectives.  A
        host whose shard of some batch holds no valid words is safe:
        _prepare dispatches an all-padding block instead of skipping,
        keeping the slice in lockstep.
        """
        pipe = _Pipeline(self, on_batch)
        batch = []

        def submit(b):
            prep = self._prepare(b)        # async H2D starts here
            # A find in an in-flight batch is still honored for the
            # batches behind it at decode time — _collect masks rows by
            # the live-net set, so overshoot costs only the rare find
            # batch's compute.
            if prep is not None and self.groups:
                pipe.push(self._dispatch(prep), len(b))
            else:
                pipe.skip(len(b))

        for pw in candidates:
            if not self.groups and not pipe.active:
                break
            batch.append(pw)
            if len(batch) == self.batch_size:
                submit(batch)
                batch = []
        if batch:
            submit(batch)
        pipe.drain()
        return pipe.founds

    def crack_blocks(self, blocks, on_batch=None) -> list:
        """Crack a framed candidate-block stream (``dwpa_tpu.feed``).

        The feed-era twin of ``crack``: instead of slicing a flat word
        iterable itself, the engine consumes ``Block``s whose
        ``(offset, count)`` framing was fixed by the producer — so
        ``on_batch(consumed, founds)`` reports each block's GLOBAL
        candidate coverage (count, not local shard rows), which is what
        the client's resume checkpoint and the multi-host no-rules
        pass-2 both need (this replaces the ad-hoc global-count closure
        the client used to wrap around ``crack``).

        Staging is double-buffered (``feed.staging.DeviceStager``): the
        next block's candidate H2D is enqueued before this block's
        steps dispatch, and the ``_Pipeline`` trails the hits-gate sync
        ``PIPELINE_DEPTH`` batches behind — packing (producer threads),
        upload (stager) and gate latency (pipeline) all hide behind
        PBKDF2 compute.

        Multi-process contract: identical to ``crack`` — every host
        must consume the same NUMBER of blocks; the feed's sharded
        framing guarantees it (an empty local shard arrives as an
        all-padding block and still dispatches via ``_padding_prep``).
        """
        from ..feed.staging import DeviceStager

        pipe = _Pipeline(self, on_batch)
        for block, prep in DeviceStager(self, blocks):
            if not self.groups and not pipe.active:
                break
            if prep is not None and self.groups:
                pipe.push(self._dispatch(prep), block.count)
            else:
                pipe.skip(block.count)
        pipe.drain()
        return pipe.founds

    def crack_streams(self, blocks, on_batch=None, *, devices=None,
                      registry=None, tracer=None, engine_factory=None,
                      max_attempts=2) -> list:
        """Crack a framed block stream as independent device streams.

        The stream twin of ``crack_blocks`` (``parallel/streams.py``):
        instead of splitting every block 1/ndev across a lockstep
        ``shard_map`` mesh, each local device gets its own single-device
        engine and crunches WHOLE blocks pulled from a shared queue —
        no per-batch collective, no global barrier, so a straggler only
        slows its own stream.  ``on_batch(consumed, founds)`` keeps the
        ``crack_blocks`` contract exactly: one call per block, in
        global stream order, with the block's global count — resume
        framing is unchanged.  Found lists match the lockstep path's
        (ordered demux dedups by net; first block wins).

        Single-process only: a multi-host slice needs the lockstep
        global hits-gate (every host must agree a batch is finished) —
        ``parallel.streams.streams_default()`` is the switch the client
        uses.  ``engine_factory(device)`` overrides the per-stream
        engine for tests/benches; the default builds this engine's twin
        over a 1-device mesh, sharing the SAME hashline objects so a
        find on one stream prunes the net on every other.
        """
        from ..parallel.streams import StreamExecutor

        if jax.process_count() > 1:
            raise RuntimeError(
                "crack_streams is single-process only — multi-host slices "
                "keep the lockstep shard_map path (parallel/streams.py)")
        if devices is None:
            devices = list(self.mesh.devices.flat)
        lines = [n.line for n in self.nets]

        def _default_factory(device):
            from ..parallel import default_mesh

            return type(self)(
                lines, nc=self.nc, batch_size=self.batch_size,
                verify_with_oracle=self.verify_with_oracle,
                mesh=default_mesh(devices=[device]),
                pmk_store=self.pmk_store, tracer=self.tracer)

        ex = StreamExecutor(engine_factory or _default_factory, devices,
                            registry=registry, tracer=tracer,
                            max_attempts=max_attempts)
        founds = ex.run(blocks, on_batch=on_batch)
        for f in founds:
            self.remove(f)  # keep this (parent) engine's live view in sync
        return founds

    def crack_fused(self, parts, on_batch=None, max_units=8, tracer=None,
                    on_fused=None) -> list:
        """Crack several small work units as fused mixed-ESSID batches.

        ``parts``: iterable of ``(essid, words[, count])`` — one entry
        per (work unit, ESSID) pair, where ``words`` is the unit's raw
        candidate list for that ESSID and ``count`` its global coverage
        (defaults to ``len(words)``; the resume-framing analog of
        ``feed.framing.Block.count``).  Units are buffered and packed
        into full device batches (``sched.fuse.fuse_units``): up to
        ``max_units`` units per batch, flushed early when the next part
        would overflow ``batch_size`` or reuse a pending ESSID (one
        salt-table row per ESSID per batch).  Oversize parts split into
        engine-sized chunks and ride the same machinery.

        This is the small-unit throughput fix (BENCH unit_overhead):
        serially, every ~1k-word unit pads to the compiled batch width
        and pays the per-dispatch fixed costs alone; fused, eight such
        units share one batch and one set of round trips.

        ``on_batch(essid, consumed, founds)`` fires per PART in stream
        order — same at-least-once checkpoint seam as ``crack_blocks``,
        keyed by ESSID so a multi-unit caller can demux.  ``on_fused``
        (optional) receives each ``FusedBatch`` before dispatch — the
        executor's fill/units-per-batch metrics hook.  ``tracer``
        (optional ``obs.trace.SpanTracer``) wraps packing in
        ``sched:fuse`` and sync/demux in ``sched:demux`` spans.

        Single-process only: fusion exists to fill ONE small slice from
        a thin work-unit stream; a multi-host slice implies work units
        big enough to saturate it, and the lockstep block contract
        (every host, same batch count) would make partial waves hang.
        """
        import collections
        from contextlib import nullcontext
        from ..sched.fuse import fuse_units

        if jax.process_count() > 1:
            raise RuntimeError(
                "crack_fused is single-process only (multi-host slices "
                "take the crack_blocks path; see the method docstring)")

        pipe_founds = []
        inflight = collections.deque()  # (fb, outs, wb), oldest first
        pending = []                    # buffered (essid, words, count)
        raw = 0                         # candidate estimate of pending

        def finish_one():
            fb, outs, wb = inflight.popleft()
            pipe_founds.extend(
                self._collect_fused(fb, outs, wb, on_batch, tracer))

        def flush():
            nonlocal pending, raw
            if not pending:
                return
            parts_now, pending, raw = pending, [], 0
            with (tracer.span("sched:fuse") if tracer else nullcontext()):
                fb = fuse_units(parts_now, self.batch_size, self.mesh.size,
                                max_units, store=self.pmk_store,
                                salts=self._salts)
            if on_fused is not None:
                on_fused(fb)
            if fb.total == 0:
                # Every candidate was invalid: nothing to dispatch, but
                # the units' coverage must still reach the checkpoint.
                if on_batch is not None:
                    for u in fb.units:
                        on_batch(u.key, u.count, [])
                return
            inflight.append(self._dispatch_fused(fb))
            if len(inflight) > self.PIPELINE_DEPTH:
                finish_one()

        for part in parts:
            key, words = part[0], list(part[1])
            count = part[2] if len(part) > 2 else len(words)
            if not self.groups and not inflight:
                break  # everything cracked; stop consuming the stream
            if key not in self.groups:
                # Unit for an already-cracked (or unknown) ESSID: consume
                # it so the caller's checkpoint advances past it.
                if on_batch is not None:
                    on_batch(key, count, [])
                continue
            # Oversize unit: split into engine-sized chunks; each chunk
            # fuses (alone — a full chunk flushes whatever is pending).
            while len(words) > self.batch_size:
                chunk, words = words[:self.batch_size], words[self.batch_size:]
                count -= len(chunk)
                flush()
                pending, raw = [(key, chunk, len(chunk))], len(chunk)
                flush()
            if (raw + len(words) > self.batch_size
                    or any(k == key for k, _, _ in pending)
                    or len(pending) >= max_units):
                flush()
            pending.append((key, words, count))
            raw += len(words)
        flush()
        while inflight:
            finish_one()
        return pipe_founds

    @_staged("dispatch")
    def _dispatch_fused(self, fb):
        """Launch one fused batch (no host sync): ONE per-lane-salt
        PBKDF2 over the compacted miss lanes (``fused_pmk_step`` — the
        unit_id gather resolves each lane's salt on device), the mixed
        ``mix_step`` gather when the PMK store contributed hits, then
        every live unit's verify kernels over the SAME [8, W] PMK
        matrix.  A unit's verify sees other units' lanes too — their
        PMKs were derived under a different ESSID, so they cannot match
        (and ``_collect_fused`` masks the columns anyway)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel import shard_candidates
        from ..parallel.mesh import DP_AXIS, shard_vector
        from ..parallel.step import fused_pmk_step, mix_step

        pmk_sharding = getattr(self, "_pmk_sharding", None)
        if pmk_sharding is None:
            pmk_sharding = self._pmk_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, DP_AXIS))
        wb = None
        if fb.nmiss == 0 and fb.cached is not None:
            # Every lane was a store hit: zero PBKDF2 dispatched.
            pmk = jax.device_put(fb.cached, pmk_sharding)
        else:
            w = _trim_cols(int(fb.miss_lens.max()) if fb.nmiss
                           else MIN_PSK_LEN)
            rows_dev = shard_candidates(
                self.mesh, np.ascontiguousarray(fb.miss_rows[:, :w]))
            uid_dev = shard_vector(self.mesh, fb.unit_id)
            repl = NamedSharding(self.mesh, PartitionSpec())
            t1 = jax.device_put(fb.table1, repl)
            t2 = jax.device_put(fb.table2, repl)
            pmk_miss = fused_pmk_step(self.mesh)(rows_dev, uid_dev, t1, t2)
            entries = [(u.key, u.mlo, u.nmiss, u.miss_words)
                       for u in fb.units if u.nmiss]
            wb = (pmk_miss, entries)
            pmk = (pmk_miss if fb.idx is None else
                   mix_step(self.mesh)(pmk_miss, fb.cached, fb.idx))
        outs = []
        for u in fb.units:
            group = self._full.get(u.key)
            if group is None:  # group cracked out from under the stream
                outs.append((u, None, None))
                continue
            outs.append((u, group, self._step_for(u.key).verify(pmk)))
        return fb, outs, wb

    @_staged("collect")
    def _collect_fused(self, fb, outs, wb, on_batch, tracer) -> list:
        """Sync + demux one fused batch: gate each unit's verify on its
        hit scalar, mask the found matrix down to the unit's OWN lane
        window ``[lo, lo + nvalid)`` before decode (a hit in unit A must
        never surface as unit B's find — the columns outside the window
        belong to other units), prune cracked nets, write new PMKs back
        to the store, and fire ``on_batch`` per unit in layout order."""
        from contextlib import nullcontext

        founds = []
        by_unit = {id(u): [] for u, _, _ in outs}
        live = {id(n.line) for g in self.groups.values() for n in g}
        with (tracer.span("sched:demux") if tracer else nullcontext()):
            real = [(u, g, out) for u, g, out in outs if out is not None]
            fetched = None
            payload = sum(int(a.nbytes) for _, _, out in real
                          for a in out[1:])
            if real and payload <= self.SMALL_FETCH_BYTES:
                # One merged round trip for every unit's (hits, find
                # data) — fused batches exist to amortize exactly this
                # fixed cost (see SMALL_FETCH_BYTES).
                fetched = jax.device_get([out for _, _, out in real])
            for i, (u, group, out) in enumerate(real):
                if fetched is not None:
                    out = fetched[i]
                if int(np.asarray(out[0])) == 0:
                    continue
                hits, found_dev, pmk_dev = out
                found, pmk_host = jax.device_get((found_dev, pmk_dev))
                found = np.array(found)
                # Demux mask: zero every column outside this unit's lane
                # window (other units' candidates + padding).
                found[:, :, :u.lo] = False
                found[:, :, u.lo + u.nvalid:] = False
                new = self._decode(group, found,
                                   lambda b: pmk_host[:, b],
                                   _ShiftedWords(u.words, u.lo), None, live)
                by_unit[id(u)].extend(new)
                founds.extend(new)
            for f in founds:
                self.remove(f)
            if wb is not None and self.pmk_store is not None:
                # Store write-back (consumer thread, post-fetch — lint
                # rule DW108): each unit's slice of the fused miss PMK
                # matrix lands under its own ESSID.
                pmk_miss, entries = wb
                pmk_host = jax.device_get(pmk_miss)
                for key, mlo, nm, miss_words in entries:
                    self.pmk_store.put(key, miss_words,
                                       pmk_host[:, mlo:mlo + nm])
        if on_batch is not None:
            for u in fb.units:
                on_batch(u.key, u.count, by_unit[id(u)])
        return founds

    def _rules_flush(self, ctx, batch, account, gbatch, nproc, pid,
                     push, skip):
        """One base-word flush through the device-expansion seam.

        The shared body of every rules dispatch path (serial
        ``crack_rules``, block-framed ``crack_rules_blocks``, and the
        per-device stream adapter ``_RulesStreamEngine``): split the
        batch into device-eligible bases vs host-fallback words, plan
        the fused rule chunks (``simulate_lens`` overflow routing +
        per-chunk resume accounting), pack and upload the base block
        ONCE, dispatch every chunk, then host-expand the fallback tail
        through the normal packed path.  ``batch`` is either a raw word
        list or a warm ``feed.framing.RulesPrep`` (pre-split,
        pre-packed bases — the dict cache's base-block layout), in
        which case both the split and the pack are skipped.
        ``push(record, report)`` / ``skip(report)`` receive the
        dispatched sub-batches in stream order; ``account(consumed)``
        owns the caller's resume window.
        """
        from ..native import pack_candidates_fast
        from ..parallel import shard_candidates
        from ..parallel.mesh import shard_vector
        from ..parallel.step import RULES_CHUNK
        from ..rules.device import simulate_lens, stack_rules

        rules = ctx.rules
        dev_rules, host_rules = ctx.dev_rules, ctx.host_rules
        base_dev = lens_dev = None
        cap = 0
        with ctx.span("rules:expand"):
            if hasattr(batch, "rules_base"):
                # Warm base block: the fallback split and the pack
                # already ran (and were cached); bases stay in packed
                # device layout, words materialize lazily on hits.
                pre = batch
                nplain = pre.nplain
                plain = _BaseWords(pre.rows, pre.lens, nplain)
                lens_np = np.asarray(pre.lens[:nplain], dtype=np.int32)
                fallback = [(w, None) for w in pre.fallback]
            else:
                plain, fallback = [], []
                for w in batch:
                    # Host-fallback words: overlong bases, and anything
                    # that could put "$HEX[...]" syntax in front of the
                    # engine's unhex stage (the host paths unhex AFTER
                    # rule application, so the device must not hash such
                    # words literally).  The substring check also catches
                    # bases a rule could extend into a valid wrapper;
                    # synthesizing "HEX[" itself from unrelated
                    # characters via chained inserts remains a
                    # documented, pathological divergence.
                    if len(w) > MAX_PSK_LEN or b"HEX[" in w:
                        fallback.append((w, None))  # None = every rule
                    else:
                        plain.append(w)
                pre = None
                nplain = len(plain)
                lens_np = None
            plan = []  # (chunk, expanded pairs, candidates to report)
            if nplain and self.groups and dev_rules:
                # Per-chunk accounting and host-overflow routing run
                # BEFORE any device work: a resume window covering the
                # whole batch must not pay the H2D upload, and the
                # overflow pairs belong to the host tail regardless.
                # ``consumed`` excludes the overflow pairs deferred to
                # the host tail — each candidate is counted exactly
                # once, or skip-by-count resume would overshoot.
                if lens_np is None:
                    lens_np = np.asarray([len(w) for w in plain], np.int32)
                for c0 in range(0, len(dev_rules), RULES_CHUNK):
                    chunk = dev_rules[c0:c0 + RULES_CHUNK]
                    overflow = 0
                    for rule, _steps in chunk:
                        _, hostneed = simulate_lens(rule, lens_np)
                        if hostneed.any():
                            pairs = [(plain[i], rule)
                                     for i in np.flatnonzero(hostneed)]
                            fallback.extend(pairs)
                            overflow += len(pairs)
                    expanded = nplain * len(chunk) - overflow
                    plan.append((chunk, expanded, account(expanded)))
            if any(rep for _, _, rep in plan):
                with self._stage("prepare"):
                    # Pad to the engine batch size like _prepare: a distinct
                    # cap per partial batch would mean a fresh multi-second
                    # XLA compile of the fused step per distinct count.
                    cap = max(gbatch,
                              -(-nplain // self.mesh.size) * self.mesh.size)
                    if pre is not None:
                        rows = pre.padded_rows(cap)
                    else:
                        packed = pack_candidates_fast(plain, 0, MAX_PSK_LEN, cap)
                        if packed is None:  # no native lib: plain Python pack
                            rows = np.zeros((cap, 16), np.uint32)
                            rows[:nplain] = bo.pack_passwords_be(plain)
                        else:
                            rows, _, n = packed  # lens_np above is the source
                            assert n == nplain  # min_len=0: no compaction
                    lens_pad = np.zeros(cap, np.int32)
                    lens_pad[:nplain] = lens_np
                    # Every host packed the identical global batch; ship only
                    # this host's row slice (shard_* assemble the global
                    # array from per-process slices on a multi-process mesh).
                    lo, hi = pid * (cap // nproc), (pid + 1) * (cap // nproc)
                    base_dev = shard_candidates(self.mesh, rows[lo:hi])
                    lens_dev = shard_vector(self.mesh, lens_pad[lo:hi])
        if base_dev is not None:
            # Chunked fused dispatch: each chunk of RULES_CHUNK rules
            # runs expand+PBKDF2+verify in ONE device call per group
            # with ONE hits-gate (see parallel/step.py
            # build_rules_step).
            for chunk, expanded, report in plan:
                if not self.groups:
                    break
                if report == 0:
                    continue  # chunk wholly inside the resume prefix
                stack = stack_rules([s for _, s in chunk], RULES_CHUNK,
                                    ctx.n_steps)
                pws = [_RuleWords(plain, r) for r, _ in chunk]
                pws += [None] * (RULES_CHUNK - len(chunk))
                outs = []
                with self._stage("dispatch"):
                    for essid in list(self.groups):
                        step = self._rules_step_for(essid)
                        outs.append(
                            (self._full[essid],
                             step(base_dev, lens_dev, stack)))
                ctx.m_device.inc(expanded)
                push((pws, nplain, outs, cap // self.mesh.size), report)
        # Host-expanded tail: unsupported rules over plain words,
        # plus the per-(word, rule) fallbacks collected above.
        # ``consumed`` counts attempted (word, rule) pairs — rejects
        # included, mirroring how the device chunks count them.
        out = []
        pairs_pending = 0

        def submit_host(cands, consumed):
            report = account(consumed)
            if report == 0:
                return  # batch wholly inside the resume prefix
            if nproc > 1:
                # The tail stream is the identical global expansion
                # on every host; each host dispatches its contiguous
                # 1/nproc block (an empty block still dispatches
                # padding via _prepare, keeping SPMD lockstep).
                blk = -(-len(cands) // nproc)
                cands = cands[pid * blk:(pid + 1) * blk]
            prep = self._prepare(cands)
            if prep is not None and self.groups:
                push(self._dispatch(prep), report)
            else:
                skip(report)

        def tail(w, rr):
            nonlocal out, pairs_pending
            pairs_pending += 1
            o = rr.apply(w)
            if o is not None:
                out.append(o)
                if len(out) >= gbatch:
                    submit_host(out, pairs_pending)
                    out, pairs_pending = [], 0

        for w, r in fallback:
            ctx.m_overflow.inc(len(rules) if r is None else 1)
            for rr in (rules if r is None else [r]):
                tail(w, rr)
        if host_rules and nplain:
            ctx.m_purge.inc(nplain * len(host_rules))
            for w in plain:
                for rr in host_rules:
                    tail(w, rr)
        if out or pairs_pending:
            submit_host(out, pairs_pending)

    def crack_rules(self, words, rules, on_batch=None, skip: int = 0, *,
                    registry=None, tracer=None) -> list:
        """Rules attack with ON-DEVICE mangling (rules/device.py).

        The host uploads each base batch ONCE (packed + lengths) and
        every device-eligible rule mangles it on device — candidate H2D
        drops by the rule count (hashcat runs its rule engine on the
        GPU for the same reason: host expansion can't feed a mesh).
        Per base batch:

        - words a rule can't cover on device ($HEX/overlong bases, the
          rare length-overflow (word, rule) pairs flagged by
          ``simulate_lens``, rules with unsupported ops) are expanded
          by the host interpreter and fed through the normal packed
          path — same pipeline, same stream;
        - hit columns decode by applying the HOST rule to the base word
          (``_RuleWords``), so the device transform is never trusted
          for results; with ``verify_with_oracle`` every find is
          re-checked against the executable spec.

        ``on_batch(consumed, founds)`` fires per dispatched batch with
        ``consumed`` = candidates that batch covered (a fused chunk
        covers base-words x chunk-rules at once).  Stream order is
        fixed (base-batch major, then device rule chunks in order, then
        the batch's host-expanded tail), so skip-by-count resume works
        like ``crack``.

        Multi-process contract — UNLIKE ``crack``'s local-shard feed:
        every host passes the SAME global word stream and the same
        ``skip`` (hosts hold full dict copies anyway — the reference's
        volunteers each download whole dictionaries, get_work.php).
        Each host then packs the global batch but uploads only its
        1/nproc row slice, and the find decode replicates the bit-packed
        mask so every host re-derives identical founds from the global
        column index — the mask path's global-indexing trick
        (``_LazyWords``), with no candidate exchange.  Host-expanded
        tails slice the identical global tail per host, so dispatch
        counts stay in SPMD lockstep with zero extra collectives.

        ``skip``: resume fast-forward — the first ``skip`` candidates
        of the (deterministic) stream are not re-reported.  Sub-batches
        wholly inside the window are not dispatched at all; a sub-batch
        straddling the boundary is re-dispatched in full (at-least-once,
        like ``crack``'s in-flight replay) but reports only its
        unskipped remainder, so the caller's cumulative count stays
        exact.  The client's intra-unit resume hangs off this — pass-2
        candidates never exist host-side, so it cannot islice() them
        the way pass 1 does (help_crack.py:737-763 restart contract).
        """
        nproc = jax.process_count()
        pid = jax.process_index()
        #: global words per flush: each host uploads a batch_size slice
        gbatch = self.batch_size * nproc

        ctx = _RulesCtx(rules, registry=registry, tracer=tracer)
        pipe = _Pipeline(self, on_batch)
        skip_left = int(skip)

        def account(consumed: int) -> int:
            """Consume up to ``consumed`` from the resume window; returns
            how many candidates this sub-batch must REPORT (0 = wholly
            inside the completed prefix: don't dispatch)."""
            nonlocal skip_left
            take = min(skip_left, consumed)
            skip_left -= take
            return consumed - take

        def flush(batch):
            self._rules_flush(ctx, batch, account, gbatch, nproc, pid,
                              pipe.push, pipe.skip)

        batch = []
        for w in words:
            if not self.groups and not pipe.active:
                break
            batch.append(w)
            # Flush at the GLOBAL batch size: each flush pads the packed
            # rows to gbatch and every host uploads a 1/nproc slice, so
            # slicing the stream at batch_size would leave every host
            # beyond the first shipping pure zero padding (N-host rules
            # attacks at 1-host throughput).
            if len(batch) == gbatch:
                flush(batch)
                batch = []
        if batch and (self.groups or pipe.active):
            flush(batch)
        pipe.drain()
        return pipe.founds

    def crack_rules_blocks(self, blocks, rules, on_batch=None,
                           skip: int = 0, *, registry=None,
                           tracer=None) -> list:
        """Rules attack over a framed base-word block stream.

        The block-framed twin of ``crack_rules``: the feed hands
        ``Block``s of BASE words (cold: raw word lists; warm: the dict
        cache's pre-packed ``RulesPrep`` base layout) and every block
        expands on device through the shared ``_rules_flush`` seam, so
        the serial block path, the stream path and the flat-iterable
        path are ONE dispatch regime.  ``on_batch(consumed, founds)``
        fires once per BLOCK in stream order, where ``consumed`` counts
        EXPANDED (word x rule) candidates — the resume domain.  The
        expansion stream is bit-identical to ``crack_rules`` over the
        same words when blocks are framed at ``batch_size x
        process_count`` words (``feed.framing.frame_blocks``), so skip
        offsets are interchangeable between the two entry points.

        ``skip`` counts expanded candidates.  A block wholly inside the
        resume window is dropped in O(1) — its coverage is exactly
        ``count x len(rules)`` because the seam counts every (word,
        rule) pair exactly once (device chunks + host tail, rejects
        included) — without packing or device work; the straddling
        block replays at-least-once and reports only its remainder,
        exactly like ``crack_rules``'s sub-batch accounting.

        Multi-process: pass GLOBAL blocks (every host the same stream),
        the ``crack_rules`` contract.
        """
        ctx = _RulesCtx(rules, registry=registry, tracer=tracer)
        nproc = jax.process_count()
        pid = jax.process_index()
        gbatch = self.batch_size * nproc
        agg = _BlockAgg(on_batch)
        pipe = _Pipeline(self, agg.record)
        skip_left = int(skip)

        def account(consumed: int) -> int:
            nonlocal skip_left
            take = min(skip_left, consumed)
            skip_left -= take
            return consumed - take

        def push(rec, report):
            agg.emit()
            pipe.push(rec, report)

        def skipf(report):
            agg.emit()
            pipe.skip(report)

        for block in blocks:
            if not self.groups and not pipe.active:
                break
            exp = block.count * ctx.n_rules
            if skip_left >= exp:
                # O(1) whole-block drop: the expanded-count invariant
                # makes the block's total coverage count x n_rules
                # without splitting, packing or expanding it.
                skip_left -= exp
                continue
            prep = getattr(block, "prep", None)
            batch = prep if hasattr(prep, "rules_base") else block.words
            agg.begin()
            self._rules_flush(ctx, batch, account, gbatch, nproc, pid,
                              push, skipf)
            agg.close()
        pipe.drain()
        return pipe.founds

    def crack_rules_streams(self, blocks, rules, on_batch=None,
                            skip: int = 0, *, devices=None, registry=None,
                            tracer=None, engine_factory=None,
                            max_attempts=2) -> list:
        """Rules attack as independent per-device streams.

        The stream twin of ``crack_rules_blocks`` (and the rules analog
        of ``crack_streams``): each local device gets its own
        single-device engine wrapped in the rules seam adapter
        (``_RulesStreamEngine``) and pulls WHOLE base blocks from the
        shared queue, expanding rules directly ahead of its own PBKDF2
        dispatch — the host ships compact base blocks only (candidate
        H2D divided by the rule count), there is no cross-device
        candidate traffic, and a straggler or crash affects only its
        own stream (requeue comes free from ``StreamExecutor``).
        ``on_batch(consumed, founds)`` fires once per base block in
        global stream order with the block's EXPANDED coverage —
        identical framing to ``crack_rules_blocks``, so resume offsets
        interop across all three rules entry points.  Blocks wholly
        inside ``skip`` are dropped before they reach the queue (O(1)
        per block); the straddler carries its in-block expanded skip
        immutably, so a crash requeue replays it deterministically.

        Single-process only (``crack_streams``'s contract).
        ``engine_factory(device)`` overrides the per-stream INNER
        engine (the seam adapter still wraps it) for tests/benches.
        """
        from ..parallel.streams import StreamExecutor

        if jax.process_count() > 1:
            raise RuntimeError(
                "crack_rules_streams is single-process only — multi-host "
                "slices keep the lockstep crack_rules path")
        ctx = _RulesCtx(rules, registry=registry, tracer=tracer)
        if devices is None:
            devices = list(self.mesh.devices.flat)
        lines = [n.line for n in self.nets]

        def _default_factory(device):
            from ..parallel import default_mesh

            return type(self)(
                lines, nc=self.nc, batch_size=self.batch_size,
                verify_with_oracle=self.verify_with_oracle,
                mesh=default_mesh(devices=[device]),
                pmk_store=self.pmk_store, tracer=self.tracer)

        inner = engine_factory or _default_factory

        def factory(device):
            return _RulesStreamEngine(inner(device), ctx)

        def wrapped():
            pos, skip_left = 0, int(skip)
            for block in blocks:
                exp = block.count * ctx.n_rules
                if skip_left >= exp:
                    skip_left -= exp
                    pos += exp
                    continue
                prep = getattr(block, "prep", None)
                base = prep if hasattr(prep, "rules_base") else block.words
                yield _RulesBlock(pos + skip_left, exp - skip_left,
                                  base, skip_left)
                pos += exp
                skip_left = 0

        ex = StreamExecutor(factory, devices, registry=registry,
                            tracer=tracer, max_attempts=max_attempts)
        founds = ex.run(wrapped(), on_batch=on_batch)
        for f in founds:
            self.remove(f)  # keep this (parent) engine's live view in sync
        return founds

    def crack_mask(self, mask: str, skip: int = 0, limit: int = None,
                   custom: dict = None, on_batch=None) -> list:
        """Mask attack with on-device candidate generation.

        Unlike ``crack``, no candidate bytes ever exist host-side: each
        batch is generated by ``gen.mask.device_mask_words`` (SURVEY §7
        M5 — iota→digits→pack, one fused program) and fed straight to
        the crack steps, so the only host work per batch is an
        O(positions) digit vector and the hits-gate scalar.  Words are
        materialized lazily from their keyspace index only for the rare
        hit columns.  ``skip``/``limit`` slice the keyspace exactly like
        ``gen.mask.mask_words`` (hashcat -s/-l semantics).

        Since the mesh-aggregate refactor this is a thin front over
        ``crack_blocks`` with ``gen.mask.mask_blocks``'s ``MaskPrep``
        stream — generation happens in ``_prepare_block`` under this
        engine's mesh sharding, so the SAME block stream also schedules
        through ``crack_streams`` (each device stream generates its own
        keyspace slices) or the multi-unit executor.
        """
        from ..gen.mask import mask_blocks

        return self.crack_blocks(
            mask_blocks(mask, self.batch_size, skip=skip, limit=limit,
                        custom=custom),
            on_batch=on_batch)


class _RulesBlock:
    """Work item for the per-device rules streams: a base-word block in
    EXPANDED (word x rule) coordinates.

    ``offset``/``count`` frame the block's expanded remainder in the
    global candidate stream (``StreamExecutor`` orders on_batch demux by
    them and reports ``count`` as the consumed amount — identical to
    ``crack_rules_blocks`` framing).  ``base`` is the raw base-word list
    or a warm ``RulesPrep``; ``skip_pairs`` is the immutable in-block
    expanded resume offset — immutable so a crash requeue replays the
    straddling block deterministically on the surviving stream.
    """

    __slots__ = ("offset", "count", "base", "skip_pairs")

    def __init__(self, offset, count, base, skip_pairs=0):
        self.offset = offset
        self.count = count
        self.base = base
        self.skip_pairs = skip_pairs


class _RulesStreamEngine:
    """Adapter giving a single-device engine the block protocol
    ``parallel.streams.DeviceStream`` drives, with rules expansion done
    ON this stream's device via the shared ``_rules_flush`` seam.

    ``_prepare_block`` runs the whole seam for the block (split, pack,
    per-chunk fused dispatch, host tail) and buffers the dispatched
    records; ``_dispatch`` is the identity (device work was issued
    during prepare — the stream still overlaps blocks because results
    are only BLOCKED on in ``_collect``, ``PIPELINE_DEPTH`` blocks
    later).  ``_collect`` drains the block's records in order through
    the inner engine's normal decode path.
    """

    def __init__(self, inner, ctx):
        self.inner = inner
        self.ctx = ctx
        self.PIPELINE_DEPTH = inner.PIPELINE_DEPTH

    @property
    def groups(self):
        return self.inner.groups

    @property
    def nets(self):
        return self.inner.nets

    def remove(self, found):
        self.inner.remove(found)

    def warm_groups(self):
        """Nothing: a rules step is keyed on the unit's own net
        partition and compiles at its first chunk, as the client's
        ``prewarm`` says."""

    def _prepare_block(self, block):
        eng = self.inner
        recs = []
        skip_left = block.skip_pairs

        def account(consumed):
            nonlocal skip_left
            take = min(skip_left, consumed)
            skip_left -= take
            return consumed - take

        eng._rules_flush(self.ctx, block.base, account, eng.batch_size,
                         1, 0, lambda rec, rep: recs.append(rec),
                         lambda rep: None)
        return recs

    def _dispatch(self, recs):
        return recs

    def _collect(self, recs):
        founds = []
        for rec in recs:
            founds.extend(self.inner._collect(rec))
        return founds
