"""The sharded full crack step: PBKDF2 -> verify, shard_map'd over the mesh.

``build_crack_step`` returns one callable that runs the complete pipeline
for a candidate batch:

- the [B, 16] packed-password batch is split over the "dp" mesh axis;
- each device runs PBKDF2(4096) + every net's MIC/PMKID check on its local
  candidate shard — no communication at all in the hot loop;
- the only collective is a ``psum`` of the scalar hit count over ICI, used
  by the host as a cheap "anything found?" gate before it pulls the
  (dp-sharded) per-net match matrix back for the rare positives.

Compilation strategy (the part that matters operationally): a reference
work unit is one ESSID group (all nets sharing the target's SSID,
web/content/get_work.php:96-109), so a design that bakes the group's
constants into the trace pays a full XLA compile (~tens of seconds on
TPU) for every new work unit.  Here nothing net-specific is baked:

- the PBKDF2 step takes the ESSID salt blocks as *data* — one compile
  per batch size serves every ESSID ever cracked;
- the verify steps take the nets' constants as stacked arrays and
  ``lax.map`` over the net axis (one net's working set at a time, so
  a multi-BSSID group fits HBM at full batch), cached per shape signature
  ``(kind, keyver, V variants, E eapol blocks)`` with the net count
  padded up to a power-of-two bucket — a handful of compilations for a
  server's whole lifetime, all shared across groups, engines and work
  units.

This is the TPU mapping of the reference's work distribution (volunteer
data parallelism + ESSID-amortized PBKDF2) described in SURVEY.md §5.7.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import m22000 as m
from .mesh import DP_AXIS

#: (mesh, kind, *static) -> jitted sharded step, shared process-wide.
_STEP_CACHE = {}


def _shard(mesh, fn, in_specs, out_specs):
    # check_vma=False: the rolled compressions seed their fori_loop
    # carries from unsharded per-net constants, which fails JAX's
    # varying-manual-axes check even though every carry is elementwise over
    # the dp-sharded batch (each device runs the identical replicated
    # constants against its own candidate shard, so replication is trivially
    # consistent).
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )


def pmk_step(mesh):
    """jitted ``(pw_words[B,16], salt1[16], salt2[16]) -> pmk uint32[8, B]``.

    Salts are data, so one compile per batch size serves every ESSID.
    """
    key = (mesh, "pmk")
    if key not in _STEP_CACHE:
        use_pallas = all(d.platform == "tpu" for d in mesh.devices.flat)

        def local(pw_words, s1, s2):
            return m._pmk_impl(pw_words, s1, s2, use_pallas=use_pallas)

        _STEP_CACHE[key] = _shard(
            mesh, local, (P(DP_AXIS, None), P(), P()), P(None, DP_AXIS)
        )
    return _STEP_CACHE[key]


def fused_pmk_step(mesh):
    """jitted ``(pw_words[B,16], unit_id[B], table1[U,16], table2[U,16])
    -> pmk uint32[8, B]`` — the mixed-ESSID fused PBKDF2 step.

    Each lane gathers its OWN salt blocks from the replicated per-unit
    tables (``table[unit_id]``, a device-side [b, 16] gather on the
    local shard) and the per-lane-salt PBKDF2 kernel runs unchanged —
    the H2D cost of mixing ESSIDs in one batch is 4 bytes/lane of
    ``unit_id``, not 128 bytes/lane of salt blocks.  Everything is
    data: one compile serves every unit combination ever fused, keyed
    only on the (bounded) lane-width/table-shape signature — callers
    pad ``B`` to the static fused-width table (``sched.fuse``, lint
    rule DW109) and ``U`` to the fixed ``fuse_max_units`` bucket
    (repeat row 0), so the jit cache stays a handful of entries.
    """
    key = (mesh, "pmk_fused")
    if key not in _STEP_CACHE:
        use_pallas = all(d.platform == "tpu" for d in mesh.devices.flat)

        def local(pw_words, unit_id, t1, t2):
            return m._pmk_impl(pw_words, t1[unit_id], t2[unit_id],
                               use_pallas=use_pallas)

        _STEP_CACHE[key] = _shard(
            mesh, local,
            (P(DP_AXIS, None), P(DP_AXIS), P(), P()),
            P(None, DP_AXIS),
        )
    return _STEP_CACHE[key]


def _gate(found, mask):
    """found bool[N, V, b], mask bool[N] -> replicated exact hit count.

    The mask (data, so it never retriggers a trace) zeroes the bucket-pad
    rows out of both the count and the returned matrix, keeping ``hits``
    an exact match count and pad rows all-False for consumers.
    """
    found = found & mask[:, None, None]
    return jax.lax.psum(jnp.sum(found, dtype=jnp.int32), DP_AXIS), found


# One descriptor per verify code path — the single place that ties
# together (a) the static trace parameters extracted from a net, (b) the
# PreppedNet fields shipped to the device, and (c) the per-net match
# function.  _partition, build_crack_step and verify_step all read this
# table, so a new keyver is one new row, not three hand-synced switches.
# Each match fn: (pmk[8,b], static tuple, *per-net consts) -> bool[V, b].
_KINDS = {
    "pmkid": (
        lambda net: (),
        ("pmkid_block", "target"),
        lambda pmk, st, blk, tgt: m._pmkid_impl(pmk, blk, tgt)[None],
    ),
    "eapol": (
        lambda net: (net.keyver,),
        ("prf_blocks", "eapol_blocks", "target"),
        lambda pmk, st, prf, eap, tgt: m.eapol_match(
            pmk, prf, eap, tgt, keyver=st[0]
        ),
    ),
    "cmac": (
        lambda net: (bool(net.cmac_last_complete),),
        ("prf_blocks", "cmac_full", "cmac_last", "cmac_target"),
        lambda pmk, st, prf, full, last, tgt: m.eapol_cmac_match(
            pmk, prf, full, last, tgt, last_complete=st[0]
        ),
    ),
}


def _kind_of(net) -> str:
    if net.keyver == 100:
        return "pmkid"
    return "cmac" if net.keyver == 3 else "eapol"


def verify_step(mesh, kind, static):
    """jitted ``(pmk[8,B], mask[N], *stacked consts) -> (hits, found[N,V,B])``.

    ``kind``/``static`` select the code path; array shapes (net-count
    bucket, variant count, EAPOL blocks, batch) key jit's own cache.
    """
    key = (mesh, kind, static)
    if key not in _STEP_CACHE:
        _, fields, match = _KINDS[kind]

        def local(pmk, mask, *consts):
            fnd = jax.lax.map(lambda cs: match(pmk, static, *cs), consts)
            return _gate(fnd, mask)

        _STEP_CACHE[key] = _shard(
            mesh,
            local,
            (P(None, DP_AXIS), P()) + (P(),) * len(fields),
            (P(), P(None, None, DP_AXIS)),
        )
    return _STEP_CACHE[key]


def _bucket(n: int) -> int:
    """Pad net counts to powers of two so jit's shape cache hits across
    groups of nearby sizes."""
    b = 1
    while b < n:
        b *= 2
    return b


def _pad_nets(arrs):
    """Stack per-net arrays and pad the net axis to its bucket by
    repeating the last row.  Pad rows are dead weight whose hits the
    verify step's mask (see ``_gate``) excludes from both the count and
    the matrix; callers additionally slice found[:n]."""
    stacked = np.stack(arrs)
    pad = _bucket(len(arrs)) - len(arrs)
    if pad:
        stacked = np.concatenate([stacked, np.repeat(stacked[-1:], pad, axis=0)])
    return stacked


def _partition(nets):
    """Group net indices by verify-step signature (kind, static params,
    device-const shapes — everything that keys a distinct compilation)."""
    parts = {}
    for i, net in enumerate(nets):
        kind = _kind_of(net)
        statics, fields, _ = _KINDS[kind]
        sig = (kind, statics(net),
               tuple(getattr(net, f).shape for f in fields))
        parts.setdefault(sig, []).append(i)
    return parts


def _assemble_step(mesh, struct, v_max, inv):
    """jitted ``(*found parts) -> found[N, v_max, B]``: slice off bucket
    padding, zero-pad variant axes, concatenate, restore input order.
    Cached per part structure so the whole assembly stays one fused XLA
    program instead of a chain of eager device ops per batch."""
    key = (mesh, "asm", struct, v_max, None if inv is None else tuple(inv))
    if key not in _STEP_CACHE:

        def assemble(*fnds):
            rows = []
            for fnd, (n, v) in zip(fnds, struct):
                fnd = fnd[:n]
                if v < v_max:
                    fnd = jnp.pad(fnd, ((0, 0), (0, v_max - v), (0, 0)))
                rows.append(fnd)
            found = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
            return found if inv is None else found[np.asarray(inv)]

        _STEP_CACHE[key] = jax.jit(assemble)
    return _STEP_CACHE[key]


def mix_step(mesh):
    """jitted ``(pmk_miss[8, Mb], cached[8, B], idx[B]) -> pmk uint32[8, B]``.

    The PMK-store mixed-block assembly: ``pmk_miss`` is the PBKDF2 output
    of the compacted miss sub-batch, ``cached`` the host-built matrix
    with cache-hit PMKs at their batch columns, and ``idx`` the gather
    map over ``concat([pmk_miss, cached], axis=1)`` (misses read their
    computed slot, hits and padding read ``cached`` at their own column
    — ``pmkstore.stage.split_block`` builds it).  ``idx`` is data, never
    a trace constant; one jit object per mesh, so XLA recompiles only
    per ``(Mb, B)`` shape pair — and the miss widths are bucketed
    (``pmkstore.stage.miss_widths``, <= 3 values) precisely so that
    count stays bounded however the hit ratio wanders.
    """
    key = (mesh, "mix")
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = jax.jit(
            lambda pm, cached, idx: jnp.concatenate(
                [pm, cached], axis=1)[:, idx],
            out_shardings=NamedSharding(mesh, P(None, DP_AXIS)),
        )
    return _STEP_CACHE[key]


#: Rules per fused dispatch (build_rules_step).  Fixed so the step's jit
#: signature is independent of the ruleset size: a 134-line set runs in
#: ceil(134/8) dispatches, the last padded with noop rules (<= 1 chunk
#: of waste per base batch) — vs a multi-second XLA compile per distinct
#: ruleset size.
RULES_CHUNK = 8


def build_rules_step(mesh, nets, salt1, salt2):
    """The fused rules crack step: expand + PBKDF2 + verify, one dispatch.

    Returns ``step(base[B,16], lens[B], steps[RULES_CHUNK,S,3]) ->
    (hits, foundbits[R, B/32])``: each rule of the chunk mangles the
    base batch ON DEVICE (rules/device.expand_traced) and feeds PBKDF2
    + every net's verify, with ONE psum'd hit scalar gating the whole
    chunk.  Fusion keeps expansion off the feed path and saves a
    dispatch per rule (hashcat's GPU rule engine exists for the same
    reason); the per-dispatch cost that motivated it was measured on an
    earlier remote setup and is unmeasured on a local chip.

    Each net, and each NC variant inside a net, is verified in turn
    (``lax.map``), not all at once: materialising ``[N, V, B]``
    intermediates of the PRF/MIC hashes at B=131072 needs more than a
    v5e's 16 GB of HBM.

    The find output is a BIT-PACKED any-net-matched mask (uint32, bit b
    of word b>>5 = column b) rather than the [N, V, B] matrix + PMKs:
    a chunk's dense matrices are tens of MB while the bitmask is B/8
    bytes (~32 KB).  The engine re-derives
    (net, NC, endian, PMK) for the rare hit columns with the host
    oracle — the executable spec — so no information is lost.

    Like build_crack_step, nothing group-specific is compiled: salts
    and rule programs are data; the jit cache keys on (batch, step
    bucket, net-part signatures) only.
    """
    from ..rules.device import _get_branches, expand_traced

    _get_branches()  # op table must exist before any trace

    repl = NamedSharding(mesh, P())
    s1 = jax.device_put(np.asarray(salt1), repl)
    s2 = jax.device_put(np.asarray(salt2), repl)
    use_pallas = all(d.platform == "tpu" for d in mesh.devices.flat)

    parts = []
    for sig, idxs in _partition(nets).items():
        kind, static = sig[0], sig[1]
        _, fields, match = _KINDS[kind]
        group = [nets[i] for i in idxs]
        mask = np.zeros(_bucket(len(group)), dtype=bool)
        mask[: len(group)] = True
        consts = (mask,) + tuple(
            _pad_nets([getattr(g, f) for g in group]) for f in fields
        )
        consts = tuple(jax.device_put(c, repl) for c in consts)
        parts.append((kind, static, match, consts))

    key = (mesh, "rules_step", use_pallas,
           tuple((p[0], p[1]) for p in parts),
           tuple(tuple(c.shape for c in p[3]) for p in parts))
    if key not in _STEP_CACHE:
        # The cached closure must NOT capture ``parts``: its const
        # arrays are the first-built group's replicated device buffers,
        # and the cache entry outlives that group (verify_step has the
        # same contract).  Capture only code + arity metadata; consts
        # arrive per call via *flat_consts.
        meta = tuple((p[0], p[1], p[2], 1 + len(_KINDS[p[0]][1]))
                     for p in parts)

        def local(base, lens, steps, s1, s2, *flat_consts):
            # reassemble the per-part const tuples from the flat arg list
            it = iter(flat_consts)
            pcs = [tuple(next(it) for _ in range(nc)) for *_m, nc in meta]

            def one_rule(_carry, rsteps):
                pw = expand_traced(base, lens, rsteps)
                pmk = m._pmk_impl(pw, s1, s2, use_pallas=use_pallas)
                hits_l = jnp.int32(0)
                any_l = None
                for (kind, static, match, _nc), consts in zip(meta, pcs):
                    mask = consts[0]
                    fnd = jax.lax.map(
                        lambda cs: match(pmk, static, *cs), consts[1:])
                    fnd = fnd & mask[:, None, None]
                    hits_l = hits_l + jnp.sum(fnd, dtype=jnp.int32)
                    a = fnd.any(axis=(0, 1))  # [b]
                    any_l = a if any_l is None else (any_l | a)
                pad = (-any_l.shape[0]) % 32  # static: local batch shard
                if pad:
                    any_l = jnp.pad(any_l, (0, pad))
                bits = (
                    any_l.reshape(-1, 32).astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32)[None, :]
                ).sum(axis=1, dtype=jnp.uint32)
                return None, (hits_l, bits)

            _, (h, bits) = jax.lax.scan(one_rule, None, steps)
            return jax.lax.psum(h.sum(), DP_AXIS), bits

        n_specs = sum(1 + len(_KINDS[p[0]][1]) for p in parts)
        _STEP_CACHE[key] = _shard(
            mesh, local,
            (P(DP_AXIS, None), P(DP_AXIS), P(), P(), P()) + (P(),) * n_specs,
            (P(), P(None, DP_AXIS)),
        )
    fn = _STEP_CACHE[key]
    flat_consts = tuple(c for p in parts for c in p[3])

    def step(base, lens, steps):
        return fn(base, lens, steps, s1, s2, *flat_consts)

    return step


def build_crack_step(mesh, nets, salt1, salt2):
    """The full crack step for one ESSID group over ``mesh``.

    ``nets``: list of PreppedNet sharing one ESSID.  Returns
    ``step(pw_words[B,16]) -> (hits, found, pmk)`` where ``found`` is
    bool[N, V_max, B] in the order of ``nets`` (variant axes zero-padded
    so the per-net matrices stack) and ``pmk`` is uint32[8, B]; B must be
    divisible by the mesh size.  The host should gate on the replicated
    scalar ``hits`` and only fetch ``found``/``pmk`` for the rare
    positives (the psum hits-gate, SURVEY.md §5.7).

    Building a step never compiles anything group-specific: all jitted
    pieces come from the process-wide shape-keyed cache above.
    """
    repl = NamedSharding(mesh, P())
    s1 = jax.device_put(np.asarray(salt1), repl)
    s2 = jax.device_put(np.asarray(salt2), repl)
    v_max = max(1 if n.keyver == 100 else len(n.variants) for n in nets)
    pmk_fn = pmk_step(mesh)

    parts = []
    order = []   # original net index per concatenated found row
    struct = []  # (real net count, variant count) per part
    for sig, idxs in _partition(nets).items():
        kind, static = sig[0], sig[1]
        _, fields, _ = _KINDS[kind]
        group = [nets[i] for i in idxs]
        mask = np.zeros(_bucket(len(group)), dtype=bool)
        mask[: len(group)] = True
        consts = (mask,) + tuple(
            _pad_nets([getattr(g, f) for g in group]) for f in fields
        )
        consts = tuple(jax.device_put(c, repl) for c in consts)
        parts.append((verify_step(mesh, kind, static), consts))
        v = 1 if kind == "pmkid" else len(group[0].variants)
        struct.append((len(group), v))
        order.extend(idxs)
    inv = np.argsort(np.asarray(order)) if order != sorted(order) else None
    # Fast path: one part, no bucket padding, full variant width, input
    # order — the verify step's output IS the final found matrix.
    trivial = (
        len(parts) == 1
        and struct[0] == (len(nets), v_max)
        and _bucket(len(nets)) == len(nets)
        and inv is None
    )
    asm = None if trivial else _assemble_step(mesh, tuple(struct), v_max, inv)

    def compute_pmk(pw_words):
        return pmk_fn(pw_words, s1, s2)

    def verify(pmk):
        hits = None
        fnds = []
        for fn, consts in parts:
            h, fnd = fn(pmk, *consts)
            hits = h if hits is None else hits + h
            fnds.append(fnd)
        found = fnds[0] if asm is None else asm(*fnds)
        return hits, found, pmk

    def step(pw_words):
        return verify(compute_pmk(pw_words))

    # The two halves are the PMK-store seams (M22000Engine._dispatch_mixed):
    # PBKDF2 over a miss sub-batch of any static width, and verification
    # of a PMK matrix that arrived by any route (computed, cached via
    # mix_step, or fully cached) — same jit caches either way.
    step.compute_pmk = compute_pmk
    step.verify = verify
    return step
