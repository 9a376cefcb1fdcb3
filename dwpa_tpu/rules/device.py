"""On-device hashcat rule mangling (the GPU-rule-engine equivalent).

hashcat runs its rule engine *on the accelerator*: the host uploads the
base wordlist once and every rule's mangling happens in the kernel, so
candidate bandwidth is multiplied by the rule count for free.  The
reference inherits that via ``hashcat -r`` (help_crack.py:773); our host
interpreter (rules/engine.py) is the behavioral spec, but host expansion
tops out around ~1M cand/s on a small host — it cannot feed a mesh,
and every host-expanded candidate costs H2D bytes.  This module is the TPU seat of that GPU feature
(SURVEY §7.2 M5 "then on-device for mask/append families").

TPU-first design — rules are DATA, not code:

- A rule is encoded as an int32[S, 3] array of (opcode, arg1, arg2)
  steps.  One jitted interpreter — ``lax.scan`` over the steps, each
  step a ``lax.switch`` over the op table — serves EVERY rule at a
  given (batch, step-bucket) shape: compiling per rule (134 lines in a
  bestWPA-class set) would pay ~100 XLA compiles per work unit, while
  the data encoding pays a handful for a server's lifetime, exactly
  like the PBKDF2 salt-as-data design (ops/pbkdf2.py).
- Words are held as one uint8 lane per byte (uint8[B, W], W=64) so
  every op is an elementwise map or a gather along the unsharded byte
  axis; the dp-sharded batch axis is never communicated.  Unpack from
  and repack to the engine's packed uint32[B, 16] key blocks happen
  inside the same jit, so XLA fuses the whole expansion into the
  PBKDF2 feed.
- Semantics are bit-identical to rules/engine.py (differentially
  tested): same position conventions, same out-of-range no-ops, same
  reject filters (a rejected word's column is zeroed, the engine's
  oracle re-check keeps decode honest).  The single unsupported op is
  ``@`` (purge — data-dependent compaction, a poor fit for fixed-shape
  lanes); rules containing it fall back to host expansion.
- Length overflow: hashcat words may grow to 256 bytes mid-rule (host
  MAX_WORD); device lanes stop at W=64.  Growth is LENGTH-deterministic
  for every supported op (only ``@`` is content-dependent, and it is
  excluded), so the host pre-computes each rule's length trajectory
  over the batch's length vector (``simulate_lens`` — pure numpy) and
  routes the rare overflowing (word, rule) pairs to host expansion;
  the device independently flags them (ok=False) so its output stays
  correct even if a caller skips the simulation.
"""

import numpy as np

from .engine import _POS, MAX_WORD, Rule

#: Device lane width per word: intermediate rule results up to 64 bytes
#: stay on device; the final 8..63 PSK filter applies afterwards.  64
#: (not hashcat's 256-byte MAX_WORD) keeps the lane array at one uint8
#: per byte of key block — growth past it is length-deterministic, so
#: the host routes those rare (word, rule) pairs to its own interpreter
#: (see simulate_lens) instead of paying 4x the HBM traffic on every
#: batch for them.
W = 64

#: Final WPA PSK length bounds (models/m22000.py MIN/MAX_PSK_LEN).
_MIN_OUT, _MAX_OUT = 8, 63

# Op table order — _BRANCHES below and the encoder agree on these codes.
_OPS = [
    ":", "l", "u", "c", "C", "t", "T", "r", "d", "f", "{", "}", "[", "]",
    "D", "x", "O", "i", "o", "'", "$", "^", "s", "z", "Z", "q", "k", "K",
    "*", "L", "R", "+", "-", ".", ",", "y", "Y", "e", "E", "p",
    "<", ">", "_", "!", "/", "(", ")", "=", "%",
]
_OPCODE = {c: i for i, c in enumerate(_OPS)}

#: ops whose single arg is a position/count (0-9A-Z)
_POS1 = set("TD'zZLR+-.,yY<>_p")
#: ops whose single arg is a literal char
_CHR1 = set("$^!/()e")
#: (position, char) pairs
_POS_CHR = set("io=%")
#: (position, position) pairs
_POS_POS = set("xO*")
#: (char, char) pairs
_CHR_CHR = set("s")


def device_supported(rule: Rule) -> bool:
    """True when every step of ``rule`` runs on device (everything in
    the fast-kernel op set except ``@``)."""
    return all(op in _OPCODE for op, _ in rule.steps)


def encode_rule(rule: Rule) -> np.ndarray:
    """Rule -> int32[S, 3] (opcode, arg1, arg2) step array (device data)."""
    rows = []
    for op, args in rule.steps:
        a1 = a2 = 0
        if op in _POS1:
            a1 = _POS[args[0]]
        elif op in _CHR1:
            a1 = args.encode("latin1")[0]
        elif op == "E":
            a1 = 0x20  # title-case with the fixed space separator
        elif op in _POS_CHR:
            a1 = _POS[args[0]]
            a2 = args[1].encode("latin1")[0]
        elif op in _POS_POS:
            a1, a2 = _POS[args[0]], _POS[args[1]]
        elif op in _CHR_CHR:
            enc = args.encode("latin1")
            a1, a2 = enc[0], enc[1]
        rows.append((_OPCODE[op], a1, a2))
    if not rows:
        rows.append((0, 0, 0))  # ":" — empty rule is the noop
    return np.asarray(rows, dtype=np.int32)


def step_bucket(n: int) -> int:
    """Pad step counts to powers of two so the interpreter's jit cache
    hits across rules of nearby length (pad steps are ':' noops)."""
    b = 1
    while b < n:
        b *= 2
    return b


def simulate_lens(rule: Rule, lens: np.ndarray):
    """Length trajectory of ``rule`` over a batch's length vector.

    Returns ``(out_lens, hostneed)``: final lengths (int64) and a bool
    mask of columns whose INTERMEDIATE length ever exceeded the device
    lane width W — those (word, rule) pairs must be host-expanded (the
    host's 256-byte MAX_WORD allows shrink-back the device cannot
    represent).  Pure numpy; every supported op's length effect is
    content-independent, which is what makes this exact.
    """
    L = lens.astype(np.int64)
    hostneed = np.zeros(L.shape, dtype=bool)
    for op, args in rule.steps:
        if op in ("d", "f", "q"):
            L2 = 2 * L
        elif op == "p":
            L2 = (1 + _POS[args[0]]) * L
        elif op in ("z", "Z"):
            L2 = np.where(L > 0, L + _POS[args[0]], L)
        elif op in ("y", "Y"):
            n = _POS[args[0]]
            L2 = np.where(n <= L, L + n, L)
        elif op == "i":
            L2 = np.where(_POS[args[0]] <= L, L + 1, L)
        elif op == "x":
            p, m = _POS[args[0]], _POS[args[1]]
            L2 = np.where(p + m <= L, m, L)
        elif op == "O":
            p, m = _POS[args[0]], _POS[args[1]]
            L2 = np.where(p + m <= L, L - m, L)
        elif op == "D":
            L2 = np.where(_POS[args[0]] < L, L - 1, L)
        elif op in ("[", "]"):
            L2 = np.maximum(L - 1, 0)
        elif op == "'":
            L2 = np.minimum(L, _POS[args[0]])
        elif op in ("$", "^"):
            L2 = L + 1
        else:
            L2 = L
        hostneed |= L2 > W
        L = np.where(L2 > MAX_WORD, 0, L2)  # host rejects >256 outright
    return L, hostneed


# ---------------------------------------------------------------------------
# The interpreter (jax)
# ---------------------------------------------------------------------------


def _branches():
    """Build the op-branch table lazily (keeps jax out of module import)."""
    import jax.numpy as jnp

    iota = jnp.arange(W, dtype=jnp.int32)[None, :]  # [1, W]

    def c8(v):
        return jnp.asarray(v).astype(jnp.uint8)

    def isup(b):
        return (b >= 65) & (b <= 90)

    def islo(b):
        return (b >= 97) & (b <= 122)

    def tog(b):
        return jnp.where(islo(b), b - 32, jnp.where(isup(b), b + 32, b))

    def low(b):
        return jnp.where(isup(b), b + 32, b)

    def up(b):
        return jnp.where(islo(b), b - 32, b)

    def gather(b, idx):
        return jnp.take_along_axis(b, jnp.clip(idx, 0, W - 1), axis=1)

    def grow(b, L, ok, newL):
        """Apply a length increase; overflowing columns die (ok=False,
        len 0 — simulate_lens routes them to host expansion)."""
        over = newL > W
        return b, jnp.where(over, 0, newL), ok & ~over

    def condL(c, newB, newL, b, L):
        """Per-candidate conditional op: c bool[B]."""
        return (jnp.where(c[:, None], newB, b), jnp.where(c, newL, L))

    B_ = None  # branches close over shapes at trace time

    def noop(b, L, ok, a1, a2):
        return b, L, ok

    def f_l(b, L, ok, a1, a2):
        return low(b), L, ok

    def f_u(b, L, ok, a1, a2):
        return up(b), L, ok

    def f_c(b, L, ok, a1, a2):
        return jnp.where(iota == 0, up(b), low(b)), L, ok

    def f_C(b, L, ok, a1, a2):
        return jnp.where(iota == 0, low(b), up(b)), L, ok

    def f_t(b, L, ok, a1, a2):
        return tog(b), L, ok

    def f_T(b, L, ok, a1, a2):
        return jnp.where(iota == a1, tog(b), b), L, ok

    def f_r(b, L, ok, a1, a2):
        return gather(b, L[:, None] - 1 - iota), L, ok

    def f_d(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < L[:, None], iota, iota - L[:, None]))
        return grow(out, L, ok, 2 * L)

    def f_f(b, L, ok, a1, a2):
        idx = jnp.where(iota < L[:, None], iota, 2 * L[:, None] - 1 - iota)
        return grow(gather(b, idx), L, ok, 2 * L)

    def f_rotl(b, L, ok, a1, a2):
        Ls = jnp.maximum(L, 1)[:, None]
        return gather(b, (iota + 1) % Ls), L, ok

    def f_rotr(b, L, ok, a1, a2):
        Ls = jnp.maximum(L, 1)[:, None]
        return gather(b, (iota + Ls - 1) % Ls), L, ok

    def f_delfirst(b, L, ok, a1, a2):
        return gather(b, iota + 1), jnp.maximum(L - 1, 0), ok

    def f_dellast(b, L, ok, a1, a2):
        return b, jnp.maximum(L - 1, 0), ok

    def f_D(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < a1, iota, iota + 1))
        nb, nL = condL(a1 < L, out, L - 1, b, L)
        return nb, nL, ok

    def f_x(b, L, ok, a1, a2):
        out = gather(b, iota + a1)
        nb, nL = condL(a1 + a2 <= L, out, jnp.full_like(L, a2), b, L)
        return nb, nL, ok

    def f_O(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < a1, iota, iota + a2))
        nb, nL = condL(a1 + a2 <= L, out, L - a2, b, L)
        return nb, nL, ok

    def f_i(b, L, ok, a1, a2):
        ins = jnp.where(iota == a1, c8(a2), gather(b, iota - 1))
        out = jnp.where(iota < a1, b, ins)
        c = a1 <= L
        over = (L + 1 > W) & c
        nb, nL = condL(c & ~over, out, L + 1, b, L)
        return nb, jnp.where(over, 0, nL), ok & ~over

    def f_o(b, L, ok, a1, a2):
        hit = (iota == a1) & (a1 < L[:, None])
        return jnp.where(hit, c8(a2), b), L, ok

    def f_trunc(b, L, ok, a1, a2):
        return b, jnp.minimum(L, a1), ok

    def f_append(b, L, ok, a1, a2):
        out = jnp.where(iota == L[:, None], c8(a1), b)
        return grow(out, L, ok, L + 1)

    def f_prepend(b, L, ok, a1, a2):
        out = jnp.where(iota == 0, c8(a1), gather(b, iota - 1))
        return grow(out, L, ok, L + 1)

    def f_sub(b, L, ok, a1, a2):
        hit = (b == c8(a1)) & (iota < L[:, None])
        return jnp.where(hit, c8(a2), b), L, ok

    def f_z(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < a1, 0, iota - a1))
        c = L > 0
        newL = jnp.where(c, L + a1, L)
        over = newL > W
        nb, nL = condL(c & ~over, out, newL, b, L)
        return nb, jnp.where(over, 0, nL), ok & ~over

    def f_Z(b, L, ok, a1, a2):
        out = gather(b, jnp.minimum(iota, L[:, None] - 1))
        c = L > 0
        newL = jnp.where(c, L + a1, L)
        over = newL > W
        nb, nL = condL(c & ~over, out, newL, b, L)
        return nb, jnp.where(over, 0, nL), ok & ~over

    def f_q(b, L, ok, a1, a2):
        return grow(gather(b, iota // 2), L, ok, 2 * L)

    def f_k(b, L, ok, a1, a2):
        idx = jnp.where(iota == 0, 1, jnp.where(iota == 1, 0, iota))
        nb, nL = condL(L >= 2, gather(b, idx), L, b, L)
        return nb, nL, ok

    def f_K(b, L, ok, a1, a2):
        p, m = (L - 2)[:, None], (L - 1)[:, None]
        idx = jnp.where(iota == p, m, jnp.where(iota == m, p, iota))
        nb, nL = condL(L >= 2, gather(b, idx), L, b, L)
        return nb, nL, ok

    def f_swap(b, L, ok, a1, a2):
        idx = jnp.where(iota == a1, a2, jnp.where(iota == a2, a1, iota))
        nb, nL = condL((a1 < L) & (a2 < L), gather(b, idx), L, b, L)
        return nb, nL, ok

    def _at(b, L, a1, fn):
        hit = (iota == a1) & (a1 < L[:, None])
        return jnp.where(hit, fn(b), b)  # uint8 lanes wrap mod 256

    def f_shl(b, L, ok, a1, a2):
        return _at(b, L, a1, lambda x: x << 1), L, ok

    def f_shr(b, L, ok, a1, a2):
        return _at(b, L, a1, lambda x: x >> 1), L, ok

    def f_incr(b, L, ok, a1, a2):
        return _at(b, L, a1, lambda x: x + 1), L, ok

    def f_decr(b, L, ok, a1, a2):
        return _at(b, L, a1, lambda x: x + 255), L, ok

    def f_repl_next(b, L, ok, a1, a2):
        nxt = gather(b, iota + 1)
        hit = (iota == a1) & (a1 + 1 < L[:, None])
        return jnp.where(hit, nxt, b), L, ok

    def f_repl_prior(b, L, ok, a1, a2):
        prv = gather(b, iota - 1)
        hit = (iota == a1) & (a1 > 0) & (a1 < L[:, None])
        return jnp.where(hit, prv, b), L, ok

    def f_y(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < a1, iota, iota - a1))
        c = a1 <= L
        newL = jnp.where(c, L + a1, L)
        over = newL > W
        nb, nL = condL(c & ~over, out, newL, b, L)
        return nb, jnp.where(over, 0, nL), ok & ~over

    def f_Y(b, L, ok, a1, a2):
        out = gather(b, jnp.where(iota < L[:, None], iota, iota - a1))
        c = a1 <= L
        newL = jnp.where(c, L + a1, L)
        over = newL > W
        nb, nL = condL(c & ~over, out, newL, b, L)
        return nb, jnp.where(over, 0, nL), ok & ~over

    def f_title(b, L, ok, a1, a2):
        lo = low(b)
        prev = gather(lo, iota - 1)
        upmask = (iota == 0) | (prev == c8(a1))
        return jnp.where(upmask & islo(lo), lo - 32, lo), L, ok

    def f_p(b, L, ok, a1, a2):
        Ls = jnp.maximum(L, 1)[:, None]
        return grow(gather(b, iota % Ls), L, ok, (1 + a1) * L)

    def f_rej_less(b, L, ok, a1, a2):
        return b, L, ok & (L < a1)

    def f_rej_greater(b, L, ok, a1, a2):
        return b, L, ok & (L > a1)

    def f_rej_eq(b, L, ok, a1, a2):
        return b, L, ok & (L == a1)

    def _contains(b, L, x):
        import jax.numpy as jnp

        return ((b == c8(x)) & (iota < L[:, None])).any(axis=1)

    def f_rej_contain(b, L, ok, a1, a2):
        return b, L, ok & ~_contains(b, L, a1)

    def f_rej_not_contain(b, L, ok, a1, a2):
        return b, L, ok & _contains(b, L, a1)

    def f_rej_first(b, L, ok, a1, a2):
        return b, L, ok & (L > 0) & (b[:, 0] == c8(a1))

    def f_rej_last(b, L, ok, a1, a2):
        last = jnp.take_along_axis(
            b, jnp.maximum(L - 1, 0)[:, None], axis=1
        )[:, 0]
        return b, L, ok & (L > 0) & (last == c8(a1))

    def f_rej_at(b, L, ok, a1, a2):
        at = jnp.take_along_axis(
            b, jnp.clip(jnp.full_like(L, a1), 0, W - 1)[:, None], axis=1
        )[:, 0]
        return b, L, ok & (a1 < L) & (at == c8(a2))

    def f_rej_count(b, L, ok, a1, a2):
        cnt = ((b == c8(a2)) & (iota < L[:, None])).sum(axis=1)
        return b, L, ok & (cnt >= a1)

    return [
        noop, f_l, f_u, f_c, f_C, f_t, f_T, f_r, f_d, f_f, f_rotl, f_rotr,
        f_delfirst, f_dellast, f_D, f_x, f_O, f_i, f_o, f_trunc, f_append,
        f_prepend, f_sub, f_z, f_Z, f_q, f_k, f_K, f_swap, f_shl, f_shr,
        f_incr, f_decr, f_repl_next, f_repl_prior, f_y, f_Y, f_title,
        f_title, f_p,
        f_rej_less, f_rej_greater, f_rej_eq, f_rej_contain,
        f_rej_not_contain, f_rej_first, f_rej_last, f_rej_at, f_rej_count,
    ]


_BRANCH_CACHE = []


def _get_branches():
    # Must be first called OUTSIDE any jit trace (expand_batch does so):
    # the branch closures capture a concrete iota constant, and building
    # them mid-trace would capture a tracer instead (leak on reuse).
    if not _BRANCH_CACHE:
        _BRANCH_CACHE.append(_branches())
    return _BRANCH_CACHE[0]


def expand_traced(packed, lens, steps):
    """Traceable core: one rule over one packed batch.

    ``(packed uint32[B,16], lens int32[B], steps int32[S,3]) ->
    uint32[B,16]`` with rejected/out-of-range columns zeroed.  Pure
    function of traced arrays — composable into larger jits: the
    engine's fused rules crack step (parallel/step.py build_rules_step)
    runs this under shard_map ahead of PBKDF2, so expansion costs no
    dispatch of its own.
    """
    import jax.numpy as jnp
    from jax import lax

    B = packed.shape[0]
    shifts = jnp.asarray([24, 16, 8, 0], dtype=jnp.uint32)
    b = ((packed[:, :, None] >> shifts[None, None, :])
         & jnp.uint32(0xFF)).astype(jnp.uint8).reshape(B, W)
    L = lens.astype(jnp.int32)
    ok = jnp.ones((B,), dtype=bool)
    branches = _get_branches()
    iota = jnp.arange(W, dtype=jnp.int32)[None, :]

    def body(carry, step):
        b, L, ok = carry
        b, L, ok = lax.switch(
            jnp.clip(step[0], 0, len(branches) - 1), branches,
            b, L, ok, step[1], step[2],
        )
        # invariant: byte lanes beyond the word length stay zero, so
        # gathers in later steps never leak stale bytes
        b = jnp.where(iota < L[:, None], b, 0)
        return (b, L, ok), None

    (b, L, ok), _ = lax.scan(body, (b, L, ok), steps)
    valid = ok & (L >= _MIN_OUT) & (L <= _MAX_OUT)
    out = (b.astype(jnp.uint32).reshape(B, 16, 4)
           << shifts[None, None, :]).sum(axis=2, dtype=jnp.uint32)
    return out * valid[:, None].astype(jnp.uint32)


def apply_rule_device(words, rule: Rule):
    """Differential-test helper: run one rule over host words on device.

    Returns a list aligned with ``words``: the mangled bytes where the
    device produced a valid candidate (8..63, not rejected), else None.
    The host interpreter (rule.apply + the PSK length filter) is the
    reference this must match exactly.
    """
    import jax

    from ..utils import bytesops as bo

    words = list(words)
    packed = bo.pack_passwords_be(words)
    lens = np.asarray([len(w) for w in words], np.int32)
    out = np.asarray(
        expand_batch(jax.device_put(packed), jax.device_put(lens),
                     encode_rule(rule))
    )
    out_lens, hostneed = simulate_lens(rule, lens)
    res = []
    for i in range(len(words)):
        if hostneed[i] or not out[i].any():
            res.append(None)
        else:
            res.append(bo.words_to_bytes_be(out[i])[: int(out_lens[i])])
    return res


_EXPAND_JITS = {}  # (impl, sharding or None) -> jitted expand


def stack_rules(steps_list, n_rules: int, n_steps: int) -> np.ndarray:
    """Pad a chunk of encoded rules to one int32[n_rules, n_steps, 3] stack.

    Missing steps and missing rules pad with ':' noops.  Fixing
    ``n_rules`` (the engine's RULES_CHUNK) keeps the fused step's jit
    signature constant across rulesets — a padded noop rule costs one
    wasted PBKDF2 pass on at most the final chunk, vs a fresh XLA
    compile per ruleset size.  ``n_steps`` is the step bucket of the
    WHOLE ruleset, not of this chunk, for the same reason: per-chunk
    buckets (1, 2, 4, 8 for wpa.rule) meant one compile of the fused
    rules step per bucket and net group, over a minute each on a v5e
    host (chip run, PR 21), against a few noop steps per rule.
    """
    if n_steps < max(s.shape[0] for s in steps_list):
        raise ValueError(f"n_steps={n_steps} shorter than a rule")
    stack = np.zeros((n_rules, n_steps, 3), dtype=np.int32)
    for r, s in enumerate(steps_list):
        stack[r, : s.shape[0]] = s
    return stack


def expand_batch(packed_dev, lens_dev, steps: np.ndarray, sharding=None):
    """Run one encoded rule over an uploaded base batch, on device.

    ``steps`` is padded to its power-of-two bucket with ':' noops so the
    jit cache is keyed by (B, bucket) only — a whole ruleset reuses one
    compilation.  Returns uint32[B, 16] packed candidates with rejected
    / out-of-range columns zeroed (a zero key block cannot decode to a
    valid PSK; the engine's oracle re-check makes false hits impossible
    to report).
    """
    import jax

    _get_branches()  # build the op table outside the jit trace
    fn = _EXPAND_JITS.get(("one", sharding))
    if fn is None:
        kw = {} if sharding is None else {"out_shardings": sharding}
        fn = jax.jit(expand_traced, **kw)
        _EXPAND_JITS[("one", sharding)] = fn
    S = step_bucket(steps.shape[0])
    if S != steps.shape[0]:
        pad = np.zeros((S - steps.shape[0], 3), dtype=np.int32)
        steps = np.concatenate([steps, pad])
    return fn(packed_dev, lens_dev, steps)
