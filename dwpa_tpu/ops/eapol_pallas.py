"""Pallas TPU kernel for the keyver-1/2 EAPOL MIC verify.

Reference semantics: for each NC variant of the AP nonce,
``KCK = PRF-SHA1(PMK, "Pairwise key expansion", macs || nonces)[:16]``
and ``MIC = HMAC-{MD5,SHA1}(KCK, eapol)[:16]`` (web/common.php:240-264),
compared with the captured MIC.  The XLA formulation
(``models/m22000.eapol_match``) runs each compression as a rolled
80-round ``fori_loop`` over full-batch arrays, so every round's state
and schedule round-trip through HBM.  This kernel runs the whole check
of a net part inside one Pallas program per (lane tile, net):

- the grid is ``(batch tiles, nets)``, nets innermost, so a tile's PMK
  block stays in VMEM while every net of the part is checked against it;
- the PMK's HMAC ipad/opad states are computed once per tile (at the
  first net) into a VMEM scratch and read by every net and variant;
- each net's constants (PRF message variants, padded EAPOL blocks, the
  target MIC words) are scalars in SMEM, broadcast over the lanes, so
  the message schedules of the PRF and MIC inner hashes run on the
  scalar unit;
- a ``fori_loop`` over the NC variants runs the PRF (3 compressions)
  and the MIC (2 + E + 1) on (TILE, 128) tiles, the hash state in
  vector registers, and writes one match row per variant.

The compressions roll their rounds into in-kernel loops of
``_UNROLL`` rounds (a Pallas loop keeps its carry in registers, unlike
an XLA one) and call lax directly, so a part traces in under a second.
Each step that embeds the kernel traces it once per process, on the
client's set-up path: fully unrolled (some 22,000 operations) the
traces added about 150 s to the client's prewarm on a v5e host, for a
kernel that runs about 1.7x faster that way (11 ms against 19 ms for two
keyver-2 nets at NC 8 over 131,072 PMKs, about 1% of a batch).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import md5, sha1
from .hmac import IPAD, OPAD

# Lane-tile sublane count per Pallas program.  Swept on one v5e (2
# keyver-2 nets at NC 8, B = 131,072; median call): rounds unrolled,
# 8 / 16 / 32 / 48 / 64 / 128 -> 24.2 / 13.5-14.5 / 10.2-11.2 / 10.5 /
# 10.3 / 12.4 ms; rolled as below, 16 / 32 / 64 -> 17.7 / 18.8 / 23.5 ms.
# Either way the verify is about 1% of a batch's device time.
DEFAULT_TILE = 32

# Rounds per in-kernel loop iteration: divides SHA-1's 20-round and
# MD5's 16-round stages, and MD5's 4-periodic shift amounts stay static.
_UNROLL = 4

_SHA1_K = tuple(np.uint32(k) for k in (sha1.K0, sha1.K1, sha1.K2, sha1.K3))
# MD5 message word of round i: (mult * i + add) % 16 per stage (RFC 1321)
_MD5_G = ((1, 0), (5, 1), (3, 5), (7, 0))

# The hashes below call lax directly: each jnp operator traces as a
# nested jit, about three times the Python cost of the primitive.
_add = functools.partial(functools.reduce, lax.add)
_xor = functools.partial(functools.reduce, lax.bitwise_xor)
_and, _or, _not = lax.bitwise_and, lax.bitwise_or, lax.bitwise_not


def _rotl(x, n):
    return _or(lax.shift_left(x, np.uint32(n)),
               lax.shift_right_logical(x, np.uint32(32 - n)))


def _bswap(x):
    byte1 = lax.shift_left(_and(x, np.uint32(0xFF00)), np.uint32(8))
    byte2 = _and(lax.shift_right_logical(x, np.uint32(8)), np.uint32(0xFF00))
    return _or(_or(lax.shift_left(x, np.uint32(24)),
                   lax.shift_right_logical(x, np.uint32(24))),
               _or(byte1, byte2))


def _const(w, zero):
    """Word ``w`` as a uint32 value: an int becomes ``zero + w``, a
    vector with ``zero``'s layout (Mosaic refuses a loop whose carry
    starts as a splat constant and comes back computed)."""
    return lax.add(zero, np.uint32(w)) if isinstance(w, int) else w


def _zero_like(x):
    """A zero vector computed from ``x``, not a constant."""
    return lax.shift_right_logical(
        lax.shift_right_logical(x, np.uint32(16)), np.uint32(16))


def _sha1(state, block):
    """One SHA-1 compression (FIPS 180-4) with its rounds in four stage
    loops.  ``block``: 16 words, all SMEM scalars (the schedule then runs
    on the scalar unit) or vectors and ints (ints broadcast to vectors).
    The schedule rides in the carry as a ring: round t reads W[t] at its
    head and appends W[t+16]."""
    if any(jnp.ndim(w) for w in block):
        zero = _zero_like(state[0])
        block = [_const(w, zero) for w in block]

    def stage(s):
        k = _SHA1_K[s]

        def body(_, carry):
            (a, b, c, d, e), ring = carry[:5], carry[5:]
            for _ in range(_UNROLL):
                w = ring[0]
                ring = ring[1:] + (_rotl(_xor((ring[13], ring[8], ring[2], w)), 1),)
                if s == 0:
                    f = _xor((d, _and(b, _xor((c, d)))))
                elif s == 2:
                    f = _or(_and(b, c), _and(d, _xor((b, c))))
                else:
                    f = _xor((b, c, d))
                a, b, c, d, e = (_add((_rotl(a, 5), f, e, lax.add(w, k))), a,
                                 _rotl(b, 30), c, d)
            return (a, b, c, d, e) + ring

        return body

    carry = tuple(state) + tuple(block)
    for s in range(4):
        carry = lax.fori_loop(0, 20 // _UNROLL, stage(s), carry)
    return tuple(lax.add(x, y) for x, y in zip(state, carry[:5]))


def _md5(state, word, t_ref):
    """One MD5 compression (RFC 1321) with its rounds in four stage
    loops.  ``word(g)`` loads message word ``g`` (a traced index);
    ``t_ref`` holds the round constants T[0..63] in SMEM."""

    def stage(s):
        mult, add = _MD5_G[s]

        def body(j, st):
            a, b, c, d = st
            for r in range(_UNROLL):
                i = 16 * s + _UNROLL * j + r
                if s == 0:
                    f = _or(_and(b, c), _and(_not(b), d))
                elif s == 1:
                    f = _or(_and(d, b), _and(_not(d), c))
                elif s == 2:
                    f = _xor((b, c, d))
                else:
                    f = _xor((c, _or(b, _not(d))))
                m = lax.add(word((mult * i + add) & 15), t_ref[i])
                a, b, c, d = (d, lax.add(b, _rotl(_add((a, f, m)),
                                                  md5.S[16 * s + r])), b, c)
            return a, b, c, d

        return body

    st = tuple(state)
    for s in range(4):
        st = lax.fori_loop(0, 16 // _UNROLL, stage(s), st)
    return tuple(lax.add(x, y) for x, y in zip(state, st))


def _pad_key(key, pad):
    """A key of up to 16 words (the first a vector) XORed with an HMAC
    pad, zero-filled."""
    return ([lax.bitwise_xor(w, np.uint32(pad)) for w in key]
            + [pad] * (16 - len(key)))


def _iv(words, like):
    zero = _zero_like(like)
    return tuple(_const(v, zero) for v in words)


def _sha1_pads(key):
    """HMAC-SHA1 ipad/opad states of ``key`` (words, the first a vector)."""
    iv = _iv(sha1.IV, key[0])
    return tuple(_sha1(iv, _pad_key(key, p)) for p in (IPAD, OPAD))


def _sha1_hmac(ist, ost, n_blocks, block):
    """HMAC-SHA1 of ``n_blocks`` SMEM blocks, ``block(i)`` the i-th."""
    st = lax.fori_loop(0, n_blocks, lambda i, st: _sha1(st, block(i)), ist)
    return _sha1(ost, list(st) + [0x80000000] + [0] * 9 + [84 * 8])


def _md5_hmac(key, n_blocks, word, t_ref, msg_ref):
    """HMAC-MD5 of ``n_blocks`` SMEM blocks (``word(i)`` reads word i of
    them all) under a 4-word key.  Blocks built here go through the VMEM
    scratch ``msg_ref``, so that a round can load its word by a traced
    index."""

    def staged(words):
        zero = _zero_like(words[0])
        msg_ref[...] = jnp.stack([_const(w, zero) for w in words])
        return lambda g: msg_ref[g]

    iv = _iv(md5.IV, key[0])
    ist = _md5(iv, staged(_pad_key(key, IPAD)), t_ref)
    ost = _md5(iv, staged(_pad_key(key, OPAD)), t_ref)
    st = lax.fori_loop(
        0, n_blocks,
        lambda i, st: _md5(st, lambda g: word(16 * i + g), t_ref), ist)
    # outer message: the 16-byte digest; 64 + 16 = 80 bytes hashed
    return _md5(ost, staged(list(st) + [0x80] + [0] * 9 + [80 * 8, 0]), t_ref)


def _mic_kernel(keyver, n_var, n_eap, pmk_ref, prf_ref, eap_ref, tgt_ref,
                t_ref, out_ref, pads_ref, *msg_ref):
    """One (lane tile, net) program.

    ``pmk_ref``: uint32[8, TILE, 128] PMK words.  ``prf_ref``:
    uint32[1, 1, V*32] the net's two PRF blocks per variant; ``eap_ref``:
    uint32[1, 1, E*16] its EAPOL blocks; ``tgt_ref``: uint32[1, 1, 4] its
    MIC words; ``t_ref``: uint32[64] MD5's round constants (all SMEM).
    ``out_ref``: int32[1, V, TILE, 128] match rows.  ``pads_ref``:
    uint32[10, TILE, 128] scratch, the PMK's ipad/opad states, written at
    the tile's first net; ``msg_ref`` (keyver 1): uint32[16, TILE, 128]
    scratch for the MD5 blocks built in the kernel.
    """

    @pl.when(pl.program_id(1) == 0)
    def _():
        ist, ost = _sha1_pads([pmk_ref[i] for i in range(8)])
        pads_ref[...] = jnp.stack(ist + ost)

    def eap_block(i):
        return [eap_ref[0, 0, 16 * i + j] for j in range(16)]

    def variant(v, carry):
        ist = tuple(pads_ref[i] for i in range(5))
        ost = tuple(pads_ref[5 + i] for i in range(5))
        kck = _sha1_hmac(ist, ost, 2, lambda i: [
            prf_ref[0, 0, 32 * v + 16 * i + j] for j in range(16)])[:4]
        if keyver == 1:
            # MD5 reads the KCK bytes as little-endian words
            mic = _md5_hmac([_bswap(w) for w in kck], n_eap,
                            lambda g: eap_ref[0, 0, g], t_ref, msg_ref[0])
        else:
            mic = _sha1_hmac(*_sha1_pads(kck), n_eap, eap_block)
        hit = mic[0] == tgt_ref[0, 0, 0]
        for i in range(1, 4):
            hit = hit & (mic[i] == tgt_ref[0, 0, i])
        out_ref[0, v] = hit.astype(jnp.int32)
        return carry

    lax.fori_loop(0, n_var, variant, 0)


@functools.partial(jax.jit, static_argnames=("keyver", "tile", "interpret"))
def eapol_mic_pallas(pmk, prf_blocks, eapol_blocks, target, *, keyver,
                     tile=DEFAULT_TILE, interpret=False):
    """MIC match of a part of keyver-1/2 nets over all NC variants.

    ``pmk``: uint32[8, B]; ``prf_blocks``: uint32[N, V, 2, 16];
    ``eapol_blocks``: uint32[N, E, 16] (LE words for keyver 1, BE for
    2); ``target``: uint32[N, 4].  Returns bool[N, V, B], equal to
    ``models/m22000.eapol_match`` mapped over the nets.
    """
    B = pmk.shape[1]
    N, V = prf_blocks.shape[:2]
    E = eapol_blocks.shape[1]
    # Clamp the tile to the lane rows (min 8 sublanes, the uint32 tiling
    # floor) so a small shard does not pad to a full tile of dead work.
    tile = max(8, min(tile, -(-B // 128)))
    step = tile * 128
    padded = -(-B // step) * step
    rows = padded // 128
    pmk = jnp.pad(pmk, ((0, 0), (0, padded - B))).reshape(8, rows, 128)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    scratch = [pltpu.VMEM((10, tile, 128), jnp.uint32)]
    if keyver == 1:
        scratch.append(pltpu.VMEM((16, tile, 128), jnp.uint32))
    out = pl.pallas_call(
        functools.partial(_mic_kernel, keyver, V, E),
        grid=(rows // tile, N),
        in_specs=[
            pl.BlockSpec((8, tile, 128), lambda i, n: (0, i, 0),
                         memory_space=pltpu.VMEM),
            smem((1, 1, V * 32), lambda i, n: (n, 0, 0)),
            smem((1, 1, E * 16), lambda i, n: (n, 0, 0)),
            smem((1, 1, 4), lambda i, n: (n, 0, 0)),
            smem((64,), lambda i, n: (0,)),
        ],
        out_specs=pl.BlockSpec((1, V, tile, 128), lambda i, n: (n, 0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((N, V, rows, 128), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="eapol_mic_pallas",
    )(pmk, prf_blocks.reshape(N, 1, V * 32),
      eapol_blocks.reshape(N, 1, E * 16), target.reshape(N, 1, 4),
      jnp.asarray(md5.T, dtype=jnp.uint32))
    return out.reshape(N, V, padded)[:, :, :B] != 0
