"""AES-128 and AES-128-CMAC (OMAC1) as batched JAX ops.

Needed only for the WPA2 802.11w keyver=3 MIC (AES-128-CMAC over the EAPOL
frame, reference semantics: web/common.php:272 / omac1_aes_128 at
web/common.php:56-112).  The state is 16 per-byte uint32 arrays.

SubBytes is computed, not looked up: the Boyar-Peralta S-box circuit (a
fixed network of XOR/AND gates over the byte's bit planes), evaluated on
four bytes per uint32 word where the array shape allows.  A 256-entry
``jnp.take`` gathers one element at a time on the TPU: on a v5e one
keyver-3 net's NC-8 verify at B=131,072 then took tens of seconds per
batch, far longer than the PBKDF2 it follows (chip run, PR 21).  The
circuit is plain element-wise uint32 work, which the VPU runs at full
width.

The S-box table is generated from the GF(2^8) definition at import time
(rather than transcribed) as the reference the circuit is tested against,
exhaustively; FIPS-197 vectors check the cipher as a whole.
"""

import numpy as np
import jax.numpy as jnp

from .common import u32


def _gf_mul(a: int, b: int) -> int:
    p = 0
    for _ in range(8):
        if b & 1:
            p ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1B
        b >>= 1
    return p


def _make_sbox() -> np.ndarray:
    # multiplicative inverse table via exp/log in GF(2^8), generator 3
    exp = [0] * 510
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x = _gf_mul(x, 3)
    for i in range(255, 510):
        exp[i] = exp[i - 255]
    sbox = np.zeros(256, dtype=np.uint32)
    for v in range(256):
        inv = 0 if v == 0 else exp[255 - log[v]]
        s = inv
        for shift in (1, 2, 3, 4):
            s ^= ((inv << shift) | (inv >> (8 - shift))) & 0xFF
        sbox[v] = s ^ 0x63
    return sbox


SBOX = _make_sbox()
RCON = [1, 2, 4, 8, 16, 32, 64, 128, 27, 54]


# Boyar & Peralta, "A depth-16 circuit for the AES S-box" (2011): input
# bits U0 (MSB) .. U7, a linear top layer, a non-linear middle and a
# linear bottom; outputs S0 (MSB) .. S7, four of them complemented.
_BP_TOP = (
    ("T1", "U0", "U3"), ("T2", "U0", "U5"), ("T3", "U0", "U6"),
    ("T4", "U3", "U5"), ("T5", "U4", "U6"), ("T6", "T1", "T5"),
    ("T7", "U1", "U2"), ("T8", "U7", "T6"), ("T9", "U7", "T7"),
    ("T10", "T6", "T7"), ("T11", "U1", "U5"), ("T12", "U2", "U5"),
    ("T13", "T3", "T4"), ("T14", "T6", "T11"), ("T15", "T5", "T11"),
    ("T16", "T5", "T12"), ("T17", "T9", "T16"), ("T18", "U3", "U7"),
    ("T19", "T7", "T18"), ("T20", "T1", "T19"), ("T21", "U6", "U7"),
    ("T22", "T7", "T21"), ("T23", "T2", "T22"), ("T24", "T2", "T10"),
    ("T25", "T20", "T17"), ("T26", "T3", "T16"), ("T27", "T1", "T12"),
)
_BP_MID = (  # (out, op, a, b): op "&" is AND, "^" is XOR
    ("M1", "&", "T13", "T6"), ("M2", "&", "T23", "T8"),
    ("M3", "^", "T14", "M1"), ("M4", "&", "T19", "U7"),
    ("M5", "^", "M4", "M1"), ("M6", "&", "T3", "T16"),
    ("M7", "&", "T22", "T9"), ("M8", "^", "T26", "M6"),
    ("M9", "&", "T20", "T17"), ("M10", "^", "M9", "M6"),
    ("M11", "&", "T1", "T15"), ("M12", "&", "T4", "T27"),
    ("M13", "^", "M12", "M11"), ("M14", "&", "T2", "T10"),
    ("M15", "^", "M14", "M11"), ("M16", "^", "M3", "M2"),
    ("M17", "^", "M5", "T24"), ("M18", "^", "M8", "M7"),
    ("M19", "^", "M10", "M15"), ("M20", "^", "M16", "M13"),
    ("M21", "^", "M17", "M15"), ("M22", "^", "M18", "M13"),
    ("M23", "^", "M19", "T25"), ("M24", "^", "M22", "M23"),
    ("M25", "&", "M22", "M20"), ("M26", "^", "M21", "M25"),
    ("M27", "^", "M20", "M21"), ("M28", "^", "M23", "M25"),
    ("M29", "&", "M28", "M27"), ("M30", "&", "M26", "M24"),
    ("M31", "&", "M20", "M23"), ("M32", "&", "M27", "M31"),
    ("M33", "^", "M27", "M25"), ("M34", "&", "M21", "M22"),
    ("M35", "&", "M24", "M34"), ("M36", "^", "M24", "M25"),
    ("M37", "^", "M21", "M29"), ("M38", "^", "M32", "M33"),
    ("M39", "^", "M23", "M30"), ("M40", "^", "M35", "M36"),
    ("M41", "^", "M38", "M40"), ("M42", "^", "M37", "M39"),
    ("M43", "^", "M37", "M38"), ("M44", "^", "M39", "M40"),
    ("M45", "^", "M42", "M41"), ("M46", "&", "M44", "T6"),
    ("M47", "&", "M40", "T8"), ("M48", "&", "M39", "U7"),
    ("M49", "&", "M43", "T16"), ("M50", "&", "M38", "T9"),
    ("M51", "&", "M37", "T17"), ("M52", "&", "M42", "T15"),
    ("M53", "&", "M45", "T27"), ("M54", "&", "M41", "T10"),
    ("M55", "&", "M44", "T13"), ("M56", "&", "M40", "T23"),
    ("M57", "&", "M39", "T19"), ("M58", "&", "M43", "T3"),
    ("M59", "&", "M38", "T22"), ("M60", "&", "M37", "T20"),
    ("M61", "&", "M42", "T1"), ("M62", "&", "M45", "T4"),
    ("M63", "&", "M41", "T2"),
)
_BP_BOT = (
    ("L0", "M61", "M62"), ("L1", "M50", "M56"), ("L2", "M46", "M48"),
    ("L3", "M47", "M55"), ("L4", "M54", "M58"), ("L5", "M49", "M61"),
    ("L6", "M62", "L5"), ("L7", "M46", "L3"), ("L8", "M51", "M59"),
    ("L9", "M52", "M53"), ("L10", "M53", "L4"), ("L11", "M60", "L2"),
    ("L12", "M48", "M51"), ("L13", "M50", "L0"), ("L14", "M52", "M61"),
    ("L15", "M55", "L1"), ("L16", "M56", "L0"), ("L17", "M57", "L1"),
    ("L18", "M58", "L8"), ("L19", "M63", "L4"), ("L20", "L0", "L1"),
    ("L21", "L1", "L7"), ("L22", "L3", "L12"), ("L23", "L18", "L2"),
    ("L24", "L15", "L9"), ("L25", "L6", "L10"), ("L26", "L7", "L9"),
    ("L27", "L8", "L10"), ("L28", "L11", "L14"), ("L29", "L11", "L17"),
)
_BP_OUT = (  # S0 .. S7: (a, b, complemented)
    ("L6", "L24", False), ("L16", "L26", True), ("L19", "L28", True),
    ("L6", "L21", False), ("L20", "L22", False), ("L25", "L29", False),
    ("L13", "L27", True), ("L6", "L23", True),
)


def _sbox_planes(x, one):
    """The S-box of every byte lane of ``x`` at once.  ``one`` marks bit
    0 of each lane: 1 for one byte per word, 0x01010101 for four."""
    v = {f"U{i}": (x >> (7 - i)) & one for i in range(8)}
    for out, a, b in _BP_TOP:
        v[out] = v[a] ^ v[b]
    for out, op, a, b in _BP_MID:
        v[out] = (v[a] & v[b]) if op == "&" else (v[a] ^ v[b])
    for out, a, b in _BP_BOT:
        v[out] = v[a] ^ v[b]
    y = None
    for i, (a, b, inv) in enumerate(_BP_OUT):
        s = v[a] ^ v[b]
        if inv:
            s = s ^ one
        s = s << (7 - i)
        y = s if y is None else y | s
    return y


def _sub(byte_arr):
    """SubBytes over uint32 byte values (see the module docstring).

    When the leading axis holds a multiple of four bytes (the rolled
    state's 16, the key schedule's 4), four bytes share a word so each
    gate of the circuit serves four S-boxes."""
    x = u32(byte_arr)
    if x.ndim == 0 or x.shape[0] % 4:
        return _sbox_planes(x, u32(1))
    q = x.reshape((x.shape[0] // 4, 4) + x.shape[1:])
    w = q[:, 0] | (q[:, 1] << 8) | (q[:, 2] << 16) | (q[:, 3] << 24)
    y = _sbox_planes(w, u32(0x01010101))
    return jnp.stack([(y >> s) & u32(0xFF) for s in (0, 8, 16, 24)],
                     axis=1).reshape(x.shape)


def _xtime(b):
    return ((b << 1) ^ ((b >> 7) * u32(0x1B))) & u32(0xFF)


def aes128_expand_key(key16):
    """key16: list of 16 uint32 byte-value arrays -> list of 11 round keys."""
    rk = [list(key16)]
    for r in range(10):
        prev = rk[-1]
        t = [_sub(prev[13]), _sub(prev[14]), _sub(prev[15]), _sub(prev[12])]
        t[0] = t[0] ^ u32(RCON[r])
        nk = []
        for c in range(4):
            for row in range(4):
                t[row] = u32(prev[4 * c + row]) ^ t[row]
            nk.extend(t)
            t = list(nk[-4:])
        rk.append(nk)
    return rk


def aes128_encrypt_block(round_keys, block16):
    """Encrypt one 16-byte block (per-byte uint32 arrays, index = byte pos).

    Byte order follows FIPS-197: block16[i] is byte i of the input, state
    column c is bytes 4c..4c+3.
    """
    s = [u32(block16[i]) ^ u32(round_keys[0][i]) for i in range(16)]
    for r in range(1, 11):
        s = [_sub(b) for b in s]
        # ShiftRows: state[row + 4c] <- state[row + 4((c + row) % 4)]
        s = [s[(i + 4 * (i % 4)) % 16] for i in range(16)]
        if r < 10:
            ns = []
            for c in range(4):
                a0, a1, a2, a3 = s[4 * c : 4 * c + 4]
                x0, x1, x2, x3 = _xtime(a0), _xtime(a1), _xtime(a2), _xtime(a3)
                ns.extend(
                    [
                        x0 ^ x1 ^ a1 ^ a2 ^ a3,
                        a0 ^ x1 ^ x2 ^ a2 ^ a3,
                        a0 ^ a1 ^ x2 ^ x3 ^ a3,
                        x0 ^ a0 ^ a1 ^ a2 ^ x3,
                    ]
                )
            s = ns
        s = [s[i] ^ u32(round_keys[r][i]) for i in range(16)]
    return s


# ---------------------------------------------------------------------------
# Rolled array-state variant (cold-path compile-time trade, like
# sha1_compress_rolled): state is ONE uint32[16, ...] array, rounds are a
# fori_loop, SubBytes the gate circuit (see the module docstring),
# ShiftRows a constant permutation.
# ---------------------------------------------------------------------------

import jax

_SHIFT_ROWS = np.array([(i + 4 * (i % 4)) % 16 for i in range(16)])
_ROT_WORD = np.array([13, 14, 15, 12])


def _mix_columns_arr(s):
    a = s.reshape((4, 4) + s.shape[1:])
    x = _xtime(a)
    rows = [
        x[:, 0] ^ x[:, 1] ^ a[:, 1] ^ a[:, 2] ^ a[:, 3],
        a[:, 0] ^ x[:, 1] ^ x[:, 2] ^ a[:, 2] ^ a[:, 3],
        a[:, 0] ^ a[:, 1] ^ x[:, 2] ^ x[:, 3] ^ a[:, 3],
        x[:, 0] ^ a[:, 0] ^ a[:, 1] ^ a[:, 2] ^ x[:, 3],
    ]
    return jnp.stack(rows, axis=1).reshape(s.shape)


def aes128_expand_key_rolled(key16):
    """key16: uint32[16, ...] byte-value array -> uint32[11, 16, ...]."""
    rcon = jnp.asarray(RCON, dtype=jnp.uint32)

    def body(prev, rc):
        t = _sub(prev[_ROT_WORD])
        t = t.at[0].set(t[0] ^ rc)
        words = []
        cur = t
        for c in range(4):
            cur = prev[4 * c : 4 * c + 4] ^ cur
            words.append(cur)
        nk = jnp.concatenate(words)
        return nk, nk

    _, rks = jax.lax.scan(body, key16, rcon)
    return jnp.concatenate([key16[None], rks])


def aes128_encrypt_rolled(rks, block):
    """``rks``: uint32[11, 16, ...]; ``block``: uint32[16, ...]."""
    s = block ^ rks[0]

    def round_body(r, s):
        s = _sub(s)[_SHIFT_ROWS]
        s = _mix_columns_arr(s)
        return s ^ rks[r]

    s = jax.lax.fori_loop(1, 10, round_body, s)
    s = _sub(s)[_SHIFT_ROWS]
    return s ^ rks[10]


def _dbl_arr(b):
    carry = jnp.concatenate([b[1:] >> 7, jnp.zeros_like(b[:1])])
    out = ((b << 1) & u32(0xFF)) | carry
    return out.at[15].set(out[15] ^ (b[0] >> 7) * u32(0x87))


def aes128_cmac_rolled(key16, msg_blocks, last_block, last_complete):
    """AES-128-CMAC with the rolled AES core.

    ``key16``: uint32[16, ...] (batched KCK bytes); ``msg_blocks``:
    uint32[F, 16] constants; ``last_block``: uint32[16] (10*-padded if
    incomplete); ``last_complete``: static bool.  Returns uint32[16, ...].
    """
    rks = aes128_expand_key_rolled(key16)
    shape = key16.shape[1:]
    zero = jnp.zeros((16,) + shape, dtype=jnp.uint32)
    k1 = _dbl_arr(aes128_encrypt_rolled(rks, zero))
    sub = k1 if last_complete else _dbl_arr(k1)

    c = zero
    for i in range(msg_blocks.shape[0]):
        blk = jnp.broadcast_to(msg_blocks[i][(...,) + (None,) * len(shape)], c.shape)
        c = aes128_encrypt_rolled(rks, blk ^ c)
    last = jnp.broadcast_to(last_block[(...,) + (None,) * len(shape)], c.shape)
    return aes128_encrypt_rolled(rks, last ^ sub ^ c)


def _dbl(b16):
    """GF(2^128) doubling for CMAC subkeys (left shift 1, xor 0x87)."""
    out = []
    for i in range(15):
        out.append(((b16[i] << 1) | (b16[i + 1] >> 7)) & u32(0xFF))
    out.append(((b16[15] << 1) & u32(0xFF)) ^ ((b16[0] >> 7) * u32(0x87)))
    return out


def aes128_cmac(key16, msg_blocks, last_block, last_complete):
    """AES-128-CMAC (OMAC1, RFC 4493).

    ``key16``: 16 uint32 byte arrays (the per-candidate KCK).
    ``msg_blocks``: list of full 16-byte blocks *before* the last block
    (each a list of 16 uint32 words/ints).
    ``last_block``: the final block, already 10*-padded if incomplete.
    ``last_complete``: static bool — selects the K1/K2 subkey.

    Returns 16 uint32 byte arrays (the MAC).
    """
    rks = aes128_expand_key(key16)
    zero = [u32(0)] * 16
    l = aes128_encrypt_block(rks, zero)
    k1 = _dbl(l)
    sub = k1 if last_complete else _dbl(k1)

    c = [u32(0)] * 16
    for blk in msg_blocks:
        c = aes128_encrypt_block(rks, [u32(blk[i]) ^ c[i] for i in range(16)])
    final = [u32(last_block[i]) ^ sub[i] ^ c[i] for i in range(16)]
    return aes128_encrypt_block(rks, final)
