"""The plain m22000 reference: hashlib PBKDF2, HMAC-MD5/SHA-1/SHA-256
and AES-128-CMAC, with the reference server's nonce-error search.

A copy kept with the benchmark: it imports nothing of ``dwpa_tpu`` (its
``oracle/m22000.py`` and ``models/hashline.py`` are what it was copied
from), so no later change to the program can move the yardstick.  It
decides which nets a candidate list cracks; the harness compares that
with what the client submitted and the server accepted.
"""

import hashlib
import hmac
import struct
from dataclasses import dataclass

TYPE_PMKID = 1
TYPE_EAPOL = 2

PRF_LABEL_V12 = b"Pairwise key expansion\x00"
PRF_LABEL_V3 = b"\x01\x00Pairwise key expansion"


@dataclass(frozen=True)
class Line:
    """One parsed m22000 hashline (``WPA*TYPE*MIC*AP*STA*ESSID*ANONCE*EAPOL*MP``)."""

    hash_type: int
    pmkid_or_mic: bytes
    mac_ap: bytes
    mac_sta: bytes
    essid: bytes
    anonce: bytes
    eapol: bytes

    @property
    def keyver(self) -> int:
        """1, 2 or 3 for EAPOL (key_information & 3); 100 for PMKID."""
        if self.hash_type == TYPE_PMKID:
            return 100
        return struct.unpack_from(">H", self.eapol, 5)[0] & 3


def parse(raw: str) -> Line:
    parts = raw.strip().split("*", 8)
    if len(parts) != 9 or parts[0] != "WPA" or parts[1] not in ("01", "02"):
        raise ValueError(f"not an m22000 line: {raw[:40]!r}")
    f = [bytes.fromhex(p) for p in parts[2:8]]
    return Line(int(parts[1]), f[0], f[1], f[2], f[3], f[4], f[5])


def serialize(hash_type: int, mic: bytes, mac_ap: bytes, mac_sta: bytes,
              essid: bytes, anonce: bytes = b"", eapol: bytes = b"",
              message_pair: int = None) -> str:
    mp = "" if message_pair is None else f"{message_pair:02x}"
    return "*".join(["WPA", f"{hash_type:02d}", mic.hex(), mac_ap.hex(),
                     mac_sta.hex(), essid.hex(), anonce.hex(), eapol.hex(),
                     mp])


# -- AES-128 (encrypt only) for the keyver-3 CMAC ---------------------------

def _sbox():
    def mul(a, b):
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

    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x = mul(x, 3)
    for i in range(255, 510):
        exp[i] = exp[i - 255]
    box = [0] * 256
    for v in range(256):
        inv = 0 if v == 0 else exp[255 - log[v]]
        s = inv
        for sh in (1, 2, 3, 4):
            s ^= ((inv << sh) | (inv >> (8 - sh))) & 0xFF
        box[v] = s ^ 0x63
    return box


_SBOX = _sbox()
_RCON = [1, 2, 4, 8, 16, 32, 64, 128, 27, 54]


def _round_keys(key: bytes):
    w = [list(key[i:i + 4]) for i in range(0, 16, 4)]
    for i in range(4, 44):
        t = list(w[i - 1])
        if i % 4 == 0:
            t = [_SBOX[t[1]], _SBOX[t[2]], _SBOX[t[3]], _SBOX[t[0]]]
            t[0] ^= _RCON[i // 4 - 1]
        w.append([w[i - 4][j] ^ t[j] for j in range(4)])
    return [sum(w[4 * r:4 * r + 4], []) for r in range(11)]


def _xt(b):
    return ((b << 1) ^ (0x1B if b & 0x80 else 0)) & 0xFF


def aes128_encrypt(rks, block: bytes) -> bytes:
    s = [block[i] ^ rks[0][i] for i in range(16)]
    for r in range(1, 11):
        s = [_SBOX[b] for b in s]
        s = [s[(i + 4 * (i % 4)) % 16] for i in range(16)]
        if r < 10:
            ns = []
            for c in range(4):
                a0, a1, a2, a3 = s[4 * c:4 * c + 4]
                ns += [_xt(a0) ^ _xt(a1) ^ a1 ^ a2 ^ a3,
                       a0 ^ _xt(a1) ^ _xt(a2) ^ a2 ^ a3,
                       a0 ^ a1 ^ _xt(a2) ^ _xt(a3) ^ a3,
                       _xt(a0) ^ a0 ^ a1 ^ a2 ^ _xt(a3)]
            s = ns
        s = [s[i] ^ rks[r][i] for i in range(16)]
    return bytes(s)


def aes_cmac(msg: bytes, key: bytes) -> bytes:
    """AES-128-CMAC (RFC 4493)."""
    def dbl(b: bytes) -> bytes:
        v = int.from_bytes(b, "big") << 1
        if b[0] & 0x80:
            v ^= 0x87
        return (v & (1 << 128) - 1).to_bytes(16, "big")

    rks = _round_keys(key)
    k1 = dbl(aes128_encrypt(rks, b"\x00" * 16))
    k2 = dbl(k1)
    n = max(1, (len(msg) + 15) // 16)
    last = msg[(n - 1) * 16:]
    if msg and len(msg) % 16 == 0:
        last = bytes(a ^ b for a, b in zip(last, k1))
    else:
        last = last + b"\x80" + b"\x00" * (15 - len(last))
        last = bytes(a ^ b for a, b in zip(last, k2))
    c = b"\x00" * 16
    for i in range(n - 1):
        c = aes128_encrypt(rks, bytes(a ^ b for a, b in
                                      zip(c, msg[i * 16:i * 16 + 16])))
    return aes128_encrypt(rks, bytes(a ^ b for a, b in zip(c, last)))


# -- m22000 -----------------------------------------------------------------

def pmk_from_psk(psk: bytes, essid: bytes) -> bytes:
    return hashlib.pbkdf2_hmac("sha1", psk, essid, 4096, 32)


def compute_pmkid(pmk: bytes, mac_ap: bytes, mac_sta: bytes) -> bytes:
    return hmac.new(pmk, b"PMK Name" + mac_ap + mac_sta,
                    hashlib.sha1).digest()[:16]


def compute_mic(pmk: bytes, keyver: int, m: bytes, n: bytes,
                eapol: bytes) -> bytes:
    if keyver in (1, 2):
        ptk = hmac.new(pmk, PRF_LABEL_V12 + m + n + b"\x00",
                       hashlib.sha1).digest()
        alg = hashlib.md5 if keyver == 1 else hashlib.sha1
        return hmac.new(ptk[:16], eapol, alg).digest()[:16]
    if keyver == 3:
        ptk = hmac.new(pmk, PRF_LABEL_V3 + m + n + b"\x80\x01",
                       hashlib.sha256).digest()
        return aes_cmac(eapol, ptk[:16])
    raise ValueError(f"unknown keyver {keyver}")


def pairs(mac_ap: bytes, mac_sta: bytes, anonce: bytes, snonce: bytes):
    """MAC and nonce pairs in min order, and where the AP nonce sits."""
    m = mac_ap + mac_sta if mac_ap < mac_sta else mac_sta + mac_ap
    if snonce[:6] < anonce[:6]:
        return m, snonce + anonce, 32
    return m, anonce + snonce, 0


def nc_variants(anonce: bytes, nc: int):
    """The AP nonce's last 4 bytes: exact, then +/-i little- and
    big-endian for i = 1 .. nc/2+1 (the reference server's order)."""
    le = struct.unpack_from("<I", anonce, 28)[0]
    be = struct.unpack_from(">I", anonce, 28)[0]
    yield anonce[28:32]
    for i in range(1, (nc >> 1) + 2):
        yield struct.pack("<I", (le + i) & 0xFFFFFFFF)
        yield struct.pack("<I", (le - i) & 0xFFFFFFFF)
        yield struct.pack(">I", (be + i) & 0xFFFFFFFF)
        yield struct.pack(">I", (be - i) & 0xFFFFFFFF)


def cracks(line: Line, pmk: bytes, nc: int) -> bool:
    """Whether ``pmk`` opens ``line`` within the nonce-error window ``nc``."""
    if line.hash_type == TYPE_PMKID:
        return compute_pmkid(pmk, line.mac_ap, line.mac_sta) == line.pmkid_or_mic
    keyver = line.keyver
    if keyver not in (1, 2, 3):
        return False
    m, n, off = pairs(line.mac_ap, line.mac_sta, line.anonce,
                      line.eapol[17:49])
    for last4 in nc_variants(line.anonce, nc):
        nv = n[:off + 28] + last4 + n[off + 32:]
        if compute_mic(pmk, keyver, m, nv, line.eapol) == line.pmkid_or_mic:
            return True
    return False


def found_psk(line: Line, candidates, nc: int, pmk_of) -> bytes:
    """The first of ``candidates`` that cracks ``line``, or None.
    ``pmk_of(psk, essid)`` supplies PMKs (so callers can share them)."""
    for psk in candidates:
        if 8 <= len(psk) <= 63 and cracks(line, pmk_of(psk, line.essid), nc):
            return psk
    return None
