"""The one traffic generator: a traffic file's parameters and a seed in,
ESSID groups of hashlines and one dictionary out.

The line builders are copies of ``dwpa_tpu/testing.py``'s and the word
generator of ``chip_smoke.py``'s, kept here so that no change to the
program can change the traffic.

A traffic file (``benchmark/workloads/<traffic>.json``) holds:

- ``groups``: ESSID groups the window may take, leased in this order;
- ``shapes``: a list of group shapes, each a list of net kinds
  (``pmkid``, ``k1``, ``k2``, ``k3``); group ``i`` takes shape
  ``i % len(shapes)``;
- ``same_ap``: whether the nets of a group share one AP (one capture's
  PMKID and M1M2) or are different APs of one ESSID;
- ``planted_every``/``planted_offset``: group ``i`` holds a net whose
  PSK is a dictionary word when ``i % planted_every == planted_offset``;
- ``planted_nets``: ``all`` (every net of a planted group shares the
  PSK, so the unit stops at its find) or a list of net indices that
  share it while the others share a PSK no dictionary holds (so the
  unit runs its whole dictionary);
- ``nc_delta``: per kind, the nonce-error correction its captures need
  (little-endian AP nonce, as a NC-incrementing router writes it);
- ``dict``: ``words``, ``alphabet``, ``min_len``, ``max_len``;
- ``correctness`` (read by the controls and tests, not here): the
  cell's ``control`` (a name in ``faults.CONTROLS``), the
  ``control_traffic`` it runs on (keys of this file that it changes),
  and the ``tiny_seed`` of the CPU tests' tiny runs.

Every seed gives the same sizes, shapes and planting pattern; only the
bytes and the planted positions differ.  One warm-up group per shape
comes first: all of its nets share a PSK planted among the first
dictionary words, so its unit compiles the shape's programs and stops
at its find.
"""

import hashlib
import struct
from dataclasses import dataclass, field

import numpy as np

from . import reference as R

#: key_information of an M2 per key descriptor version
_KEY_INFO = {1: 0x0109, 2: 0x010A, 3: 0x010B}
_KIND_KEYVER = {"k1": 1, "k2": 2, "k3": 3}
#: bit 7 of an EAPOL line's message pair: replay count unchecked (NC needed)
_MP_NC_NEEDED = 0x80
#: unplanted PSKs hold this letter, which no dictionary alphabet holds
_NOT_IN_DICT = b"#"


def rand_bytes(seed: str, n: int) -> bytes:
    out, i = b"", 0
    while len(out) < n:
        out += hashlib.sha256(f"{seed}:{i}".encode()).digest()
        i += 1
    return out[:n]


def random_words(rng, n: int, alphabet: bytes, lo: int, hi: int) -> list:
    alpha = np.frombuffer(alphabet, dtype=np.uint8)
    chars = alpha[rng.integers(0, len(alpha), size=(n, hi))]
    lens = rng.integers(lo, hi + 1, size=n)
    return [row[:k].tobytes() for row, k in zip(chars, lens)]


def pmkid_line(pmk: bytes, essid: bytes, mac_ap: bytes, mac_sta: bytes) -> str:
    return R.serialize(R.TYPE_PMKID, R.compute_pmkid(pmk, mac_ap, mac_sta),
                       mac_ap, mac_sta, essid, message_pair=1)


def eapol_m2(key_information: int, snonce: bytes, key_data: bytes) -> bytes:
    body = struct.pack(">BHH8s32s16s8s8s16sH", 2, key_information, 0,
                       b"\x00" * 7 + b"\x01", snonce, b"\x00" * 16,
                       b"\x00" * 8, b"\x00" * 8, b"\x00" * 16,
                       len(key_data)) + key_data
    return struct.pack(">BBH", 2, 3, len(body)) + body


def eapol_line(pmk: bytes, essid: bytes, keyver: int, mac_ap: bytes,
               mac_sta: bytes, seed: str, nc_delta: int = 0) -> str:
    """An M1M2 line; with ``nc_delta`` the MIC is over the AP nonce plus
    ``nc_delta`` (little-endian) while the line keeps the captured one."""
    anonce = rand_bytes(seed + "anonce", 32)
    snonce = rand_bytes(seed + "snonce", 32)
    eapol = eapol_m2(_KEY_INFO[keyver], snonce, rand_bytes(seed + "rsn", 22))
    real, mp = anonce, 0
    if nc_delta:
        last = struct.unpack_from("<I", anonce, 28)[0]
        real = anonce[:28] + struct.pack("<I", (last + nc_delta) & 0xFFFFFFFF)
        mp |= _MP_NC_NEEDED
    m, n, _ = R.pairs(mac_ap, mac_sta, real, snonce)
    mic = R.compute_mic(pmk, keyver, m, n, eapol)
    return R.serialize(R.TYPE_EAPOL, mic, mac_ap, mac_sta, essid, anonce,
                       eapol, mp)


@dataclass
class Group:
    essid: bytes
    lines: list                  # hashlines, in net order
    psks: list                   # each net's PSK
    planted: list = field(default_factory=list)  # PSKs that are dict words


@dataclass
class Traffic:
    words: list                  # the dictionary, in served order
    warm: list                   # warm-up groups, one per shape
    groups: list                 # window groups, in release order
    planted: list                # every dictionary word that is some PSK

    def all_groups(self):
        return self.warm + self.groups


def _macs(seed: str):
    mac_ap = bytearray(rand_bytes(seed + "ap", 6))
    mac_ap[0] &= 0xFC            # a unicast, globally administered MAC
    mac_sta = bytearray(rand_bytes(seed + "sta", 6))
    mac_sta[0] &= 0xFC
    return bytes(mac_ap), bytes(mac_sta)


def _group(tag: str, essid: bytes, shape: list, psks: list, same_ap: bool,
           nc_delta: dict) -> Group:
    lines, pmks = [], {}
    ap = _macs(tag)
    for i, (kind, psk) in enumerate(zip(shape, psks)):
        if psk not in pmks:
            pmks[psk] = R.pmk_from_psk(psk, essid)
        mac_ap, mac_sta = ap if same_ap else _macs(f"{tag}-{i}")
        if kind == "pmkid":
            lines.append(pmkid_line(pmks[psk], essid, mac_ap, mac_sta))
        else:
            lines.append(eapol_line(pmks[psk], essid, _KIND_KEYVER[kind],
                                    mac_ap, mac_sta, f"{tag}-{i}",
                                    nc_delta.get(kind, 0)))
    return Group(essid, lines, list(psks))


def build(params: dict, seed: int) -> Traffic:
    """The traffic of ``params`` for ``seed`` (any non-negative integer)."""
    rng = np.random.default_rng(seed)
    d = params["dict"]
    alphabet = d["alphabet"].encode()
    if _NOT_IN_DICT in alphabet:
        raise ValueError(f"dict alphabet may not hold {_NOT_IN_DICT!r}")
    words = random_words(rng, d["words"], alphabet, d["min_len"], d["max_len"])
    shapes = params["shapes"]
    n = params["groups"]
    every, offset = params["planted_every"], params["planted_offset"]
    n_planted = sum(1 for g in range(n) if g % every == offset)
    # warm PSKs sit among the first 64 words, window PSKs anywhere after
    warm_pos = rng.choice(min(64, len(words)), len(shapes), replace=False)
    pos = 64 + rng.choice(len(words) - 64, n_planted, replace=False)
    nc_delta = params.get("nc_delta", {})
    same_ap = params["same_ap"]
    tag = f"{seed}"

    def essid(i):
        return b"bench-" + random_words(rng, 1, b"abcdefghijklmnopqrstuvwxyz"
                                        b"0123456789", 8, 8)[0] + b"-%d" % i

    def unplanted(i):
        return random_words(rng, 1, alphabet, d["min_len"] - 1,
                            d["max_len"] - 1)[0] + _NOT_IN_DICT

    warm = []
    for s, shape in enumerate(shapes):
        psk = words[int(warm_pos[s])]
        g = _group(f"{tag}-w{s}", essid(10000 + s), shape, [psk] * len(shape),
                   same_ap, nc_delta)
        g.planted = [psk]
        warm.append(g)
    groups, k = [], 0
    for i in range(n):
        shape = shapes[i % len(shapes)]
        other = unplanted(i)
        psks = [other] * len(shape)
        planted = []
        if i % every == offset:
            psk = words[int(pos[k])]
            k += 1
            which = params["planted_nets"]
            for j in (range(len(shape)) if which == "all" else which):
                psks[j] = psk
            planted = [psk]
        g = _group(f"{tag}-g{i}", essid(i), shape, psks, same_ap, nc_delta)
        g.planted = planted
        groups.append(g)
    planted = [p for g in warm + groups for p in g.planted]
    return Traffic(words, warm, groups, planted)
