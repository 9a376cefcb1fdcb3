"""The copied reference against hashlib, published vectors and the
traffic generator's lines (PMKID and keyver 1/2/3)."""

import hashlib
import hmac

import pytest

from benchmark import reference as R
from benchmark import traffic as T


def test_pbkdf2_ieee_802_11i_vector():
    # IEEE 802.11i-2004 H.4.1: passphrase "password", SSID "IEEE"
    assert R.pmk_from_psk(b"password", b"IEEE").hex() == (
        "f42c6fc52df0ebef9ebb4b90b38a5f902e83fe1b135a70e23aed762e9710a12e")


def test_aes_cmac_rfc4493_vectors():
    key = bytes.fromhex("2b7e151628aed2a6abf7158809cf4f3c")
    assert R.aes_cmac(b"", key).hex() == "bb1d6929e95937287fa37d129b756746"
    msg = bytes.fromhex("6bc1bee22e409f96e93d7e117393172a")
    assert R.aes_cmac(msg, key).hex() == "070a16b46b4d4144f79bdd9dd04a287c"
    msg40 = bytes.fromhex(
        "6bc1bee22e409f96e93d7e117393172aae2d8a571e03ac9c9eb76fac45af8e51"
        "30c81c46a35ce411")
    assert R.aes_cmac(msg40, key).hex() == "dfa66747de9ae63030ca32611497c827"


def _mic_by_hashlib(pmk, keyver, line):
    m, n, off = R.pairs(line.mac_ap, line.mac_sta, line.anonce,
                        line.eapol[17:49])
    if keyver == 3:
        ptk = hmac.new(pmk, b"\x01\x00Pairwise key expansion" + m + n
                       + b"\x80\x01", hashlib.sha256).digest()
        return R.aes_cmac(line.eapol, ptk[:16])
    ptk = hmac.new(pmk, b"Pairwise key expansion\x00" + m + n + b"\x00",
                   hashlib.sha1).digest()
    alg = hashlib.md5 if keyver == 1 else hashlib.sha1
    return hmac.new(ptk[:16], line.eapol, alg).digest()[:16]


@pytest.mark.parametrize("kind", ["pmkid", "k1", "k2", "k3"])
def test_generated_lines_crack_by_hashlib_and_reference(kind):
    psk, essid = b"correct horse", b"bench-test"
    g = T._group("t", essid, [kind], [psk], True, {})
    line = R.parse(g.lines[0])
    pmk = hashlib.pbkdf2_hmac("sha1", psk, essid, 4096, 32)
    if kind == "pmkid":
        want = hmac.new(pmk, b"PMK Name" + line.mac_ap + line.mac_sta,
                        hashlib.sha1).digest()[:16]
    else:
        assert line.keyver == int(kind[1])
        want = _mic_by_hashlib(pmk, line.keyver, line)
    assert line.pmkid_or_mic == want
    assert R.cracks(line, pmk, nc=8)
    assert not R.cracks(line, R.pmk_from_psk(b"wrong horse", essid), nc=8)


def test_nc_window_bounds_the_search():
    psk, essid = b"nonce-error-1", b"bench-nc"
    g = T._group("nc", essid, ["k2"], [psk], True, {"k2": 3})
    line = R.parse(g.lines[0])
    pmk = R.pmk_from_psk(psk, essid)
    assert R.cracks(line, pmk, nc=8)       # +/-5 covers a delta of 3
    assert R.cracks(line, pmk, nc=4)       # +/-3
    assert not R.cracks(line, pmk, nc=2)   # +/-2 does not


def test_traffic_is_a_function_of_the_seed():
    params = {"groups": 6, "shapes": [["pmkid"], ["k2", "pmkid"]],
              "same_ap": False, "planted_every": 2, "planted_offset": 1,
              "planted_nets": [0], "nc_delta": {},
              "dict": {"words": 500, "alphabet": "abc123",
                       "min_len": 8, "max_len": 9}}
    a, b = T.build(params, 2 ** 33 + 5), T.build(params, 2 ** 33 + 5)
    c = T.build(params, 2 ** 33 + 6)
    assert a.words == b.words and a.planted == b.planted
    assert [g.lines for g in a.groups] == [g.lines for g in b.groups]
    assert a.words != c.words
    # every seed: the same shapes, sizes and planting pattern
    assert ([len(g.lines) for g in a.groups]
            == [len(g.lines) for g in c.groups])
    assert ([bool(g.planted) for g in a.groups]
            == [bool(g.planted) for g in c.groups] == [0, 1, 0, 1, 0, 1])
    words = set(a.words)
    for g in a.all_groups():
        for psk in g.psks:
            assert (psk in words) == (psk in g.planted)
    assert all(w in words for w in a.planted)
