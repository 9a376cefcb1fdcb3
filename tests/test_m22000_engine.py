"""Differential tests: device m22000 engine vs the pure-Python oracle.

Fixtures are synthesized (dwpa_tpu/testing.py) with known PSKs, mirroring
the role of the reference client's hardcoded known-PSK challenge gate
(help_crack/help_crack.py:690-725): the engine must crack them from a small
dictionary and agree with the oracle on (psk, nc, endian, pmk).
"""

import pytest

from dwpa_tpu import testing as tfx
from dwpa_tpu.models import hashline as hl
from dwpa_tpu.models.m22000 import M22000Engine
from dwpa_tpu.oracle import m22000 as oracle

BATCH = 64


def small_dict(*planted):
    words = [f"word{i:04d}xx".encode() for i in range(40)]
    out = []
    for i, w in enumerate(words):
        out.append(w)
        for j, p in enumerate(planted):
            if i == 7 * (j + 1):
                out.append(p)
    return out


def crack_one(line, psk):
    eng = M22000Engine([line], batch_size=BATCH)
    founds = eng.crack(small_dict(psk))
    assert len(founds) == 1
    f = founds[0]
    assert f.psk == psk
    # oracle must agree bit-for-bit (pmk + nc semantics)
    o = oracle.check_key_m22000(line, [psk])
    assert o is not None
    assert f.pmk == o[3]
    return f


def test_pmkid_crack():
    psk = b"s3cretpass"
    f = crack_one(tfx.make_pmkid_line(psk, b"TestNet"), psk)
    assert f.nc == 0 and f.endian == ""


@pytest.mark.parametrize("keyver", [1, 2, 3])
def test_eapol_exact(keyver):
    psk = b"hunter2hunter2"
    line = tfx.make_eapol_line(psk, b"MyWifi", keyver=keyver, seed=f"kv{keyver}")
    f = crack_one(line, psk)
    assert f.nc == 0


@pytest.mark.parametrize("delta,endian", [(3, "LE"), (-2, "BE")])
def test_eapol_nonce_error_correction(delta, endian):
    psk = b"correcthorse"
    line = tfx.make_eapol_line(
        psk, b"NCNet", keyver=2, nc_delta=delta, endian=endian, seed=f"nc{delta}{endian}"
    )
    f = crack_one(line, psk)
    assert (f.nc, f.endian) == (delta, endian)
    o = oracle.check_key_m22000(line, [psk])
    assert (o[1], o[2]) == (delta, endian)


def test_apless_message_pair_skips_nc():
    psk = b"exactonly1"
    # ap-less: nonce taken from the AP's own M1, NC must not be searched
    line = tfx.make_eapol_line(
        psk, b"ApLess", keyver=2, message_pair=hl.MP_APLESS, seed="apless"
    )
    eng = M22000Engine([line], batch_size=BATCH)
    assert len(eng.nets[0].variants) == 1
    assert eng.crack(small_dict(psk))[0].psk == psk

    # same net but NC-shifted: gated engine must NOT find it
    shifted = tfx.make_eapol_line(
        psk, b"ApLess", keyver=2, nc_delta=2, endian="LE",
        message_pair=hl.MP_APLESS, seed="apless2",
    )
    shifted = shifted[:-2] + "10"  # keep only the ap-less bit (clear NC-needed)
    eng2 = M22000Engine([shifted], batch_size=BATCH)
    assert eng2.crack(small_dict(psk)) == []


def test_endian_gating_bits():
    psk = b"legatedpass"
    line = tfx.make_eapol_line(
        psk, b"LeNet", keyver=2, nc_delta=1, endian="LE",
        message_pair=hl.MP_LE, seed="gate-le",
    )
    eng = M22000Engine([line], batch_size=BATCH)
    # LE-gated: every non-exact variant must be LE
    assert all(e == "LE" for d, e in eng.nets[0].variants if d != 0)
    assert eng.crack(small_dict(psk))[0].nc == 1


def test_essid_grouping_multi_net():
    essid = b"SharedESSID"
    psk1, psk2 = b"password-one", b"password-two"
    lines = [
        tfx.make_eapol_line(psk1, essid, keyver=2, seed="g1"),
        tfx.make_eapol_line(psk2, essid, keyver=2, seed="g2"),
        tfx.make_pmkid_line(psk1, essid, seed="g3"),
    ]
    eng = M22000Engine(lines, batch_size=BATCH)
    assert len(eng.groups) == 1  # one PBKDF2 pass serves all three nets
    founds = eng.crack(small_dict(psk1, psk2))
    assert sorted(f.psk for f in founds) == sorted([psk1, psk1, psk2])
    assert not eng.groups  # all nets cracked and retired


def test_wrong_passwords_find_nothing():
    line = tfx.make_eapol_line(b"rightpass99", b"NoNet", keyver=2, seed="none")
    eng = M22000Engine([line], batch_size=BATCH)
    assert eng.crack(small_dict()) == []


def test_short_candidates_filtered():
    psk = b"okpass88"
    eng = M22000Engine([tfx.make_pmkid_line(psk, b"Len")], batch_size=BATCH)
    founds = eng.crack([b"short", b"x" * 64, psk])
    assert [f.psk for f in founds] == [psk]


def test_randomized_differential_vs_oracle():
    """Seeded fuzz: random (keyver, NC delta/endian, hint bits, essid and
    psk lengths incl. binary bytes) configurations must crack on device
    exactly when the oracle accepts them, with matching PMK/nc/endian."""
    import random

    rng = random.Random(0xD3AD)
    lines, psks = [], []
    for i in range(14):
        essid = bytes(rng.randrange(1, 256) for _ in range(rng.randrange(1, 33)))
        psk = bytes(rng.randrange(1, 256) for _ in range(rng.randrange(8, 64)))
        if rng.random() < 0.3:
            line = tfx.make_pmkid_line(psk, essid, seed=f"fz{i}")
        else:
            keyver = rng.choice([1, 2, 3])
            delta = rng.choice([0, 0, 1, -1, 2, -2, 4, -4, 5])
            endian = rng.choice(["LE", "BE"])
            mp = 0
            if delta and rng.random() < 0.5:
                mp |= hl.MP_LE if endian == "LE" else hl.MP_BE
            line = tfx.make_eapol_line(psk, essid, keyver=keyver,
                                     nc_delta=delta, endian=endian,
                                     message_pair=mp, seed=f"fz{i}")
        lines.append(line)
        psks.append(psk)

    eng = M22000Engine(lines, batch_size=32)
    chaff = [bytes(rng.randrange(1, 256) for _ in range(10)) for _ in range(40)]
    founds = eng.crack(chaff + psks)
    by_line = {f.line.raw: f for f in founds}
    assert len(founds) == len(lines)
    for line, psk in zip(lines, psks):
        f = by_line[line]
        ref = oracle.check_key_m22000(hl.parse(line), [psk])
        assert ref is not None
        assert (f.psk, f.pmk) == (psk, ref[3])
        assert f.nc == (ref[1] or 0)
        assert (f.endian or "") == (ref[2] or "")


def test_find_gather_compiles_once_per_mesh(monkeypatch):
    """Engines on one mesh and batch shape share the find gather: a
    second engine's find compiles no ``jit_find_gather`` of its own, and
    the gathered PMK is the oracle's."""
    from dwpa_tpu.analysis import watch_compiles

    # every find batch takes the gather path, as a chip-sized batch does
    monkeypatch.setattr(M22000Engine, "SMALL_PMK_BYTES", 0)
    psk = b"gatherpass1"
    line = tfx.make_pmkid_line(psk, b"GatherNet")
    with watch_compiles() as comp:
        crack_one(line, psk)
        crack_one(line, psk)
    # at most one: another test of this process may have compiled it
    assert comp.names.count("jit(find_gather)") <= 1
