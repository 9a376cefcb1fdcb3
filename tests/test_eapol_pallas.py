"""The keyver-1/2 EAPOL MIC kernel (ops/eapol_pallas.py) in interpret mode.

Each case builds a part of keyver-1/2 nets planted with known PSKs and
nonce-counter offsets, runs the kernel over a tiny PMK batch (1,100
lanes: two tiles of 8 x 128, the second mostly padding), and holds it to
the XLA ``eapol_match`` bit for bit and to the host oracle: every planted net is
found at exactly its (NC delta, endian) variant unless the hashline's
``message_pair`` bits gate that variant out.  PMKs come from hashlib
(the oracle's PBKDF2), so no PBKDF2 runs on the device here.
"""

import functools

import jax
import numpy as np
import pytest

from dwpa_tpu import testing as T
from dwpa_tpu.models import hashline as hl
from dwpa_tpu.models import m22000 as m
from dwpa_tpu.ops.eapol_pallas import eapol_mic_pallas
from dwpa_tpu.oracle import m22000 as oracle
from dwpa_tpu.parallel import step as S
from dwpa_tpu.parallel.mesh import default_mesh
from dwpa_tpu.utils import bytesops as bo

ESSID = b"KernelNet"
B = 1100  # lanes: two 8 x 128 tiles, the second part padding
PSKS = [b"alpha-pass", b"bravo-pass", b"charlie-pass", b"delta-pass"]
LANES = (5, 777, 1030, 1099)  # the lane of each PSK; the rest is noise

# (keyver, [(psk index, NC delta, endian, message_pair, key_data, found)]):
# key_data None gives a 121-byte EAPOL frame (3 MIC blocks), b"" a 99-byte
# one (2 blocks); one part's nets share V and E, as ``_partition`` groups.
CASES = {
    "k2_exact_and_nc": (2, [(0, 0, "LE", 0, None, True),
                            (1, 3, "LE", 0, None, True),
                            (2, -2, "BE", 0, None, True)]),
    "k1_exact_and_nc": (1, [(0, 0, "LE", 0, None, True),
                            (1, -3, "LE", 0, None, True),
                            (2, 2, "BE", 0, None, True)]),
    "k2_two_blocks": (2, [(3, 0, "LE", 0, b"", True),
                          (0, 5, "BE", 0, b"", True)]),
    "k1_two_blocks": (1, [(3, 4, "LE", 0, b"", True),
                          (2, -5, "BE", 0, b"", True)]),
    "k2_apless": (2, [(1, 0, "LE", hl.MP_APLESS, None, True),
                      (2, 2, "LE", hl.MP_APLESS, None, False)]),
    "k2_le_only": (2, [(1, 3, "LE", hl.MP_LE, None, True),
                       (3, 3, "BE", hl.MP_LE, None, False)]),
    "k1_be_only": (1, [(0, -1, "BE", hl.MP_BE, None, True),
                       (3, 2, "LE", hl.MP_BE, None, False)]),
}


def _pmks():
    pmk = np.random.default_rng(24).integers(
        0, 2 ** 32, size=(8, B), dtype=np.uint64).astype(np.uint32)
    for psk, lane in zip(PSKS, LANES):
        pmk[:, lane] = bo.be_words(oracle.pmk_from_psk(psk, ESSID))
    return pmk


def _part(name):
    keyver, specs = CASES[name]
    lines = [T.make_eapol_line(PSKS[p], ESSID, keyver=keyver, nc_delta=d,
                               endian=e, message_pair=mp, key_data=kd,
                               seed=f"{name}{i}")
             for i, (p, d, e, mp, kd, _) in enumerate(specs)]
    nets = [m.prep_net(hl.parse(ln), m.DEFAULT_NC) for ln in lines]
    # stacked as the crack steps stack a part: net axis padded to its bucket
    consts = [S._pad_nets([getattr(n, f) for n in nets])
              for f in S._KINDS["eapol"][1]]
    return keyver, specs, lines, nets, consts


def _expected(specs, lines, nets):
    """Every (net, variant, lane) the kernel must find, from the oracle."""
    want = set()
    for n, ((p, d, e, _, _, found), line, net) in enumerate(
            zip(specs, lines, nets)):
        # the oracle searches every variant whatever the gating bits: it
        # opens each planted net, and ``found`` says whether the gated
        # device search may
        psk, delta, endian, _ = oracle.check_key_m22000(line, [PSKS[p]], nc=8)
        assert (psk, delta, endian) == (PSKS[p], d, e if d else None)
        if found:
            want.add((n, net.variants.index((d, e if d else None)), LANES[p]))
    return want


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_xla_and_oracle(name):
    keyver, specs, lines, nets, (prf, eap, tgt) = _part(name)
    pmk = _pmks()
    got = np.asarray(eapol_mic_pallas(pmk, prf, eap, tgt, keyver=keyver,
                                      tile=8, interpret=True))
    assert got.shape == (len(prf), len(nets[0].variants), B)
    xla = jax.jit(functools.partial(m.eapol_match, keyver=keyver))
    ref = np.stack([np.asarray(xla(pmk, prf[i], eap[i], tgt[i]))
                    for i in range(len(prf))])
    np.testing.assert_array_equal(got, ref)
    hits = {tuple(int(x) for x in h) for h in np.argwhere(got[: len(nets)])}
    assert hits == _expected(specs, lines, nets)
    # a bucket-pad row repeats the last net, hits and all
    for row in range(len(nets), len(prf)):
        np.testing.assert_array_equal(got[row], got[len(nets) - 1])


def test_verify_step_runs_the_kernel_on_a_tpu_mesh(monkeypatch):
    """``verify_step`` on a mesh it takes for TPU (steered here) runs the
    kernel over the part, sharded over two devices, and its mask gate
    keeps the bucket-pad row (3 nets padded to 4) out of the count and
    the matrix."""
    monkeypatch.setattr(S, "_STEP_CACHE", {})
    monkeypatch.setattr(S, "_on_tpu", lambda mesh: True)
    monkeypatch.setattr(S, "eapol_mic_pallas",
                        functools.partial(eapol_mic_pallas, interpret=True))
    keyver, specs, lines, nets, consts = _part("k2_exact_and_nc")
    mask = np.arange(len(consts[0])) < len(nets)
    fn = S.verify_step(default_mesh(n=2), "eapol", (keyver,))
    hits, found = fn(_pmks(), mask, *consts)
    found = np.asarray(found)
    want = _expected(specs, lines, nets)
    assert int(hits) == len(want) == 3
    assert {tuple(int(x) for x in h) for h in np.argwhere(found)} == want
