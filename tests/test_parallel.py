"""Mesh-sharded crack step on the 8-device virtual CPU mesh.

Exercises the product multi-chip path (parallel/build_crack_step): the
candidate axis split over the "dp" mesh axis, per-shard PBKDF2+verify,
and the psum hits-gate — the TPU mapping of the reference's volunteer
data-parallel work distribution (web/content/get_work.php:96-135).
"""

import jax
import numpy as np

from dwpa_tpu import testing as T
from dwpa_tpu.models import hashline as hl
from dwpa_tpu.models import m22000 as m
from dwpa_tpu.parallel import build_crack_step, default_mesh, shard_candidates
from dwpa_tpu.utils import bytesops as bo

ESSID = b"mesh-essid"
PSK = b"meshpass42"


def _nets():
    return [
        m.prep_net(hl.parse(T.make_pmkid_line(PSK, ESSID, seed="mp1"))),
        m.prep_net(hl.parse(T.make_eapol_line(PSK, ESSID, keyver=2, seed="mp2"))),
        m.prep_net(
            hl.parse(
                T.make_eapol_line(PSK, ESSID, keyver=2, nc_delta=3, endian="LE", seed="mp3")
            )
        ),
    ]


def _batch(n):
    pws = [b"filler%04d" % i for i in range(n)]
    pws[n // 2] = PSK
    return pws


def test_crack_step_on_8_device_mesh():
    mesh = default_mesh()
    assert mesh.size == 8
    nets = _nets()
    s1, s2 = m.essid_salt_blocks(ESSID)
    step = build_crack_step(mesh, nets, s1, s2)

    batch = 16
    pws = _batch(batch)
    pw_words = shard_candidates(mesh, bo.pack_passwords_be(pws))
    hits, found, pmk = jax.block_until_ready(step(pw_words))
    assert int(hits) == 3  # one match per net (exact, exact, NC+3)
    # the sharded PMK comes back reassembled and matches the oracle
    from dwpa_tpu.oracle.m22000 import pmk_from_psk

    got = bo.words_to_bytes_be(np.array(pmk)[:, batch // 2])
    assert got == pmk_from_psk(PSK, ESSID)
    found = np.array(found)
    # the planted PSK's column holds every hit; no other column matches
    assert found[:, :, batch // 2].any(axis=1).all()
    found[:, :, batch // 2] = False
    assert not found.any()


def test_crack_step_matches_single_device():
    """Same founds on the full mesh and a 1-device mesh (determinism)."""
    nets = _nets()
    s1, s2 = m.essid_salt_blocks(ESSID)
    pws = _batch(8)
    pw_words = bo.pack_passwords_be(pws)

    mesh8 = default_mesh()
    step8 = build_crack_step(mesh8, nets, s1, s2)
    _, found8, _ = step8(shard_candidates(mesh8, pw_words))

    mesh1 = default_mesh(n=1)
    step1 = build_crack_step(mesh1, nets, s1, s2)
    _, found1, _ = step1(shard_candidates(mesh1, pw_words))

    np.testing.assert_array_equal(np.array(found8), np.array(found1))


def test_engine_identical_founds_on_1_and_8_device_mesh():
    """The engine product path produces the same founds on any mesh."""
    lines = [
        T.make_pmkid_line(PSK, ESSID, seed="me1"),
        T.make_eapol_line(PSK, ESSID, keyver=2, nc_delta=2, endian="BE", seed="me2"),
    ]
    results = {}
    for n in (1, 8):
        eng = m.M22000Engine(lines, batch_size=16, mesh=default_mesh(n=n))
        founds = eng.crack(_batch(16))
        results[n] = sorted(
            (f.line.pmkid_or_mic, f.psk, f.nc, f.endian, f.pmk) for f in founds
        )
    assert len(results[1]) == 2
    assert results[1] == results[8]


def test_engine_oversize_batch_pads_to_mesh_multiple():
    """A caller-supplied batch larger than batch_size still shards evenly."""
    lines = [T.make_pmkid_line(PSK, ESSID, seed="ob1")]
    eng = m.M22000Engine(lines, batch_size=8, mesh=default_mesh())
    pws = _batch(16) + [b"extra-%02d" % i for i in range(4)]  # 20 candidates
    founds = eng.crack_batch(pws)
    assert [f.psk for f in founds] == [PSK]


def test_multihost_mesh_single_process():
    """Single-process degenerate case: spans all local devices; the
    same dp axis the crack step shards over."""
    from dwpa_tpu.parallel import multihost_mesh

    mesh = multihost_mesh()
    assert mesh.axis_names == ("dp",)
    assert mesh.size == len(jax.devices())


def test_crack_step_bucket_pad_and_reorder():
    """3 same-signature EAPOL nets (bucket-padded to 4) interleaved with
    a PMKID net: exercises the _pad_nets dup-row branch (hits must stay
    an exact count) and the found-row order restoration (each found row
    must belong to the net at that index of the input list)."""
    mesh = default_mesh()
    nets = [
        m.prep_net(hl.parse(T.make_eapol_line(PSK, ESSID, keyver=2, seed="br1"))),
        m.prep_net(hl.parse(T.make_pmkid_line(PSK, ESSID, seed="br2"))),
        m.prep_net(hl.parse(T.make_eapol_line(PSK, ESSID, keyver=2, seed="br3"))),
        m.prep_net(
            hl.parse(
                T.make_eapol_line(
                    PSK, ESSID, keyver=2, nc_delta=2, endian="LE", seed="br4"
                )
            )
        ),
    ]
    s1, s2 = m.essid_salt_blocks(ESSID)
    step = build_crack_step(mesh, nets, s1, s2)
    batch = 16
    hits, found, _ = jax.block_until_ready(
        step(shard_candidates(mesh, bo.pack_passwords_be(_batch(batch))))
    )
    assert int(hits) == 4  # exact: bucket-pad dup rows masked out
    found = np.array(found)
    assert found.shape[0] == 4
    # every net matches exactly the planted column; the PMKID net's row
    # (input index 1) must be the 1-variant row — order was restored
    assert found[:, :, batch // 2].any(axis=1).all()
    assert found[1, 0, batch // 2] and not found[1, 1:, :].any()
    found[:, :, batch // 2] = False
    assert not found.any()


def test_crack_mask_device_generated():
    """crack_mask: on-device iota->digits generation end to end, founds
    identical to the host-packed path, skip/limit slicing honored."""
    psk = b"77345678"  # inside ?d x8
    lines = [T.make_pmkid_line(psk, ESSID, seed="mk1"),
             T.make_eapol_line(psk, ESSID, keyver=2, seed="mk2")]
    eng = m.M22000Engine(lines, batch_size=64, mesh=default_mesh())
    founds = eng.crack_mask("?d?d?d?d?d?d?d?d", skip=77345600, limit=256)
    assert sorted(f.psk for f in founds) == [psk, psk]
    # a slice that excludes the PSK finds nothing
    eng2 = m.M22000Engine(lines, batch_size=64, mesh=default_mesh())
    assert eng2.crack_mask("?d?d?d?d?d?d?d?d", skip=0, limit=128) == []


def test_device_mask_words_matches_host_pack():
    from dwpa_tpu.gen.mask import device_mask_words, mask_words

    for mask, start in (("?d?d?d?d?d?d?d?d", 0),
                        ("?d?d?d?d?d?d?d?d", 99999980),
                        ("ab?l?d", 7),
                        ("?d?d?d?d?d?d?d?d?d?d", 9_999_999_000)):
        dev = np.array(device_mask_words(mask, start, 16))
        ref = bo.pack_passwords_be(list(mask_words(mask, skip=start, limit=16)))
        np.testing.assert_array_equal(dev, ref, err_msg=f"{mask}@{start}")


def test_crack_step_programs_are_named():
    """Each jitted step lowers to a module under its own name (what a
    profile's ``XLA Modules`` line shows), and the verify's ops carry
    the ``verify/<kind>`` scope in their metadata."""
    from dwpa_tpu.parallel import step as S

    lines = [T.make_pmkid_line(PSK, ESSID, seed="nm1"),
             T.make_eapol_line(PSK, ESSID, keyver=2, seed="nm2"),
             T.make_eapol_line(PSK, ESSID, keyver=3, seed="nm3")]
    nets = [m.prep_net(hl.parse(ln)) for ln in lines]
    mesh = default_mesh(n=1)
    s1, s2 = m.essid_salt_blocks(ESSID)
    step = build_crack_step(mesh, nets, s1, s2)
    assert callable(step.verify)
    b = 8
    pw = jax.ShapeDtypeStruct((b, 4), np.uint32)
    pmk = jax.ShapeDtypeStruct((8, b), np.uint32)

    def module(lowered):
        text = lowered.as_text()
        return text[text.index("module @") + 8:].split(" ", 1)[0]

    assert module(S.pmk_step(mesh).lower(pw, s1, s2)) == "jit_pbkdf2_pmk"
    kinds, found = set(), []
    for (kind, static, _), idxs in S._partition(nets).items():
        _, fields, _ = S._KINDS[kind]
        group = [nets[i] for i in idxs]
        consts = (np.ones(S._bucket(len(group)), bool),) + tuple(
            S._pad_nets([getattr(g, f) for g in group]) for f in fields)
        fn = S.verify_step(mesh, kind, static)
        lowered = fn.lower(pmk, *consts)
        assert module(lowered) == f"jit_verify_{kind}"
        assert f"verify/{kind}" in lowered.as_text(debug_info=True)
        found.append(jax.eval_shape(fn, pmk, *consts)[1])
        kinds.add(kind)
    assert kinds == {"pmkid", "eapol", "cmac"}
    struct = tuple((len(idxs), f.shape[1]) for idxs, f in
                   zip(S._partition(nets).values(), found))
    v_max = max(v for _, v in struct)
    asm = S._assemble_step(mesh, struct, v_max, None)
    assert module(asm.lower(*found)) == "jit_verify_assemble"
