"""KAT + differential tests for the uint32-lane crypto primitives.

Every primitive is tested against hashlib/hmac (and FIPS-197 / RFC 4493
vectors for AES/CMAC), both scalar and batched, since the m22000 engine
relies on these exact semantics (reference oracle: web/common.php:157-307).
"""

import hashlib
import hmac as py_hmac

import numpy as np
import pytest
import jax.numpy as jnp

from dwpa_tpu.ops import aes, hmac, md5, sha1, sha256
from dwpa_tpu.utils import bytesops as bo


def _digest(state_words, le=False):
    conv = bo.words_to_bytes_le if le else bo.words_to_bytes_be
    return conv([np.asarray(w) for w in state_words])


def test_sha1_kats():
    for msg in [b"", b"abc", b"a" * 63, b"b" * 64, b"c" * 65, b"d" * 1000]:
        got = _digest(sha1.sha1_digest_blocks(bo.message_blocks(msg)))
        assert got == hashlib.sha1(msg).digest(), msg


def test_md5_kats():
    for msg in [b"", b"abc", b"a" * 63, b"b" * 64, b"c" * 65, b"d" * 1000]:
        got = _digest(
            md5.md5_digest_blocks(bo.message_blocks(msg, little_endian=True)), le=True
        )
        assert got == hashlib.md5(msg).digest(), msg


def test_sha256_kats():
    for msg in [b"", b"abc", b"a" * 63, b"b" * 64, b"c" * 65, b"d" * 1000]:
        got = _digest(sha256.sha256_digest_blocks(bo.message_blocks(msg)))
        assert got == hashlib.sha256(msg).digest(), msg


def test_rolled_compress_variants():
    """The rolled (fori_loop) compressions must match the unrolled forms."""
    for msg in [b"abc", b"d" * 150]:
        blocks = bo.message_blocks(msg)
        st = sha1.sha1_init()
        for blk in blocks:
            st = sha1.sha1_compress_rolled(st, blk)
        assert _digest(st) == hashlib.sha1(msg).digest(), msg

        st = sha256.sha256_init()
        for blk in blocks:
            st = sha256.sha256_compress_rolled(st, blk)
        assert _digest(st) == hashlib.sha256(msg).digest(), msg

        st = md5.md5_init()
        for blk in bo.message_blocks(msg, little_endian=True):
            st = md5.md5_compress_rolled(st, blk)
        assert _digest(st, le=True) == hashlib.md5(msg).digest(), msg


def test_rolled_compress_batched():
    msgs = [b"alpha-block-one!", b"beta-block-two!!", b"gamma-block-3!!!"]
    blk = np.stack(
        [np.array(bo.message_blocks(m)[0], np.uint32) for m in msgs]
    )  # [3, 16]
    st = sha1.sha1_compress_rolled(
        sha1.sha1_init((3,)), [blk[:, w] for w in range(16)]
    )
    for i, msg in enumerate(msgs):
        got = bo.words_to_bytes_be([np.asarray(w)[i] for w in st])
        assert got == hashlib.sha1(msg).digest(), msg


def _key_block(key: bytes):
    return bo.be_words(key + b"\x00" * (64 - len(key)))


def _key_block_le(key: bytes):
    return bo.le_words(key + b"\x00" * (64 - len(key)))


def test_hmac_sha1_20():
    key = b"secret-key-0123456789ab"
    msg = b"exactly-twenty-bytes"
    i, o = hmac.hmac_sha1_precompute(_key_block(key))
    got = _digest(hmac.hmac_sha1_20(i, o, bo.be_words(msg)))
    assert got == py_hmac.new(key, msg, hashlib.sha1).digest()


def test_hmac_sha1_blocks_multiblock():
    key = b"\x01" * 32
    msg = b"Pairwise key expansion\x00" + b"\xaa" * 77  # 100 bytes, 2 blocks
    i, o = hmac.hmac_sha1_precompute(_key_block(key))
    got = _digest(
        hmac.hmac_sha1_blocks(i, o, bo.padded_blocks(msg, 64 + len(msg)))
    )
    assert got == py_hmac.new(key, msg, hashlib.sha1).digest()


def test_hmac_md5_blocks():
    key = b"\x02" * 16
    for n in [1, 60, 99, 121, 250]:
        msg = bytes(range(256))[:n]
        i, o = hmac.hmac_md5_precompute(_key_block_le(key))
        got = _digest(
            hmac.hmac_md5_blocks(
                i, o, bo.padded_blocks(msg, 64 + len(msg), little_endian=True)
            ),
            le=True,
        )
        assert got == py_hmac.new(key, msg, hashlib.md5).digest(), n


def test_hmac_sha256_blocks():
    key = b"\x03" * 32
    msg = b"\x01\x00Pairwise key expansion" + b"\xbb" * 78  # 102 bytes
    i, o = hmac.hmac_sha256_precompute(_key_block(key))
    got = _digest(
        hmac.hmac_sha256_blocks(i, o, bo.padded_blocks(msg, 64 + len(msg)))
    )
    assert got == py_hmac.new(key, msg, hashlib.sha256).digest()


def test_hmac_batched():
    """Batched keys must match per-key results (vectorization check)."""
    keys = [bytes([i]) * 32 for i in range(1, 5)]
    msg = b"exactly-twenty-bytes"
    kb = np.stack([np.array(_key_block(k), np.uint32) for k in keys])  # [4,16]
    kb_words = [kb[:, w] for w in range(16)]
    i, o = hmac.hmac_sha1_precompute(kb_words, shape=(4,))
    out = hmac.hmac_sha1_20(i, o, bo.be_words(msg))
    for n, key in enumerate(keys):
        got = bo.words_to_bytes_be([np.asarray(w)[n] for w in out])
        assert got == py_hmac.new(key, msg, hashlib.sha1).digest()


def test_aes128_fips197():
    key = bytes.fromhex("000102030405060708090a0b0c0d0e0f")
    pt = bytes.fromhex("00112233445566778899aabbccddeeff")
    ct = bytes.fromhex("69c4e0d86a7b0430d8cdb78070b4c55a")
    rks = aes.aes128_expand_key([jnp.uint32(b) for b in key])
    out = aes.aes128_encrypt_block(rks, [jnp.uint32(b) for b in pt])
    assert bytes(int(np.asarray(b)) for b in out) == ct


@pytest.mark.parametrize("shape", [(256,), (64, 4), (2, 2, 64)],
                         ids=["swar_4_per_word", "swar_batched", "one_per_word"])
def test_aes_sbox_circuit_is_the_table(shape):
    """SubBytes is the Boyar-Peralta circuit, not a gather: it must equal
    the GF(2^8)-derived table on every byte, in both the four-bytes-per-
    word layout and the one-byte-per-word fallback."""
    x = np.arange(256, dtype=np.uint32).reshape(shape)
    got = np.asarray(aes._sub(jnp.asarray(x)))
    np.testing.assert_array_equal(got, aes.SBOX[x])
    assert all(int(np.asarray(aes._sub(jnp.uint32(v)))) == aes.SBOX[v]
               for v in (0, 1, 0x53, 0xFF))


def test_aes128_cmac_rfc4493():
    key = bytes.fromhex("2b7e151628aed2a6abf7158809cf4f3c")
    m = bytes.fromhex(
        "6bc1bee22e409f96e93d7e117393172a"
        "ae2d8a571e03ac9c9eb76fac45af8e51"
        "30c81c46a35ce411e5fbc1191a0a52ef"
        "f69f2445df4f9b17ad2b417be66c3710"
    )
    vectors = [
        (b"", "bb1d6929e95937287fa37d129b756746"),
        (m[:16], "070a16b46b4d4144f79bdd9dd04a287c"),
        (m[:40], "dfa66747de9ae63030ca32611497c827"),
        (m, "51f0bebf7e3b9d92fc49741779363cfe"),
    ]
    key16 = [jnp.uint32(b) for b in key]
    for msg, want in vectors:
        nfull = len(msg) // 16
        complete = len(msg) > 0 and len(msg) % 16 == 0
        if complete:
            blocks, last = msg[: (nfull - 1) * 16], msg[(nfull - 1) * 16 :]
        else:
            blocks, last = msg[: nfull * 16], msg[nfull * 16 :] + b"\x80"
        last = last + b"\x00" * (16 - len(last))
        mb = [list(blocks[i * 16 : (i + 1) * 16]) for i in range(len(blocks) // 16)]
        out = aes.aes128_cmac(key16, mb, list(last), complete)
        got = bytes(int(np.asarray(b)) for b in out)
        assert got == bytes.fromhex(want), (msg, got.hex())


def test_pack_passwords_be():
    pws = [b"aaaa1234", b"x" * 63, b"12345678"]
    arr = bo.pack_passwords_be(pws)
    assert arr.shape == (3, 16) and arr.dtype == np.uint32
    for i, pw in enumerate(pws):
        want = bo.be_words(pw + b"\x00" * (64 - len(pw)))
        assert list(arr[i]) == want, pw


def test_pbkdf2_sha1_pmk():
    import hashlib
    from dwpa_tpu.ops.pbkdf2 import pbkdf2_sha1_pmk
    from dwpa_tpu.utils.bytesops import padded_blocks

    essid = b"dlink"
    pws = [b"aaaa1234", b"password", b"x" * 63, b"12345678"]
    kb = bo.pack_passwords_be(pws)
    pw_words = [jnp.asarray(kb[:, w]) for w in range(16)]
    import struct

    s1 = padded_blocks(essid + struct.pack(">I", 1), 64 + len(essid) + 4)[0]
    s2 = padded_blocks(essid + struct.pack(">I", 2), 64 + len(essid) + 4)[0]
    pmk_words = pbkdf2_sha1_pmk(pw_words, s1, s2)
    for i, pw in enumerate(pws):
        got = bo.words_to_bytes_be([np.asarray(w)[i] for w in pmk_words])
        want = hashlib.pbkdf2_hmac("sha1", pw, essid, 4096, 32)
        assert got == want, pw


def test_sha1_hoisted_20_byte_specialization():
    """sha1_compress_20 (the PBKDF2 loop's hoisted-prologue form) is
    bit-identical to the generic compression over the fixed 20-byte
    HMAC message shape, for random states and messages — the CPU-side
    pin for the TPU kernel's hoist=True body."""
    import numpy as np

    from dwpa_tpu.ops.hmac import (
        hmac_sha1_20,
        hmac_sha1_20_hoisted,
        hmac_sha1_20_prologue,
    )
    from dwpa_tpu.ops.sha1 import sha1_20_prologue, sha1_compress, sha1_compress_20

    rng = np.random.default_rng(11)

    def rnd5():
        return tuple(
            jnp.asarray(rng.integers(0, 2**32, (9,), dtype=np.uint64).astype(np.uint32))
            for _ in range(5)
        )

    st, m5 = rnd5(), list(rnd5())
    blk = m5 + [0x80000000] + [0] * 9 + [84 * 8]
    for a, b in zip(sha1_compress(st, blk), sha1_compress_20(sha1_20_prologue(st), m5)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ist, ost = rnd5(), rnd5()
    ref = hmac_sha1_20(ist, ost, m5)
    got = hmac_sha1_20_hoisted(hmac_sha1_20_prologue(ist, ost), m5)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
