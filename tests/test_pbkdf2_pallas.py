"""Differential tests for the Pallas PBKDF2 kernel.

On the CPU test platform the kernel runs in Pallas interpret mode, so the
iteration count is kept tiny; the device path is exercised (and verified
bit-exact against hashlib) by bench.py and the TPU-only test below.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from dwpa_tpu.models.m22000 import essid_salt_blocks
from dwpa_tpu.ops.pbkdf2 import pbkdf2_sha1_pmk
from dwpa_tpu.ops.pbkdf2_pallas import pbkdf2_sha1_pmk_pallas
from dwpa_tpu.utils import bytesops as bo

ON_TPU = jax.devices()[0].platform == "tpu"


def _xla_pmk(pw_words, s1, s2, iterations):
    pw = [pw_words[:, i] for i in range(16)]
    return jnp.stack(
        pbkdf2_sha1_pmk(pw, list(s1), list(s2), iterations=iterations)
    )


def test_pallas_matches_xla_reduced_iterations():
    essid = b"unit-essid"
    s1, s2 = essid_salt_blocks(essid)
    pws = [b"password%02d" % i for i in range(5)]
    pw_words = jnp.asarray(bo.pack_passwords_be(pws))
    ref = np.asarray(_xla_pmk(pw_words, s1, s2, iterations=2))
    got = np.asarray(
        pbkdf2_sha1_pmk_pallas(
            pw_words,
            jnp.asarray(s1),
            jnp.asarray(s2),
            iterations=2,
            tile=8,
            interpret=not ON_TPU,
        )
    )
    np.testing.assert_array_equal(got, ref)


def test_pallas_full_4096_matches_hashlib():
    if not ON_TPU:
        import pytest

        pytest.skip("full-iteration Pallas run needs the TPU (interpret too slow)")
    essid = b"unit-essid"
    s1, s2 = essid_salt_blocks(essid)
    pws = [b"longpassphrase-%04d" % i for i in range(64)]
    pw_words = jnp.asarray(bo.pack_passwords_be(pws))
    out = np.asarray(
        pbkdf2_sha1_pmk_pallas(pw_words, jnp.asarray(s1), jnp.asarray(s2))
    )
    for i in (0, 31, 63):
        ref = hashlib.pbkdf2_hmac("sha1", pws[i], essid, 4096, 32)
        assert bo.words_to_bytes_be(out[:, i]) == ref


def test_tpu_throughput_floor():
    """Regression floor for the hot kernel on real hardware: a drop
    below 150k PMK/s on a v5e chip means a kernel/pipeline regression
    (the rates behind that floor came from an earlier remote setup and
    are unmeasured on a local chip).  TPU-gated — CPU interpret mode
    measures nothing relevant."""
    if not ON_TPU:
        import pytest

        pytest.skip("throughput floor only meaningful on the TPU")
    import time

    from dwpa_tpu import testing as T
    from dwpa_tpu.models.m22000 import M22000Engine

    batch = 65536
    engine = M22000Engine(
        [T.make_pmkid_line(b"not-in-keyspace", b"floor-essid", seed="floor")],
        batch_size=batch,
    )
    n = 4 * batch
    engine.crack_mask("?d?d?d?d?d?d?d?d", skip=n, limit=batch)  # warm/compile
    t0 = time.perf_counter()
    engine.crack_mask("?d?d?d?d?d?d?d?d", skip=0, limit=n)
    rate = n / (time.perf_counter() - t0)
    assert rate > 150_000, f"kernel throughput regressed: {rate:.0f} PMK/s"
