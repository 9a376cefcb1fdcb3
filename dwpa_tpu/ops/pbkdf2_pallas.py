"""Pallas TPU kernel for the PBKDF2-HMAC-SHA1 hot loop.

Reference semantics: ``PMK = PBKDF2-HMAC-SHA1(psk, essid, 4096, 32)``
(web/common.php:179).  The pure-XLA formulation (ops/pbkdf2.py) expresses
the 4096-iteration loop as a ``lax.fori_loop`` whose carry is ten [2, B]
uint32 arrays; measured on a v5e chip that plateaus near ~48k PMK/s
because the carry round-trips through memory every iteration.  This
kernel instead runs the *entire* loop inside one Pallas program per batch
tile, so the SHA-1 state lives in vector registers for all 4096
iterations and the only HBM traffic is the initial states in and the
final accumulators out.

Layout: the two PBKDF2 output blocks T1/T2 (a 32-byte PMK needs both)
are folded into extra batch *lanes* rather than a leading axis — lane i
computes T1 for candidate i, lane B+i computes T2.  Each Pallas program
owns a (TILE, 128) lane tile; per 32-bit word that is TILE/8 vector
registers, giving the VPU independent work to hide ALU latency across
the serial SHA-1 round dependency chain.

The kernel reuses the generic unrolled ``sha1_compress`` /
``hmac_sha1_20`` ops — inside Pallas they trace to the same straight-line
uint32 arithmetic, just on register-resident (TILE, 128) tiles.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hmac import (
    hmac_sha1_20,
    hmac_sha1_20_hoisted,
    hmac_sha1_20_prologue,
    hmac_sha1_blocks,
    hmac_sha1_precompute,
)
from .sha1 import sha1_compress_rolled

# Lane-tile sublane count per Pallas program.  (TILE, 128) uint32 words;
# TILE=32 -> 4 vregs per word -> 4-way independent chains per VPU op.
# Swept on hardware (r3): 32 > 64 > 16 > 128 with the hoisted loop body
# (237.8k / 234.0k / 213.1k / 185.2k PMK/s at B=128k).
DEFAULT_TILE = 32


def _loop_kernel(iterations, unroll, hoist, sin_ref, out_ref):
    """One batch tile: run iterations 1..4096 of the PBKDF2 xor-chain.

    ``sin_ref``: uint32[15, TILE, 128] — rows 0-4 the HMAC ipad state,
    5-9 the opad state, 10-14 U1 (= initial accumulator).
    ``out_ref``: uint32[5, TILE, 128] — the final T accumulator words.
    """
    s = sin_ref[:]
    ist = tuple(s[i] for i in range(5))
    ost = tuple(s[5 + i] for i in range(5))
    u1 = tuple(s[10 + i] for i in range(5))
    if hoist:
        # Hoist the loop-invariant prefix of both compressions (rounds
        # 0-4 partials over the fixed pad states) out of the loop: ~48 of
        # ~2,700 vector ops per iteration move here, run once — at the
        # cost of 16 extra live words of register pressure (A/B'd on
        # hardware; see BASELINE.md ceiling table).
        pro = hmac_sha1_20_prologue(ist, ost)

        def body(_, carry):
            u, acc = carry[:5], carry[5:]
            nu = hmac_sha1_20_hoisted(pro, u)
            return tuple(nu) + tuple(a ^ x for a, x in zip(acc, nu))

    else:

        def body(_, carry):
            u, acc = carry[:5], carry[5:]
            nu = hmac_sha1_20(ist, ost, u)
            return tuple(nu) + tuple(a ^ x for a, x in zip(acc, nu))

    fin = jax.lax.fori_loop(1, iterations, body, u1 + u1, unroll=unroll)
    out_ref[:] = jnp.stack(fin[5:])


@functools.partial(
    jax.jit,
    static_argnames=(
        "iterations", "tile", "unroll", "interpret", "hoist",
    ),
)
def pbkdf2_sha1_pmk_pallas(
    pw_words,
    salt1,
    salt2,
    *,
    iterations=4096,
    tile=DEFAULT_TILE,
    unroll=1,
    interpret=False,
    hoist=True,
):
    """Derive 32-byte PMKs for a packed password batch on TPU via Pallas.

    ``pw_words``: uint32[B, 16] zero-padded 64-byte HMAC key blocks
    (utils/bytesops.pack_passwords_be).  ``salt1``/``salt2``: uint32[16]
    pre-padded single-block salt messages for ``essid || INT32_BE(i)``
    (models/m22000.essid_salt_blocks), or uint32[B, 16] for PER-LANE
    salts (mixed-ESSID fused batches: lane b hashes its own ESSID).  The
    salt only enters the prologue's U1 computation — the first-iteration
    message block changes from broadcast scalars to [B] columns — so the
    register-resident 4096-iteration loop body, and with it the kernel's
    register pressure, is byte-identical in both modes (the hardware
    tile sweep from r3 carries over; re-sweeping is advisable but not
    required).  Returns uint32[8, B] PMK words, bit-identical to
    ops/pbkdf2.pbkdf2_sha1_pmk.
    """
    B = pw_words.shape[0]
    pw = [pw_words[:, i] for i in range(16)]
    # The hoisted loop body is a TPU-only perf feature (+4-6% on chip):
    # under interpret mode its closure-carried prologue makes the
    # XLA:CPU lowering pathologically slow (>400 s vs ~28 s measured),
    # so CPU correctness tests run the generic body; the hoisted math
    # itself is pinned CPU-side at the sha1 level (tests/test_ops.py
    # sha1_compress_20 equivalence) and bit-exact vs hashlib on TPU.
    if interpret:
        hoist = False

    # Cold prologue (5 compressions of the 8192): pad states + U1, XLA-side,
    # with the ROLLED compression.  Its run time is noise next to the
    # 8187 in-kernel compressions, while the unrolled form made every
    # step that embeds this kernel compile for ~20-30 s more on the TPU
    # compiler (v5e AOT, PR 21) — the bulk of a cold start.
    kw = {"compress": sha1_compress_rolled}
    ist, ost = hmac_sha1_precompute(pw, **kw)
    if salt1.ndim == 2:
        # Per-lane salts: word i of lane b's first-iteration message is
        # column i of the [B, 16] salt block — same U1 math, broadcast
        # against [B] instead of from a scalar.
        s1 = [[salt1[:, i] for i in range(16)]]
        s2 = [[salt2[:, i] for i in range(16)]]
    else:
        s1 = [[salt1[i] for i in range(16)]]
        s2 = [[salt2[i] for i in range(16)]]
    u1_t1 = hmac_sha1_blocks(ist, ost, s1, **kw)
    u1_t2 = hmac_sha1_blocks(ist, ost, s2, **kw)

    # Fold T into lanes: [2B] = T1 lanes then T2 lanes, padded to the tile.
    # Clamp the tile to the actual lane count (min 8 sublanes — the uint32
    # tiling floor) so small per-device shards don't pad 8x dead work.
    lanes = 2 * B
    tile = max(8, min(tile, -(-lanes // 128)))
    step = tile * 128
    padded = -(-lanes // step) * step
    rows = (
        [jnp.concatenate([w, w]) for w in ist]
        + [jnp.concatenate([w, w]) for w in ost]
        + [jnp.concatenate([a, b]) for a, b in zip(u1_t1, u1_t2)]
    )
    sin = jnp.stack([jnp.pad(r, (0, padded - lanes)) for r in rows])
    sin = sin.reshape(15, padded // 128, 128)

    out = pl.pallas_call(
        functools.partial(_loop_kernel, iterations, unroll, hoist),
        grid=(padded // step,),
        in_specs=[
            pl.BlockSpec((15, tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (5, tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((5, padded // 128, 128), jnp.uint32),
        interpret=interpret,
    )(sin)

    acc = out.reshape(5, padded)[:, :lanes].reshape(5, 2, B)
    # PMK = T1 (20 bytes) || T2[:12] -> 8 big-endian words.
    return jnp.stack(
        [
            acc[0, 0], acc[1, 0], acc[2, 0], acc[3, 0], acc[4, 0],
            acc[0, 1], acc[1, 1], acc[2, 1],
        ]
    )
