"""Measured VPU integer-throughput ceiling (Pallas microbenchmarks).

The round-2 BASELINE defended the PBKDF2 kernel's ~230k PMK/s/chip with an
*estimated* VPU peak (~6.1 Tops/s from lane-count x clock).  This module
measures what the VPU actually sustains on the op mixes the SHA-1 kernel
is made of: long dependent chains of uint32 add/xor/and/or/shift on
register-resident (TILE, 128) tiles — the same shape, tiling, and ILP
profile as ``ops/pbkdf2_pallas``.

Each mix body is a pure function on a tuple of tile-shaped uint32 arrays
with a hand-counted op cost (``NOPS``); the kernel runs it ``iters`` times
in a ``fori_loop`` and writes a reduction of the carry so nothing folds
away.  element_ops/s = iters x nops x elements / seconds.

The ``sha1_round`` mix is one faithful SHA-1 Ch-round (12 ops: two rotls,
xor-select f, three adds) — its measured rate, combined with the exact op
census in ``ops/opcount.py``, gives the attainable PMK/s ceiling:

    ceiling_pmk_s = sha1_round_ops_per_s / element_ops_per_pmk

Run: ``python -m dwpa_tpu.ops.vpu_probe`` (prints one JSON line).
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import rotl32, u32

K0 = 0x5A827999


def _mix_add(st):
    a, b, c, d, e = st
    return (a + b, b + c, c + d, d + e, e + a)


def _mix_xor(st):
    a, b, c, d, e = st
    return (a ^ b, b ^ c, c ^ d, d ^ e, e ^ a)


def _mix_rotl(st):
    # 5 independent 3-op rotls: measures whether Mosaic lowers
    # (x << n) | (x >> 32-n) to a native rotate (ops/s >> add ceiling)
    # or to three ALU slots (ops/s ~= add ceiling).
    return tuple(rotl32(x, 5 + i) for i, x in enumerate(st))


def _mix_sha1_round(st):
    # One SHA-1 Ch round, exactly as ops/sha1.py emits it.
    a, b, c, d, e = st
    f = d ^ (b & (c ^ d))  # 3 ops
    tmp = rotl32(a, 5) + f + e + u32(K0)  # 3 rotl + 3 add
    return (tmp, a, rotl32(b, 30), c, d)  # 3 rotl


MIXES = {
    # name: (body, element-ops per iteration)
    "add": (_mix_add, 5),
    "xor": (_mix_xor, 5),
    "rotl": (_mix_rotl, 15),
    "sha1_round": (_mix_sha1_round, 12),
}


# Mix applications per loop iteration: big straight-line body so the
# while-loop's scalar bookkeeping vanishes into the vector work, matching
# the real PBKDF2 kernel's ~2,700-op body.
UNROLL = 64


def _probe_kernel(iters, body, x_ref, o_ref):
    st = tuple(x_ref[i] for i in range(x_ref.shape[0]))

    def step(_, s):
        for _ in range(UNROLL):
            s = body(s)
        return s

    fin = jax.lax.fori_loop(0, iters, step, st)
    acc = fin[0]
    for x in fin[1:]:
        acc = acc ^ x
    o_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("mix", "iters", "tile", "grid"))
def _probe(x, *, mix, iters, tile, grid):
    body, _ = MIXES[mix]
    return pl.pallas_call(
        functools.partial(_probe_kernel, iters, body),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((5, tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((tile, 128), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((grid * tile, 128), jnp.uint32),
    )(x)


def _timed(x, mix, iters, tile, grid, reps):
    """Median-of-``reps`` wall seconds, materializing the result on host
    before the clock stops (same rule as bench.py)."""
    import statistics

    import numpy as np

    np.asarray(_probe(x, mix=mix, iters=iters, tile=tile, grid=grid))  # warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(_probe(x, mix=mix, iters=iters, tile=tile, grid=grid))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def measure(mix, *, tile=64, grid=16, iters=20_000, reps=5):
    """Sustained element-ops/s for one mix via differential timing:
    (t(3N) - t(N)) / 2N cancels the fixed dispatch/transfer overhead."""
    import numpy as np

    _, nops = MIXES[mix]
    rng = np.random.default_rng(7)
    x = jnp.asarray(
        rng.integers(0, 2**32, (5, grid * tile, 128), dtype=np.uint64).astype(
            np.uint32
        )
    )
    t1 = _timed(x, mix, iters, tile, grid, reps)
    t3 = _timed(x, mix, 3 * iters, tile, grid, reps)
    elems = grid * tile * 128
    dt = max(t3 - t1, 1e-9)
    return {
        "mix": mix,
        "tile": tile,
        "ops_per_iter": nops,
        "seconds_1x": round(t1, 6),
        "seconds_3x": round(t3, 6),
        "tera_ops_per_s": round(2 * iters * UNROLL * nops * elems / dt / 1e12, 4),
    }


def main():
    dev = jax.devices()[0]
    out = {"device": str(dev), "mixes": {}, "sha1_round_tiles": {}}
    for mix in MIXES:
        out["mixes"][mix] = measure(mix)
    for tile in (8, 16, 32, 64, 128, 256):
        r = measure("sha1_round", tile=tile, grid=max(1, 1024 // tile))
        out["sha1_round_tiles"][str(tile)] = r["tera_ops_per_s"]
    # Attainable PMK/s ceiling from the measured sha1-shaped rate and the
    # exact per-PMK op census.
    from .opcount import pbkdf2_iteration_census

    ops_pmk = 2 * 4095 * pbkdf2_iteration_census(hoisted=True)["alu_ops"]
    rate = out["mixes"]["sha1_round"]["tera_ops_per_s"] * 1e12
    out["element_ops_per_pmk"] = ops_pmk
    out["ceiling_pmk_per_s"] = round(rate / ops_pmk, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
