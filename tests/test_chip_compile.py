"""AOT compiles of the main path for a described v5e chip (no chip needed).

The TPU compiler is installed here and compiles for a chip that is
described and not attached, so these tests catch what interpret-mode
CPU tests cannot: a kernel the compiler refuses (VMEM, unaligned
slices), a program that loses its Pallas kernel, and a step whose
working set does not fit a chip's 16 GB of HBM — the EAPOL verify did
not, before it mapped over nets and NC variants.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports every test file.  The persistent compilation cache is
off around these compiles (a TPU entry written here cannot be read back
without a chip).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dwpa_tpu import testing as T
from dwpa_tpu.models import hashline as hl
from dwpa_tpu.models import m22000 as m
from dwpa_tpu.ops.pbkdf2_pallas import pbkdf2_sha1_pmk_pallas
from dwpa_tpu.parallel import step as st

B = 131072  # per-chip batch of the client and bench on the chip
HBM = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache, topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices[:1]), ("dp",))


def _sds(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < HBM // 4, f"{used / 2**30:.2f} GiB on one chip"


def test_pbkdf2_kernel_compiles(mesh):
    """The kernel with one ESSID's salt for the batch; the per-lane salt
    form compiles inside the fused PMK step below."""
    pw = _sds(mesh, (B, 16), jnp.uint32, P("dp", None))
    salt = _sds(mesh, (16,), jnp.uint32, P())
    compiled = pbkdf2_sha1_pmk_pallas.lower(pw, salt, salt).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_fused_pmk_and_mix_steps_compile(mesh):
    """The fused step (per-lane salts gathered by unit id) and the
    PMK-store ``mix_step`` that assembles its output with cached PMKs."""
    compiled = st.fused_pmk_step(mesh).lower(
        _sds(mesh, (B, 16), jnp.uint32, P("dp", None)),
        _sds(mesh, (B,), jnp.int32, P("dp")),
        _sds(mesh, (8, 16), jnp.uint32, P()),
        _sds(mesh, (8, 16), jnp.uint32, P())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    mix = st.mix_step(mesh).lower(
        _sds(mesh, (8, B // 2), jnp.uint32, P(None, "dp")),
        _sds(mesh, (8, B), jnp.uint32, P(None, "dp")),
        _sds(mesh, (B,), jnp.int32, P("dp"))).compile()
    _fits(mix)


def _multi_bssid_group(monkeypatch):
    """chip_smoke's largest group — 2 EAPOL keyver 2, 2 keyver 3 (CMAC)
    and a PMKID under one ESSID, NC 8 (21 variants each).  The step
    builders place their constants with ``jax.device_put``, which a
    described device cannot hold, so they stay host arrays here and
    become constants of the traced program."""
    essid = b"CompileGroup"
    lines = [T.make_eapol_line(b"password1", essid, keyver=kv, seed=f"c{i}")
             for i, kv in enumerate((2, 2, 3, 3))]
    lines.append(T.make_pmkid_line(b"password1", essid, seed="cp"))
    nets = [m.prep_net(hl.parse(ln), m.DEFAULT_NC) for ln in lines]
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: np.asarray(x))
    return (nets,) + tuple(m.essid_salt_blocks(essid))


def test_multi_bssid_verify_fits_one_chip(mesh, monkeypatch):
    """The verify half of ``build_crack_step`` for that group at the
    full per-chip batch (the PBKDF2 half is the kernel above)."""
    nets, s1, s2 = _multi_bssid_group(monkeypatch)
    step = st.build_crack_step(mesh, nets, s1, s2)
    compiled = jax.jit(step.verify).lower(
        _sds(mesh, (8, B), jnp.uint32, P(None, "dp"))).compile()
    _fits(compiled)


def test_multi_bssid_rules_step_fits_one_chip(mesh, monkeypatch):
    """``build_rules_step`` for that group with wpa.rule's step bucket
    (8, what ``_RulesCtx`` stacks every chunk to): device rules, PBKDF2
    and every net's verify in one program, the chip's slowest compile
    and, before the verify mapped over nets and variants, 14.5 GiB of
    HBM (AOT, PR 21)."""
    from dwpa_tpu.models.m22000 import _RulesCtx
    from dwpa_tpu.rules import parse_rules, wpa_rules_text

    n_steps = _RulesCtx(parse_rules(wpa_rules_text().splitlines())).n_steps
    assert n_steps == 8
    nets, s1, s2 = _multi_bssid_group(monkeypatch)
    step = st.build_rules_step(mesh, nets, s1, s2)
    compiled = jax.jit(step).lower(
        _sds(mesh, (B, 16), jnp.uint32, P("dp", None)),
        _sds(mesh, (B,), jnp.int32, P("dp")),
        _sds(mesh, (st.RULES_CHUNK, n_steps, 3), jnp.int32, P())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
