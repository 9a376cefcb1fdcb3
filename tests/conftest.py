"""Test env: force an 8-device virtual CPU platform.

Mirrors how the driver validates multi-chip sharding: a
``jax.sharding.Mesh`` over 8 virtual CPU devices stands in for a TPU
slice.  The platform is pinned through the config after import as well
as through the environment, so the pin holds even where jax was
imported before pytest started; it must happen before any backend is
initialized.
"""

import os

# DWPA_TEST_TPU=1 keeps the native platform so device-only tests (e.g. the
# full-4096 Pallas bit-exactness check) can run against the real chip.
if os.environ.get("DWPA_TEST_TPU") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) == 8, jax.devices()

# Persist XLA compilations across suite runs: the heavyweight shard_map
# steps dominate suite wall-clock and their HLO is identical run-to-run.
# JAX_COMPILATION_CACHE_DIR, when set, wins over this fixed path.
from dwpa_tpu.utils.compcache import REPO_ROOT, enable_compilation_cache

enable_compilation_cache(os.path.join(REPO_ROOT, ".pytest_xla_cache"))

# Recompilation sentinel (dwpa_tpu.analysis): guards steady-state sweeps
# against per-batch XLA recompiles.  Imported AFTER the platform setup
# above — the plugin pulls in jax.
from dwpa_tpu.analysis.pytest_plugin import (  # noqa: E402,F401
    lock_witness, recompile_sentinel)


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md); soak tests opt out with
    # this marker instead of living outside the tree
    config.addinivalue_line(
        "markers", "slow: long-running soak tests excluded from tier-1")
