"""Compile-cache placement: the environment wins, else one fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX takes the directory from
it and no code may set another.  Otherwise the cache lives at the fixed
``<repo>/.xla_cache`` whatever the cwd or the client's workdir: the
path is part of what makes a later run hit.
"""

import os

import jax
import pytest

from dwpa_tpu.utils import compcache


@pytest.fixture
def cache_calls(monkeypatch):
    """Record every directory enable_compilation_cache sets, and restore
    the suite's own cache directory afterwards."""
    calls = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            calls.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return calls


def test_env_var_wins_and_no_dir_is_set(cache_calls, monkeypatch, tmp_path):
    monkeypatch.setenv(compcache.ENV_VAR, str(tmp_path / "outside"))
    assert compcache.enable_compilation_cache() == str(tmp_path / "outside")
    assert compcache.enable_compilation_cache(str(tmp_path / "x")) == str(
        tmp_path / "outside")
    assert cache_calls == []
    assert not (tmp_path / "x").exists()


@pytest.mark.parametrize("cwd", ["repo", "elsewhere"])
def test_default_is_the_fixed_checkout_path(cache_calls, monkeypatch,
                                            tmp_path, cwd):
    monkeypatch.delenv(compcache.ENV_VAR, raising=False)
    monkeypatch.chdir(compcache.REPO_ROOT if cwd == "repo" else tmp_path)
    got = compcache.enable_compilation_cache()
    assert got == os.path.join(compcache.REPO_ROOT, ".xla_cache")
    assert cache_calls == [got]
    assert os.path.isdir(got)


def test_client_cache_ignores_workdir_and_cwd(cache_calls, monkeypatch,
                                              tmp_path):
    """The client once cached under ``<workdir>/xla_cache`` with a
    relative default workdir, so the cache moved with the cwd."""
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient

    monkeypatch.delenv(compcache.ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)
    TpuCrackClient(ClientConfig(base_url="http://127.0.0.1:9/",
                                workdir="hc_work"),
                   log=lambda *a, **k: None)
    assert cache_calls == [compcache.DEFAULT_DIR]
    assert not (tmp_path / "hc_work" / "xla_cache").exists()


def test_client_honours_env_var(cache_calls, monkeypatch, tmp_path):
    from dwpa_tpu.client.main import ClientConfig, TpuCrackClient

    monkeypatch.setenv(compcache.ENV_VAR, str(tmp_path / "env-cache"))
    TpuCrackClient(ClientConfig(base_url="http://127.0.0.1:9/",
                                workdir=str(tmp_path / "w")),
                   log=lambda *a, **k: None)
    assert cache_calls == []
