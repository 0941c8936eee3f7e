"""Where ``enable_compile_cache`` puts JAX's persistent compilation
cache.  No test here compiles, and each restores the config it touched,
so the rest of the process keeps the cache off."""

import os

import jax
import pytest

from repro.launch import compile_cache as cc


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_var_wins_and_the_code_sets_no_directory(monkeypatch, tmp_path,
                                                     cache_config):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_accelerator_default_is_one_fixed_ignored_dir(monkeypatch,
                                                      cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(cc.jax, "default_backend", lambda: "tpu")
    path = cc.enable_compile_cache()
    assert path == cc.DEFAULT_DIR == os.path.join(cc.REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(cc.REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_without_env_var_leaves_the_cache_off(monkeypatch, cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(cc.jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
