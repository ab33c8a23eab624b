"""The persistent compilation cache sits at one fixed place per checkout."""
import os

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def cache_config():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


def test_defaults_to_fixed_dir_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # the directory is part of every entry's key: a second call, as a second
    # entry point in the same process makes, must not move it
    assert compile_cache.enable_compile_cache() == want


def test_env_dir_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None     # set nothing


def test_no_in_repo_cache_on_cpu(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
