"""``launch.compile_cache``: where the persistent compile cache lives."""

import os

import jax
import pytest

from repro.launch import compile_cache as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_environment_directory_is_used_and_nothing_set(monkeypatch,
                                                       tmp_path,
                                                       cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert C.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_directory(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert C.enable_compile_cache() == want
    assert C.enable_compile_cache() == want          # same path every call
    assert jax.config.jax_compilation_cache_dir == want
