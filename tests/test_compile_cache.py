"""`repro.launch.cache.use_compile_cache`: where JAX's persistent
compilation cache goes."""

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def cache_config():
    """Restore the process-wide cache option after each test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_is_left_to_jax(monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # nothing set


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch,
                                                      cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache.jax, "default_backend", lambda: "tpu")
    first = cache.use_compile_cache()
    assert first == str(cache.CACHE_DIR)
    assert cache.CACHE_DIR.name == ".jax_cache"
    assert (cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == first
    assert cache.use_compile_cache() == first  # same path every call


def test_cpu_backend_gets_no_default_cache(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache.jax, "default_backend", lambda: "cpu")
    assert cache.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
