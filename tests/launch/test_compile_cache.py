"""The entry points' compile-cache rule: JAX_COMPILATION_CACHE_DIR wins and
nothing else is set; without it, one fixed directory inside the checkout."""
import jax
import pytest

from repro import local_cache


@pytest.fixture()
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert local_cache.use_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = local_cache.use_compile_cache()
    assert first == local_cache.use_compile_cache()
    assert first == jax.config.jax_compilation_cache_dir
    repo = local_cache.CACHE_DIR.parent
    assert first == str(repo / ".cache" / "jax")
    assert (repo / "pyproject.toml").exists()
