"""Where the entry points keep the persistent compilation cache."""

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch,
                                                 cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_dir_without_env(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    # one fixed, git-ignored directory at the root of the checkout
    assert CHECKOUT_CACHE.name == ".jax_cache"
    assert (CHECKOUT_CACHE.parent / "chip_smoke.py").is_file()
    ignored = (CHECKOUT_CACHE.parent / ".gitignore").read_text().splitlines()
    assert "/.jax_cache/" in ignored
