"""Placement of JAX's persistent compilation cache (common/compile_cache.py)."""

import jax
import pytest

from repro.common import compile_cache


@pytest.fixture
def cache_dir_option():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_defaults_to_checkout(monkeypatch, cache_dir_option):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_env_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                           cache_dir_option):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
