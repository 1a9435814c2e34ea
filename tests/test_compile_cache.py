"""The persistent compilation cache helper: ``JAX_COMPILATION_CACHE_DIR``
wins when set; otherwise one fixed directory at the checkout's root."""
from pathlib import Path

import pytest

import jax

from repro import compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after the helper has set it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    first = compile_cache.enable_compile_cache()
    assert first == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first   # never moves
    ignored = (repo / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
