"""The persistent compile cache: placed from outside, or at one fixed path."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def keep_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_placed_dir_is_left_to_jax(monkeypatch, tmp_path, keep_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unplaced_cache_sits_at_one_fixed_gitignored_path(monkeypatch,
                                                           keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CACHE_DIR == root / ".jax_cache"
    for _ in range(2):  # the same path on every call
        assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
