"""The persistent compilation cache helper (repro.launch.compile_cache):
JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed directory
in the checkout."""

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield prev
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_without_env_the_cache_is_the_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
