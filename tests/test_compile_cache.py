"""Where the entry points keep JAX's persistent compile cache.

`enable_compile_cache` leaves a `JAX_COMPILATION_CACHE_DIR` the caller
set alone, and otherwise points JAX at the fixed `<repo>/.jax_cache`
(the directory is part of the cache key, so it must never move).
"""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_alone(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = compile_cache.REPO_ROOT
    assert (root / "chip_smoke.py").is_file()
    want = str(root / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want  # same path again
    assert jax.config.jax_compilation_cache_dir == want
