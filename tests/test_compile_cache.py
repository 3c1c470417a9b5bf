"""The persistent compilation cache goes where the environment says, or
to the one fixed ``<repo>/.jax_cache``."""
import pathlib

import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_environment_moves_the_cache(monkeypatch, tmp_path,
                                     restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == repo / ".jax_cache"
    assert enable_compile_cache() == str(repo / ".jax_cache")
    assert enable_compile_cache() == str(repo / ".jax_cache")   # stable
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
