"""The persistent compilation cache helper (repro.launch.compile_cache)."""
import jax
import pytest

from repro.launch.compile_cache import (DEFAULT_CACHE_DIR, REPO_ROOT,
                                        enable_compile_cache)


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_is_used_and_nothing_else_set(monkeypatch, tmp_path,
                                                    cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_inside_the_checkout(monkeypatch, tmp_path,
                                              cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)      # not resolved from the working dir
    path = enable_compile_cache()
    assert path == str(DEFAULT_CACHE_DIR) == str(
        REPO_ROOT / "artifacts" / "jax_cache")
    assert (REPO_ROOT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
