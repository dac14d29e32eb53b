"""The persistent compile cache lives where the environment says, else at one
fixed place inside the checkout."""
import importlib
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import CACHE_ENV, DEFAULT_CACHE_DIR, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test points it somewhere."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_honoured_and_nothing_is_set(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_inside_the_checkout(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    monkeypatch.chdir(tmp_path)  # the working directory plays no part
    assert DEFAULT_CACHE_DIR == CHECKOUT / ".jax_cache"
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)  # the same on every call


def test_import_sets_nothing(monkeypatch, cache_dir_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir is None
