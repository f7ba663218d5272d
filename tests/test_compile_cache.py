"""Placement of the persistent compile cache (``repro.common.compile_cache``)."""
import pathlib

import jax
import pytest

from repro.common import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restores the global cache-dir setting the test may change."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_placement_wins_and_sets_nothing(monkeypatch, tmp_path,
                                                 cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_accelerator_default_is_fixed_ignored_dir_in_checkout(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    placed = compile_cache.enable_compile_cache()
    assert placed == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == placed
    # the same path on every call: it is part of each entry's key
    assert compile_cache.enable_compile_cache() == placed
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cpu_programs_are_not_cached_by_default(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
