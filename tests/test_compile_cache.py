"""The entry points' compile-cache placement (``repro.launch.cache``)."""
import os

import jax

from repro.launch import cache


def _restoring_cache_dir(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unset_env_uses_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def run():
        first = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        return first, cache.enable_compile_cache()

    first, second = _restoring_cache_dir(run)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo, ".jax_cache")


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    _restoring_cache_dir(run)
