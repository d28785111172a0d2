"""``enable_compile_cache``: JAX's own variable wins; otherwise a fixed
directory inside the checkout."""
import pathlib

import jax

from repro.compile_cache import enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_cache_dir_left_to_jax_when_variable_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_fixed_repo_path_when_variable_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path       # same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
