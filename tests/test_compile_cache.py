"""Where the entry points put JAX's persistent compilation cache: the
directory named by JAX_COMPILATION_CACHE_DIR (left to JAX), else a fixed
``.jax_cache`` at the checkout root. No test here turns the cache on."""
import pathlib

import jax

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_env_var_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir_to_set() is None
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.cache_dir_to_set()
    assert path == str(REPO / ".jax_cache")
    assert compile_cache.cache_dir_to_set() == path
