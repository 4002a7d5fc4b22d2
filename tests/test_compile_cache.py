"""The persistent compile cache lives in one fixed place."""
import jax

from repro import compile_cache


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prior = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prior


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == \
            compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
    assert compile_cache.DEFAULT_DIR.endswith("/.jax_cache")
