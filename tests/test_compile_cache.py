"""The entry points' persistent-compile-cache placement."""

import jax

from repro import compile_cache


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable() == got  # same place every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
