"""Where JAX's persistent compilation cache lands
(`repro.launch.compile_cache`): the directory named by
`JAX_COMPILATION_CACHE_DIR` when it is set, else one fixed, git-ignored
directory inside the checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_unset_env_uses_fixed_dir_in_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    gitignore = (compile_cache.REPO_ROOT / ".gitignore").read_text()
    assert ".jax_cache/" in gitignore.split()


def test_env_dir_is_left_to_jax(cache_config, monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
