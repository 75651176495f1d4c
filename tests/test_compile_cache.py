"""The persistent compilation cache is placed from outside or at a fixed
in-checkout path (never a temporary one)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_cache_dir_placement(from_env, tmp_path, monkeypatch, restore_config):
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        # the environment variable is JAX's own: nothing else is set in code
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE_DIR.parent.joinpath("src").is_dir()
        assert compile_cache.enable() == path           # fixed, repeatable
