"""Where the entry points put JAX's persistent compile cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_cache_dir_is_the_env_or_the_checkout(monkeypatch, cache_dir_config,
                                              env):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    else:
        # JAX reads the variable itself; the helper sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.enable_compile_cache() == env
        assert jax.config.jax_compilation_cache_dir == before
