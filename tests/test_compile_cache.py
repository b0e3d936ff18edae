"""The entry points' persistent compilation cache has one fixed home."""
import jax

from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache


def test_cache_dir_comes_from_the_environment_or_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", before)
        assert use_compile_cache() == "/elsewhere"
        # the variable is JAX's own: nothing is set over it in code
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE.parent.joinpath("pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
