"""Launch-layer tests: the compile-cache directory."""

import jax
import pytest


@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` is the only directory used when set;
    otherwise the cache lives at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(compile_cache.__file__).resolve().parents[3]
                   / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
