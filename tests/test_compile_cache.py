"""Where ``repro.compile_cache.use_compile_cache`` puts JAX's cache."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, use_compile_cache


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir(monkeypatch, tmp_path, from_env):
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(DEFAULT_DIR)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_default_dir_is_the_checkout_root():
    root = Path(__file__).resolve().parents[1]
    assert DEFAULT_DIR == root / ".jax_cache"
