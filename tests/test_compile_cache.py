"""The entry points' compile cache goes where JAX_COMPILATION_CACHE_DIR
says, else to one fixed, git-ignored directory in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", ["/var/cache/jax-elsewhere", None])
def test_use_compile_cache(monkeypatch, restore_cache_dir, env):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # fixed: a second call (another run) picks the same directory
        assert compile_cache.use_compile_cache() == want
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    else:
        # JAX reads the variable itself; the helper changes nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.use_compile_cache() == env
        assert jax.config.jax_compilation_cache_dir == before
