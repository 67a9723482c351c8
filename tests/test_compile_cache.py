"""The entry points' compile cache goes where JAX_COMPILATION_CACHE_DIR
says, else to one fixed, git-ignored directory in the checkout; its key
holds the program's metadata, so a cached executable keeps its own
spans."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src import compilation_cache

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_compilation_cache_include_metadata_in_key",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_dir():
    prev = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


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
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_cached_executable_keeps_its_own_spans(monkeypatch, tmp_path,
                                               restore_cache_dir):
    """Two programs that differ only in a span's name: the second is
    compiled anew, not loaded with the first's op_names."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CACHE_DIR", tmp_path)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()

    def program(span):
        def f(x):
            with jax.named_scope(span):
                return jnp.sin(x) * 2
        return jax.jit(f)

    x = jnp.ones(8)
    first = program("model/first").lower(x).compile().as_text()
    second = program("model/second").lower(x).compile().as_text()
    assert any(tmp_path.iterdir())
    assert "model/first" in first
    assert "model/second" in second and "model/first" not in second
