"""The entry points' persistent compilation cache location
(``repro.launch.cache``): the environment variable wins, else one fixed
directory inside the checkout — never a temporary, per-process or
per-run path, since the path is part of the cache key."""
from pathlib import Path

import jax

from repro.launch import cache


def test_env_variable_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.compile_cache_dir() == (tmp_path, True)
    # JAX reads the variable itself: the helper sets nothing
    assert cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path_without_env(monkeypatch):
    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    first, from_env = cache.compile_cache_dir()
    assert not from_env
    repo = Path(__file__).resolve().parents[1]
    assert first == repo / ".jax_cache"
    assert cache.compile_cache_dir() == (first, False)   # same on every call
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
