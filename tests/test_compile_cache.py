"""Where the persistent compilation cache goes (repro.compile_cache)."""
import os

import jax

from repro import compile_cache

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _record_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_dir_is_fixed_and_git_ignored(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == calls["jax_compilation_cache_dir"]
    assert got == os.path.join(_ROOT, ".jax_cache")
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
