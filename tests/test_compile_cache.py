"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.
``jax.config.update`` is recorded, not applied, so no test turns the
cache on."""

from pathlib import Path

import jax

from repro.launch import compile_cache


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_environment_variable_is_left_to_jax(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.enable_compile_cache() == "/somewhere/cache"
    assert calls == []


def test_default_is_fixed_path_in_checkout(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    want = str(checkout / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want   # same every call
    assert calls == [("jax_compilation_cache_dir", want)] * 2
