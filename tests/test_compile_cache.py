"""The persistent compile cache rule (:mod:`repro.compile_cache`)."""

from pathlib import Path

import jax

from repro import compile_cache

_EVERY_PROGRAM = ("jax_persistent_cache_min_compile_time_secs", 0.0)


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.setup() == str(tmp_path / "env")
    assert calls == [_EVERY_PROGRAM]  # no directory set in code


def test_fixed_dir_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recorded_updates(monkeypatch)
    checkout = Path(__file__).resolve().parents[1]
    path = str(checkout / ".jax_cache")
    assert compile_cache.setup() == path
    assert ("jax_compilation_cache_dir", path) in calls
    assert _EVERY_PROGRAM in calls
    assert compile_cache.setup() == path  # never moves
