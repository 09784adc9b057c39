"""Where the persistent compilation cache lives (launch/compile_cache.py)."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as cc

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def config_updates(monkeypatch):
    """Record jax.config updates instead of applying them: the test process
    must not start caching its own compiles."""
    calls: dict = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_default_dir_is_the_checkouts(monkeypatch, config_updates):
    monkeypatch.delenv(cc.ENV, raising=False)
    assert cc.cache_dir() == CHECKOUT / ".jax_cache"
    assert cc.enable_compilation_cache() == CHECKOUT / ".jax_cache"
    assert config_updates["jax_compilation_cache_dir"] == str(
        CHECKOUT / ".jax_cache")


def test_env_dir_is_honoured_and_never_overridden(monkeypatch, tmp_path,
                                                  config_updates):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    assert cc.enable_compilation_cache() == tmp_path
    assert "jax_compilation_cache_dir" not in config_updates
    # every executable persists, whichever directory holds them
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0
    assert config_updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_cache_entries_counts_executables(tmp_path):
    assert cc.cache_entries(tmp_path / "absent") == 0
    for name in ("jit_a-1-cache", "jit_b-2-cache", "notes.txt"):
        (tmp_path / name).write_text("x")
    assert cc.cache_entries(tmp_path) == 2
