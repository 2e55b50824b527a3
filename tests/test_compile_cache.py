"""Where the entry points put JAX's persistent compilation cache.  The
cache itself is never turned on here: ``jax.config.update`` is recorded,
not called."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_env_dir_wins_and_nothing_is_set(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert updates == []


def test_default_is_the_checkouts_ignored_dir(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert Path(path) == REPO / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", path)]
    assert "/.jax_cache/" in (REPO / ".gitignore").read_text().split()
