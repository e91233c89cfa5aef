"""The entry points' persistent compilation cache location
(repro/launch/compile_cache.py)."""
from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable(backend="tpu") == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_ignored_dir_in_checkout(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable(backend="tpu") == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable(backend="tpu") == want   # stable
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cpu_leaves_the_cache_off(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable(backend="cpu") is None
    assert jax.config.jax_compilation_cache_dir == before
