"""JAX's persistent compile cache: the environment's directory, or one
fixed path inside the checkout that git ignores."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache as cc

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def keep_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_unset_env_puts_cache_at_fixed_ignored_checkout_path(
        monkeypatch, keep_cache_dir):
    monkeypatch.delenv(cc.ENV, raising=False)
    d = cc.enable_compile_cache()
    assert d == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert cc.enable_compile_cache() == d          # same path every run
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_env_dir_wins_and_code_sets_nothing(monkeypatch, keep_cache_dir,
                                             tmp_path):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
