"""The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise to one fixed, git-ignored directory in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_setting():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_ignored(monkeypatch, cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert f"{CHECKOUT_CACHE_DIR.name}/" in ignored
