"""The persistent compile cache follows JAX_COMPILATION_CACHE_DIR, and
otherwise sits at one fixed path inside the checkout."""
import os

import jax
import pytest

from repro.compile_cache import ENV_VAR, configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_honours_env_var(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first = configure_compile_cache()
    second = configure_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
