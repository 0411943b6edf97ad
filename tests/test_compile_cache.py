"""`use_compile_cache`: the environment places JAX's persistent compilation
cache; without it the cache sits at a fixed path inside the checkout."""
import os

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_places_the_cache(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_default_is_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
    assert str(CHECKOUT_CACHE_DIR) == os.path.join(repo, ".jax_cache")
    assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    # the suite itself never reads or writes the cache
    assert not jax.config.jax_enable_compilation_cache
