"""Placement of the persistent XLA compilation cache
(settings.configure_compile_cache): the environment places it when it
says so, otherwise one fixed path inside the checkout — the path is
part of the cache key, so it may never move between runs."""

import os
import tempfile

import jax
import pytest

from ratelimit_tpu import settings


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_env_places_the_cache_and_code_sets_no_directory(
    monkeypatch, config_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert settings.configure_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(config_updates)
    # Every serving kernel is cached, however fast it compiled.
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in config_updates


def test_unset_env_uses_the_fixed_in_checkout_path(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = settings.configure_compile_cache()
    second = settings.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo, ".jax_cache")
    assert dict(config_updates)["jax_compilation_cache_dir"] == first
    # Never a temp name: nothing of the path may change between runs.
    assert os.path.dirname(first) != tempfile.gettempdir()


def test_the_knob_is_gone():
    assert not hasattr(settings.Settings(), "tpu_compile_cache_dir")
