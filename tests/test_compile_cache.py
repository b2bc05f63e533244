"""Where the entry points' persistent compile cache lands."""

import pytest

from repro.launch import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record the helper's ``jax.config.update`` calls instead of applying
    them, so no test turns the cache on for the rest of the process."""
    calls = {}
    monkeypatch.setattr(
        compile_cache.jax.config, "update", lambda name, value: calls.__setitem__(name, value)
    )
    return calls


def test_environment_directory_is_left_alone(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] < 1.0


def test_tpu_without_environment_uses_the_checkout_directory(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert (compile_cache.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert config_updates["jax_compilation_cache_dir"] == path


def test_cpu_without_environment_places_no_cache(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "cpu")
    assert compile_cache.enable_compile_cache() is None
    assert not config_updates
