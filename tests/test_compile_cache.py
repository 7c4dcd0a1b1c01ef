"""Where the persistent compilation cache lives (`repro.compile_cache`)."""

from __future__ import annotations

import jax
import pytest

from repro import compile_cache

_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture
def restore_config():
    """Leave the process's cache settings as they were: a cache turned on
    here would persist every later test's compiles."""
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_environment_directory_wins(tmp_path, monkeypatch, restore_config):
    target = tmp_path / "from-env"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(target))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(target)
    # JAX reads the variable itself; the helper must not override it
    assert jax.config.jax_compilation_cache_dir is None
    assert target.is_dir()


def test_default_directory_is_fixed_in_the_checkout(monkeypatch, restore_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()  # no temp names
    assert first == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
