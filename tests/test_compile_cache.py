"""The persistent compilation cache is placed from outside: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when set (and no other
directory), else one fixed directory inside the checkout."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache
from repro.launch.compile_cache import (CHECKOUT_ROOT, ENV_VAR,
                                        setup_compile_cache)

_KNOBS = ("jax_compilation_cache_dir",
          "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    prev = {k: getattr(jax.config, k) for k in _KNOBS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _compile_something():
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()


def test_env_dir_wins_and_nothing_else_is_set(tmp_path, monkeypatch,
                                              restore_cache_config):
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv(ENV_VAR, str(env_dir))
    monkeypatch.setattr(compile_cache, "CHECKOUT_ROOT", tmp_path / "checkout")
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "checkout").exists()


def test_unset_env_uses_fixed_dir_in_checkout(tmp_path, monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_ROOT", tmp_path)
    path = setup_compile_cache()
    assert path == str(tmp_path / ".jax_cache")
    assert setup_compile_cache() == path                # never moves
    _compile_something()
    assert os.listdir(path), "no cache entry written"
    assert os.listdir(tmp_path) == [".jax_cache"]


def test_default_root_is_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert (CHECKOUT_ROOT / "src" / "repro").is_dir()
    assert setup_compile_cache() == str(CHECKOUT_ROOT / ".jax_cache")
