"""Placement of JAX's persistent compilation cache, and the compile meter."""
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.runtime.compile_cache import (ENV_VAR, CompileMeter,
                                         compile_cache_dir,
                                         enable_compile_cache)

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Put the process's cache setting back as it was."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_var_names_the_cache(monkeypatch, tmp_path, cache_config):
    where = str(tmp_path / "from-env")
    monkeypatch.setenv(ENV_VAR, where)
    assert compile_cache_dir() == where
    assert enable_compile_cache() == where
    assert jax.config.jax_compilation_cache_dir == where


def test_default_cache_is_fixed_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first = enable_compile_cache()
    assert first == compile_cache_dir() == enable_compile_cache()
    assert first == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_meter_counts_compile_seconds():
    meter = CompileMeter()
    before = meter.snapshot()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0))
    spent = meter.since(before)
    assert spent["compile_s"] > 0
    assert spent["cache_hits"] == 0
