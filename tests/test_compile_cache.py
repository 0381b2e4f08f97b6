"""Placement of jax's persistent compilation cache (enable_compile_cache).

``JAX_COMPILATION_CACHE_DIR`` wins and nothing in code sets another
directory; otherwise ``--compile-cache DIR``; otherwise the fixed
``<checkout>/.jax_cache``.  tests/conftest.py turns the cache off for every
other test; these turn it on around themselves and never compile into the
checkout's own cache.
"""

import os

import jax
import pytest

from mpi_cuda_process_tpu import cli, serving
from mpi_cuda_process_tpu.plan import DEFAULT_COMPILE_CACHE


@pytest.fixture
def cache_on(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", True)
    yield monkeypatch
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    compilation_cache.reset_cache()


def _run_heat2d(extra=()):
    cfg = cli.config_from_args(
        ["--stencil", "heat2d", "--grid", "16,16", "--iters", "4",
         "--seed", str(len(extra))] + list(extra))
    cli.run(cfg)
    return cfg


def test_off_in_tests_by_default():
    assert not jax.config.jax_enable_compilation_cache
    assert cli.enable_compile_cache("/nonexistent/never-created") is None


def test_compile_cache_flag_populates_persistent_cache(cache_on, tmp_path):
    cache = tmp_path / "xla-cache"
    cfg = _run_heat2d(["--compile-cache", str(cache)])
    assert cfg.compile_cache == str(cache)
    assert cache.is_dir() and len(os.listdir(cache)) > 0, \
        "--compile-cache must land compiled executables on disk"


def test_env_dir_wins_over_flag_for_cli_and_engine(cache_on, tmp_path):
    env_dir = tmp_path / "env-cache"
    flag_dir = tmp_path / "flag-cache"
    cache_on.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    # what jax itself does with the variable when it is imported
    jax.config.update("jax_compilation_cache_dir", str(env_dir))
    _run_heat2d(["--compile-cache", str(flag_dir)])
    assert jax.config.jax_compilation_cache_dir == str(env_dir)
    assert env_dir.is_dir() and os.listdir(env_dir)
    assert not flag_dir.exists()
    eng = serving.ServingEngine(telemetry_dir=str(tmp_path / "tel"),
                                compile_cache=str(flag_dir))
    try:
        assert eng.compile_cache == str(env_dir)
    finally:
        eng.close()
    assert not flag_dir.exists()


def test_default_is_the_fixed_checkout_dir(cache_on):
    # placement only: nothing compiles while the default is in force
    assert cli.enable_compile_cache(None) == DEFAULT_COMPILE_CACHE
    assert jax.config.jax_compilation_cache_dir == DEFAULT_COMPILE_CACHE
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_COMPILE_CACHE == os.path.join(repo, ".jax_cache")
