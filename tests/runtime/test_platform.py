"""Entry-point helpers of ``alpa_tpu.platform``."""
import os

import jax
import pytest

from alpa_tpu import platform as alpa_platform

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def cache_dir_config():
    """Restore jax's compile-cache directory after the test."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_dir_is_left_alone(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert alpa_platform.enable_compilation_cache() == str(tmp_path)
    # no directory is set in code where the environment names one
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = alpa_platform.enable_compilation_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert alpa_platform.enable_compilation_cache() == first


def test_import_alpa_tpu_sets_no_compile_cache():
    # only entry points turn the cache on: the suite's own config (set by
    # importing alpa_tpu in conftest.py) carries none of ours
    assert (jax.config.jax_compilation_cache_dir !=
            alpa_platform.DEFAULT_COMPILATION_CACHE_DIR)
