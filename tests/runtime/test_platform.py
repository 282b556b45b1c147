"""Entry-point helpers of ``alpa_tpu.platform`` and ``bench.py``'s
refusal to measure without a TPU."""
import os

import jax
import pytest

import bench
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


def test_bench_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert bench.main() != 0
    out, err = capsys.readouterr()
    assert out == ""          # no metric line for a run that measured nothing
    assert "needs a TPU" in err


def test_bench_hbm_gate_estimates():
    """The estimator behind bench.py's HBM refusal orders the configs it
    gates: the default fits under the gate, the heavier ones do not."""
    import dataclasses
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import GPTConfig
    good = GPTConfig(hidden_size=2048, num_layers=16, num_heads=32,
                     seq_len=1024, vocab_size=51200, dtype=jnp.bfloat16,
                     remat_blocks=True)
    assert bench.estimate_hbm_gb(good, 8) < bench.HBM_GATE_GB
    for heavier in (dataclasses.replace(good, remat_policy="dots"),
                    dataclasses.replace(good, num_layers=24),
                    dataclasses.replace(good, remat_blocks=False)):
        assert bench.estimate_hbm_gb(heavier, 8) > bench.HBM_GATE_GB
    assert bench.estimate_hbm_gb(good, 16) > bench.HBM_GATE_GB
