"""Analytic ICI/DCN alpha-beta defaults per TPU generation: where a
single chip leaves the collective tables empty,
published link constants back the stage DP's comm terms instead of
abstract placeholders.
"""
import os

import numpy as np
import pytest

from alpa_tpu.mesh_profiling import (COLLECTIVE_KINDS, TPU_GENERATION_SPECS,
                                     analytic_calibration,
                                     calibration_from_file,
                                     detect_tpu_generation,
                                     get_effective_calibration,
                                     merge_calibrations)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_analytic_covers_all_kinds_and_generations():
    for gen in TPU_GENERATION_SPECS:
        cal = analytic_calibration(gen)
        for kind in COLLECTIVE_KINDS:
            alpha, beta = cal.alpha_beta(kind)
            assert alpha > 0 and beta > 0
        assert cal.sec_per_flop(1e12) > 0
    # generation ordering: faster fabric -> smaller beta; faster MXU ->
    # smaller sec/flop
    assert (analytic_calibration("v5p").alpha_beta("all_reduce")[1] <
            analytic_calibration("v5e").alpha_beta("all_reduce")[1])
    assert (analytic_calibration("v5p").sec_per_flop(1e12) <
            analytic_calibration("v5e").sec_per_flop(1e12))
    # DCN fabric is slower than ICI
    ici = analytic_calibration("v5e", "ici").alpha_beta("all_gather")
    dcn = analytic_calibration("v5e", "dcn").alpha_beta("all_gather")
    assert dcn[0] > ici[0] and dcn[1] > ici[1]


@pytest.mark.parametrize("kind,generation", [
    ("TPU v4", "v4"), ("TPU v5 lite", "v5e"), ("TPU v5p", "v5p"),
    ("TPU v6 lite", "v6e")])
def test_detect_generation_from_device_kind(kind, generation):
    assert detect_tpu_generation(kind) == generation
    assert generation in TPU_GENERATION_SPECS


@pytest.mark.parametrize("kind", ["cpu", "TPU v99", "v5e", ""])
def test_detect_generation_unknown_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peak"):
        detect_tpu_generation(kind)


def test_detect_generation_has_no_default_on_this_backend():
    # the CPU mesh the suite runs on is not a TPU generation: asking for
    # the attached device's generation raises instead of assuming one
    with pytest.raises(ValueError):
        detect_tpu_generation()


def test_merge_measured_wins_analytic_fills():
    tpu_db = os.path.join(REPO, "prof_database_tpu.json")
    if not os.path.exists(tpu_db):
        pytest.skip("no TPU profiling DB checked in")
    measured = calibration_from_file(tpu_db)
    assert measured is not None
    # the single-chip DB has dots but (r2 weak #4) no collectives
    merged = merge_calibrations(measured, analytic_calibration("v5e"))
    assert merged.dot_points == measured.dot_points  # measured dots kept
    for kind in COLLECTIVE_KINDS:
        assert merged.alpha_beta(kind) is not None  # analytic filled
    # merged calibration makes a TPU logical mesh fully calibrated
    from alpa_tpu.device_mesh import LogicalDeviceMesh
    mesh = LogicalDeviceMesh(None, np.arange(8).reshape(1, 8),
                             calibration=merged)
    assert mesh.calibrated
    # a 1 MB all-reduce over an 8-wide v5e ICI axis: ring cost in real
    # seconds, order tens of microseconds
    cost = mesh.all_reduce_cost(1 << 20, 1)
    assert 1e-6 < cost < 1e-2, cost


def test_cpu_measured_fits_match_analytic_form():
    """The CPU-mesh measured collective fits follow the analytic
    t = alpha + beta * bytes form: nonnegative alpha, positive beta,
    monotone in size."""
    cpu_db = os.path.join(REPO, "prof_database_cpu8.json")
    if not os.path.exists(cpu_db):
        pytest.skip("no CPU profiling DB checked in")
    cal = calibration_from_file(cpu_db)
    assert cal is not None and cal.collective_ab
    for kind, (alpha, beta) in cal.collective_ab.items():
        assert alpha >= 0 and beta > 0, (kind, alpha, beta)
        assert alpha + beta * 2e6 > alpha + beta * 1e6


def test_effective_calibration_platform_gate():
    # non-TPU platforms get the measured DB untouched (possibly None)
    cal_cpu = get_effective_calibration(platform="cpu")
    # TPU platforms always come back with a full collective table
    cal_tpu = get_effective_calibration(platform="tpu", generation="v5e")
    assert cal_tpu is not None
    for kind in COLLECTIVE_KINDS:
        assert cal_tpu.alpha_beta(kind) is not None
    if cal_cpu is not None:
        assert set(cal_cpu.collective_ab) <= set(cal_tpu.collective_ab)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
