"""The reduction from a profiler trace to busy seconds, top operations and
named idle gaps: on intervals made by hand, and on a small trace recorded on
the chip (``record_trace.py``)."""
import os

import pytest

from chipbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.xplane.pb")


def test_union():
    assert xplane.union([(5, 20), (0, 10), (40, 50), (41, 42)]) == \
        [[0, 20], [40, 50]]


def test_reduce_by_hand():
    device = {0: [("a", 0, 10), ("b", 5, 20), ("a", 40, 50)],
              1: [("a", 0, 30)]}
    host = [("chipbench.traced_window", 0, 60),
            ("chipbench.step_call", 20, 40)]
    out = xplane.reduce_events(device, host)
    assert out["window_s"] == pytest.approx(60e-9)
    # chip 0 is busy 0-20 and 40-50, chip 1 0-30: the mean of 30 and 30
    assert out["busy_s_by_chip"] == {"0": pytest.approx(30e-9),
                                     "1": pytest.approx(30e-9)}
    assert out["busy_s"] == pytest.approx(30e-9)
    # "a" ran three times on two chips: 1.5 a chip, rounded to even
    ops = dict(out["device_ops"])
    assert ops["a x2"] == pytest.approx((10 + 10 + 30) / 2 * 1e-9)
    assert ops["b x0"] == pytest.approx(15 / 2 * 1e-9)
    # chip 0's gap 20-40 lies under step_call; its gap 50-60 under nothing;
    # chip 1's gap 30-60 is covered for a third only
    gaps = dict(out["idle_gaps"])
    assert gaps["step_call"] == pytest.approx(20 / 2 * 1e-9)
    assert gaps["unattributed"] == pytest.approx((10 + 30) / 2 * 1e-9)


def test_op_label():
    hlo = ("%copy.95 = bf16[4,2048,32,64]{3,2,1,0:T(8,128)(2,1)} "
           "copy(bf16[4,2048,32,64]{1,3,2,0:T(8,128)(2,1)} %caches_23__0_.1)")
    assert xplane.op_label(hlo) == "copy bf16[4,2048,32,64]"
    hlo = ("%fusion.3682.remat = (f32[8,1024,51200]{2,1,0}, bf16[8,1024]{1,0})"
           " fusion(bf16[51200,2048]{1,0} %convert.860), kind=kOutput")
    assert xplane.op_label(hlo) == "fusion f32[8,1024,51200]"
    assert xplane.op_label("%multiply_reduce_fusion.73.clone = f32[2048]{0} "
                           "fusion()") == "multiply_reduce_fusion f32[2048]"
    assert xplane.op_label("a") == "a"


def test_window_clips_events():
    device = {0: [("a", 0, 100)]}
    host = [("chipbench.traced_window", 10, 60)]
    out = xplane.reduce_events(device, host)
    assert out["busy_s"] == pytest.approx(50e-9) == out["window_s"]
    assert out["idle_gaps"] == []


def test_no_window_span_falls_back_to_the_events():
    out = xplane.reduce_events({0: [("a", 5, 10), ("a", 20, 25)]}, [])
    assert out["window_s"] == pytest.approx(20e-9)
    assert out["busy_s"] == pytest.approx(10e-9)


def test_program_runs_by_hand():
    modules = {0: [("jit_decode(123)", 0, 10), ("jit_decode(123)", 20, 32),
                   ("jit_prefill(9)", 40, 70)],
               1: [("jit_decode(123)", 55, 65)]}
    host = [("chipbench.traced_window", 0, 60)]
    out = xplane.reduce_events({0: [("a", 0, 10)], 1: []}, host, modules)
    # only runs that lie wholly inside the window; fingerprints dropped
    assert out["program_runs"] == {
        "jit_decode": [pytest.approx(10e-9), pytest.approx(12e-9)]}
    assert xplane.reduce_events({0: [("a", 0, 10)]}, host)[
        "program_runs"] == {}


def test_empty_trace_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce_events({}, [])
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce_events({0: []}, [])


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    """Three steps of a jitted matmul chain with 20 ms pauses between them
    (see record_trace.py), on one TPU v5e."""
    device, host, _ = xplane.read_trace(DATA)
    assert list(device) == [0] and len(device[0]) > 0
    names = {n for n, _, _ in host}
    assert {"chipbench.traced_window", "chipbench.step_call",
            "chipbench.step_wait", "chipbench.pause"} <= names
    out = xplane.reduce_events(device, host)
    assert 0 < out["busy_s"] < out["window_s"]
    # three pauses of 20 ms each are inside the window, and idle
    assert out["window_s"] > 0.06
    gaps = dict(out["idle_gaps"])
    assert gaps["pause"] >= 0.055
    assert gaps["pause"] == max(gaps.values())
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert len(out["device_ops"]) <= 10
    # 8 matmul+tanh fusions a step, three steps
    top = out["device_ops"][0][0]
    assert top.startswith("convolution_tanh_fusion bf16[2048,2048] x")


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_program_runs():
    """The same trace: ``jit_work`` ran three times, 0.72 ms each.  The
    device's clock lies a millisecond before the host's in this trace, so
    the first run starts before the window's span and is left out."""
    device, host, modules = xplane.read_trace(DATA)
    assert [len(v) for v in modules.values()] == [3]
    out = xplane.reduce_events(device, host, modules)
    assert list(out["program_runs"]) == ["jit_work"]
    runs = out["program_runs"]["jit_work"]
    assert len(runs) == 2 and runs == sorted(runs)
    assert all(r == pytest.approx(0.72e-3, rel=0.01) for r in runs)
