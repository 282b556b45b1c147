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
    # chip 1's gap 30-60 is covered for a third, and split there
    gaps = dict(out["idle_gaps"])
    assert gaps["step_call"] == pytest.approx((20 + 10) / 2 * 1e-9)
    assert gaps["unattributed"] == pytest.approx((10 + 20) / 2 * 1e-9)


def test_shortest_cover():
    spans = [("outer", 0, 100), ("child", 10, 30), ("grandchild", 15, 20),
             ("other-thread", 25, 45), ("late", 120, 130), ("empty", 7, 7)]
    assert xplane.shortest_cover(spans) == [
        (0, 10, "outer"), (10, 15, "child"), (15, 20, "grandchild"),
        (20, 30, "child"), (30, 45, "other-thread"), (45, 100, "outer"),
        (120, 130, "late")]
    assert xplane.shortest_cover([]) == []
    # two spans of one length: the answer does not depend on their order
    tie = [("b", 0, 10), ("a", 5, 15)]
    assert xplane.shortest_cover(tie) == xplane.shortest_cover(tie[::-1]) \
        == [(0, 5, "b"), (5, 15, "a")]


def test_a_gap_goes_to_the_shortest_span_open_at_each_instant():
    """Nested spans, two threads, and a gap that straddles two phases: the
    device is idle 0-100 but for one operation at 40-50."""
    device = {0: [("op", 40, 50)]}
    host = [("chipbench.traced_window", 0, 100),
            ("chipbench.step_call", 0, 90),         # the outermost
            ("chipbench.await_tokens", 0, 100)]     # a client's, longer
    program = [("engine.wait", 10, 30),             # children of the call
               ("engine.dispatch", 30, 45),         # straddles into the op
               ("reshard.wire", 20, 25),            # a pool thread's
               ("engine.deliver", 60, 70)]
    out = xplane.reduce_events(device, host, program=program)
    gaps = {name: round(sec * 1e9) for name, sec in out["idle_gaps"]}
    assert gaps == {
        "engine.wait": 15,          # 10-20 and 25-30
        "reshard.wire": 5,          # 20-25: shorter than the wait it is in
        "engine.dispatch": 10,      # 30-40, the rest of it is busy
        "engine.deliver": 10,
        "step_call": 10 + 10 + 20,  # its self time: 0-10, 50-60, 70-90
        "await_tokens": 10}         # 90-100: only the client was waiting
    assert sum(gaps.values()) == 90
    assert out["busy_s"] == pytest.approx(10e-9)
    # without the program's spans the call keeps all it covers
    bare = dict(xplane.reduce_events(device, host)["idle_gaps"])
    assert bare["step_call"] == pytest.approx(80e-9)
    assert "unattributed" not in bare


def test_time_no_span_covers_stays_unattributed():
    device = {0: [("op", 0, 10)], 1: [("op", 0, 10)]}
    host = [("chipbench.traced_window", 0, 50)]
    out = xplane.reduce_events(device, host, program=[("a", 30, 60)])
    assert dict(out["idle_gaps"]) == {
        "unattributed": pytest.approx(20e-9), "a": pytest.approx(20e-9)}
    # ... and the stretch no span covers is reported: 30 ns from the start
    assert out["uncovered"] == [[pytest.approx(30e-9), 0.0]]


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


# what the reduction read from the recorded trace before idle gaps were
# split by the shortest span (PR 23's rule): none of it may move
RECORDED = {
    "window_s": 0.066579713, "busy_s": 0.001442716,
    "busy_s_by_chip": {"0": 0.001442716},
    "device_ops": [
        ["convolution_tanh_fusion bf16[2048,2048] x16", 0.001442684],
        ["copy-start bf16[2048,2048] x2", 2.7e-08],
        ["copy-done bf16[2048,2048] x2", 5e-09]],
    "program_runs": {"jit_work": [0.000721355, 0.000721401]}}


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_reads_as_before_but_for_the_gaps():
    device, host, modules = xplane.read_trace(DATA)
    out = xplane.reduce_events(device, host, modules)
    for key, value in RECORDED.items():
        assert out[key] == value, key
    # the three pauses are nested in nothing, so they keep their time; a
    # program span laid into the first pause takes its part of it
    gaps = dict(out["idle_gaps"])
    pause = min(s for n, s, e in host if n == "chipbench.pause")
    more = xplane.reduce_events(
        device, host, modules,
        program=[("inner", pause + 1e6, pause + 6e6)])
    split = dict(more["idle_gaps"])
    assert split["inner"] == pytest.approx(5e-3, rel=1e-3)
    assert split["pause"] == pytest.approx(gaps["pause"] - 5e-3, rel=1e-3)
    for key in RECORDED:
        assert more[key] == out[key]


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
