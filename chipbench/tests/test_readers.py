"""The metric readers on observations made by hand."""
import pytest

from chipbench import arithmetic, run


def _request(due, times, asked, cut=False):
    return {"due": due, "sent": due + 0.001, "token_times": times,
            "tokens": list(range(len(times))), "prompt_ids": [5] * 10,
            "asked": asked, "error": None, "cut": cut, "kind": "measured"}


def _serving_obs():
    return {"window": (100.0, 110.0), "seconds": 10.0, "drain_end": 120.0,
            "requests": [
                _request(100.0, [101.0, 101.5, 102.0], 3),
                # cut off at the end of the window after two of five tokens,
                # a third came after the window's end
                _request(108.0, [109.0, 109.8, 110.2], 5, cut=True),
                # queued when the window ended: no token at all
                _request(109.0, [], 4, cut=True),
                _request(101.0, [105.0, 105.1], 2)]}


def test_tokens_of_cut_requests_are_the_windows_work():
    obs = _serving_obs()
    assert run.metric_reader("out_tokens_per_s")(obs) == pytest.approx(0.7)
    # gaps 0.5, 0.5, 0.8, 0.1: the 0.8 is a cut request's
    assert run.metric_reader("gap_p99_ms")(obs) == pytest.approx(800.0)


def test_ttft_is_over_the_requests_that_count():
    obs = _serving_obs()
    # 1.0 and 4.0 s; the cut requests were not given the time to answer
    assert run.metric_reader("ttft_p90_ms")(obs) == pytest.approx(4000.0)
    obs["requests"].append(_request(103.0, [], 3))   # never answered
    assert run.metric_reader("ttft_p90_ms")(obs) == pytest.approx(17000.0)


def test_decode_roofline_is_over_the_programs_device_time():
    obs = _serving_obs()
    tick = {"name": "engine.decode-tick", "ts_us": 5, "dur_us": 40000,
            "args": {"active": 4}}
    obs.update(program_spans=[tick] * 7, program_window_us=(0, 10),
               peaks={"hbm_bytes_per_s": 800e9}, weight_bytes=4e9,
               cache_itemsize=2,
               config={"hidden_size": 2048, "num_hidden_layers": 24},
               device_trace={"program_runs": {"jit_decode": [0.01, 0.02, 0.03],
                                              "jit_prefill": [0.08]}})
    # 7 tokens in the window attended to 10+0, 10+1, 10+2, 10+0, 10+1,
    # 10+0, 10+1 positions: 75 over 7 ticks
    per_tick = arithmetic.decode_tick_bytes(4e9, 75 / 7, 2048, 24, 2)
    assert run.metric_reader("decode_hbm_roofline_pct")(obs) == \
        pytest.approx(100.0 * per_tick / 800e9 / 0.02)
    obs["device_trace"]["program_runs"].pop("jit_decode")
    assert run.metric_reader("decode_hbm_roofline_pct")(obs) is None


def test_split_metrics_share_the_reader_of_their_stem():
    obs = {"memory": [{"peak_bytes_in_use": 3e9}, {"peak_bytes_in_use": 5e9}]}
    for name in ("hbm_peak_gb.train", "hbm_peak_gb.serve"):
        assert run.metric_reader(name)(obs) == pytest.approx(5.0)
    # a file of the full name wins over the stem's
    assert "enqueueing one step" in \
        run.metric_reader("host_dispatch_ms.train").__globals__["__doc__"]


def _span(name, ts, dur, **args):
    return {"name": name, "category": "x", "ts_us": ts, "dur_us": dur,
            "args": args or None}


def test_counter_readers_read_the_rise_over_the_window():
    before = {"alpa_overlap_steps_total": 3.0,
              "alpa_overlap_wait_blocked_seconds_total": 1.0,
              "alpa_overlap_transfer_busy_seconds_total": 2.0,
              "alpa_mesh_dispatch_seconds": {"count": 10, "sum": 1.0},
              "alpa_serving_prefill_prompt_tokens_total": 100.0,
              "alpa_serving_prefill_padded_tokens_total": 2048.0}
    after = {"alpa_overlap_steps_total": 7.0,
             "alpa_overlap_wait_blocked_seconds_total": 1.8,
             "alpa_overlap_transfer_busy_seconds_total": 6.0,
             "alpa_mesh_dispatch_seconds": {"count": 30, "sum": 1.5},
             "alpa_pipeshard_dispatch_seconds": {"count": 4, "sum": 8.0},
             "alpa_serving_queue_wait_seconds": {"count": 5, "sum": 0.25},
             "alpa_serving_prefill_prompt_tokens_total": 612.0,
             "alpa_serving_prefill_padded_tokens_total": 6144.0}
    obs = {"counters": (before, after)}
    read = run.metric_reader
    assert read("reshard_wait_ms_per_step")(obs) == pytest.approx(200.0)
    assert read("reshard_busy_ms_per_step")(obs) == pytest.approx(1000.0)
    # the pipeshard histogram ticked, so it is the step's; else one mesh's
    assert read("driver_launch_ms")(obs) == pytest.approx(2000.0)
    after.pop("alpa_pipeshard_dispatch_seconds")
    assert read("driver_launch_ms")(obs) == pytest.approx(25.0)
    assert read("queue_wait_ms")(obs) == pytest.approx(50.0)
    assert read("prefill_useful_pct")(obs) == pytest.approx(12.5)


NEW_COUNTER_METRICS = ["reshard_wait_ms_per_step", "reshard_busy_ms_per_step",
                       "driver_launch_ms", "queue_wait_ms",
                       "prefill_useful_pct"]


@pytest.mark.parametrize("name", NEW_COUNTER_METRICS)
def test_counter_readers_find_nothing_where_the_program_has_no_series(name):
    still = {"alpa_overlap_steps_total": 2.0,
             "alpa_mesh_dispatch_seconds": {"count": 1, "sum": 1.0},
             "alpa_serving_prefill_padded_tokens_total": 9.0}
    for obs in ({}, {"counters": ({}, {})}, {"counters": (still, still)}):
        assert run.metric_reader(name)(obs) is None


def test_span_readers_of_the_new_metrics():
    spans = [
        _span("pipeshard.step", 0, 100, step=0),
        _span("reshard.edge", 10, 5, bytes=4_000_000, fast=True),
        _span("reshard.edge-group", 50, 5, bytes=2_000_000),
        _span("pipeshard.step", 200, 100, step=1),
        _span("reshard.edge", 210, 5, bytes=5_000_000, fast=True),
        _span("reshard.edge", 900, 5, bytes=7_000_000, fast=True),
        _span("engine.decode-tick", 1000, 40, active=4),
        _span("engine.wait", 1002, 30),
        _span("engine.decode-tick", 1100, 50, active=4),
        _span("engine.wait", 1101, 20),
        _span("engine.decode-tick", 1200, 45, active=4),
        _span("engine.wait", 1203, 33)]
    obs = {"program_spans": spans, "program_window_us": (0, 2000)}
    # 6 MB in the first step, 5 in the second; the edge outside any step
    # is no step's
    assert run.metric_reader("reshard_mb_per_step")(obs) == \
        pytest.approx(5.5)
    # ticks less their waits: 10, 30, 12
    assert run.metric_reader("tick_host_ms")(obs) == pytest.approx(0.012)
    # handed a narrower interval, the readers see only what began in it
    obs["program_window_us"] = (1050, 1150)
    assert run.metric_reader("tick_host_ms")(obs) == pytest.approx(0.030)
    assert run.metric_reader("reshard_mb_per_step")(obs) is None
    assert run.metric_reader("tick_host_ms")(
        {"program_spans": [], "program_window_us": (0, 1)}) is None
