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
