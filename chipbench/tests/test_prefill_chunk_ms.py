"""The reader of ``prefill_chunk_ms`` (PR 31) on hand-made ``program_runs``,
and its entry in ``BENCHMARK.json``."""
import pytest

from chipbench import run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
read = run.metric_reader("prefill_chunk_ms")


def test_it_is_the_median_run_of_the_chunk_program():
    obs = {"device_trace": {"program_runs": {
        "jit_chunk_prefill": [0.0279, 0.0280, 0.0281, 0.0283, 0.0400],
        "jit_decode": [0.0102] * 150, "jit_scatter_row": [0.00024]}}}
    assert read(obs) == pytest.approx(28.1)
    obs["device_trace"]["program_runs"]["jit_chunk_prefill"] = [0.028,
                                                                 0.030]
    assert read(obs) == pytest.approx(29.0)


@pytest.mark.parametrize("obs", [
    {"device_trace": None}, {},
    {"device_trace": {"program_runs": {}}},
    {"device_trace": {"program_runs": {"jit_chunk_prefill": []}}},
    # the OPT cells: a dense, padded prefill and no chunk program
    {"device_trace": {"program_runs": {"jit_prefill": [0.0712, 0.0711],
                                       "jit_decode": [0.0119] * 170}}}],
    ids=["untraced", "no-trace-key", "no-runs", "no-chunk-runs",
         "dense-prefill"])
def test_it_finds_nothing_where_no_chunk_ran(obs):
    assert read(obs) is None


def test_its_entry_follows_the_accepted_ones_and_names_the_cell():
    names = [e["name"] for e in BENCH["per_layer"]]
    at = names.index("prefill_chunk_ms")
    # appended: after the newest metric the benchmark had (PR 30's)
    assert at > names.index("moe_decode_hbm_roofline_pct")
    entry = BENCH["per_layer"][at]
    assert entry == {
        "name": "prefill_chunk_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels: the XLA programs",
        "moves": "out_tokens_per_s",
        "workloads": ["trinity-mini-1chip.mixed"]}
    assert entry["layer"] in {e["layer"] for e in BENCH["per_layer"][:at]}
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
