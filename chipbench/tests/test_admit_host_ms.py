"""The reader of ``admit_host_ms`` (PR 33) on hand-made program spans, and
its entry in ``BENCHMARK.json``."""
import pytest

from chipbench import run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
read = run.metric_reader("admit_host_ms")


def _span(ts_us, dur_us, path="dense", name="engine.prefill"):
    args = None if path is None else {"rid": 1, "prompt_len": 300,
                                      "padded_len": 2048, "path": path,
                                      "chunks": int(path == "dense")}
    return {"name": name, "cat": "serving", "ts_us": ts_us,
            "dur_us": dur_us, "args": args}


def test_it_is_the_median_dense_admission_in_ms():
    obs = {"program_window_us": (1_000_000, 4_000_000), "program_spans": [
        _span(1_100_000, 48_200), _span(1_500_000, 49_900),
        _span(2_100_000, 48_700), _span(3_900_000, 50_300),
        # none of these is a dense admission of the interval
        _span(2_500_000, 400_000, path="chunked"),
        _span(2_600_000, 900, path="handoff"),
        _span(500_000, 5_000), _span(4_100_000, 5_000),
        _span(1_200_000, 11_900, path=None, name="engine.decode-tick")]}
    assert read(obs) == pytest.approx((48.7 + 49.9) / 2)
    obs["program_spans"].append(_span(3_000_000, 6_100))
    assert read(obs) == pytest.approx(48.7)


@pytest.mark.parametrize("spans", [
    [],
    # an engine that admits in chunks (the Trinity and DeepSeek-V2 cells)
    [_span(1_100_000, 310_000, path="chunked")],
    # an interval in which nothing arrived (the steady cells' drain)
    [_span(900_000, 48_000),
     _span(1_200_000, 11_900, path=None, name="engine.decode-tick")],
    # a span the program closed without arguments
    [_span(1_100_000, 48_000, path=None)]],
    ids=["no-spans", "chunked-only", "before-the-interval", "no-args"])
def test_it_finds_nothing_where_no_dense_admission_ran(spans):
    obs = {"program_window_us": (1_000_000, 4_000_000),
           "program_spans": spans}
    assert read(obs) is None


def test_its_entry_follows_the_accepted_ones_and_names_the_cells():
    names = [e["name"] for e in BENCH["per_layer"]]
    at = names.index("admit_host_ms")
    # appended: after the newest metric the benchmark had (PR 32's)
    assert at > names.index("moe_local_rows_pct")
    entry = dict(BENCH["per_layer"][at])
    # a later PR may append cells to the list, and nothing else
    assert entry.pop("workloads")[:2] == ["opt-1.3b.saturated",
                                          "opt-1.3b.longprompt"]
    assert entry == {
        "name": "admit_host_ms", "unit": "ms", "better": "lower",
        "source": "program_span",
        "layer": "Serving: serve/controller.py, serve/engine.py, "
                 "serve/generation.py",
        "moves": "gap_p99_ms"}
    assert entry["layer"] in {e["layer"] for e in BENCH["per_layer"][:at]}
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(BENCH["per_layer"][at]["workloads"]) <= \
        set(moved["workloads"])
