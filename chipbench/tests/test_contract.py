"""BENCHMARK.json against the files it names: every configuration, mix,
metric, reference and driver is found by its name."""
import os

import pytest

from chipbench import run, stats, traffic

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
REHEARSAL = run.load_json(run.HERE, "rehearsal.json")["workloads"]
CELLS = BENCH["workloads"] + REHEARSAL


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
    assert config["name"] == cell["config"]
    mix = traffic.load_mix(cell["traffic"])
    driver = run.load_module("drivers", config["driver"])
    assert callable(driver.run)
    reference = run.load_module("references", config["reference"])
    assert callable(reference.Reference)
    wants = {"train": {"lm_batches"}, "serve": {"closed_loop", "open_loop"}}
    assert mix["kind"] in wants[config["driver"]]


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(entry):
    assert callable(run.metric_reader(entry["name"]))
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells


def test_tracing_is_in_the_runs_that_measure():
    assert BENCH["trace_in_run"] is True
    # PR 24's metrics, added at the end of the list, each with a reader of
    # its own name (test_metric_has_a_reader) and a layer that was there
    new = ["reshard_wait_ms_per_step", "reshard_busy_ms_per_step",
           "reshard_mb_per_step", "driver_launch_ms", "tick_host_ms",
           "queue_wait_ms", "prefill_useful_pct"]
    assert [m["name"] for m in BENCH["per_layer"]][-len(new):] == new
    layers = {m["layer"] for m in BENCH["per_layer"][:-len(new)]}
    for m in BENCH["per_layer"][-len(new):]:
        assert m["layer"] in layers
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))


def test_per_layer_moves_a_metric_of_the_same_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= \
            set(target.get("workloads", cells)), m["name"]


def test_every_cell_reports_enough():
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in
               run.metrics_of(BENCH, "end_to_end", cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(BENCH, "per_layer", cell["name"])


def test_configs_are_each_used_and_reduced_lists_what_differs():
    used = {c["config"] for c in BENCH["workloads"]}
    for entry in BENCH["configs"]:
        assert entry["name"] in used
        config = run.load_json(run.ROOT, entry["file"])
        assert config["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            assert config["published"][key] != config[key]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= 1


def test_rehearsal_cells_stand_for_real_ones():
    cells = {c["name"] for c in BENCH["workloads"]}
    listed = {c["name"] for c in REHEARSAL}
    assert not listed & cells
    # a rehearsal cell may stand for a cell this benchmark does not (yet)
    # hold; then it reports the metrics that name no cell
    for cell in REHEARSAL:
        assert cell["as"]


def test_percentile_and_median():
    assert stats.percentile(range(1, 101), 90) == 90
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)
