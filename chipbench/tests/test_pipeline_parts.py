"""The six readers of a pipeshard step's account on the device's clock
(PR 49) and their entries in ``BENCHMARK.json``.  The readers ask the
program for its newest capture's account
(``telemetry.trace.last_capture().pipeline_time()``); here that capture is
made by hand: none, one that traced no pipeshard step (every one-chip
cell), one of the CPU (no device event), and a table written out."""
import pytest

from alpa_tpu.telemetry import device_time as dt
from alpa_tpu.telemetry import trace as ttrace
from chipbench import pipeline_parts, run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
NEW = ["mesh_idle_max_pct", "pipeline_bubble_pct", "dispatch_starved_pct",
       "edge_exposed_pct", "step_boundary_idle_pct",
       "collective_exposed_pct"]
CELL = "gpt-1.3b-4chip.pipeshard"
RUNTIME = "Runtime: mesh_executable, pipeshard_executable, runtime_emitter"


def _with_account(monkeypatch, account):
    """The program's newest capture is one whose account is ``account``."""
    capture = None if account is None else ttrace.Capture(
        "/nowhere", [], 0.0, _offset_us=0.0, _device_time=dt.empty_table(),
        _pipeline_time=account)
    monkeypatch.setattr(ttrace, "_LAST_CAPTURE", capture)


def _row(chips, envelope, busy, boundary, upstream, dispatch, edge,
         exposed, hidden=0.0):
    assert busy + boundary + upstream + dispatch + edge == \
        pytest.approx(envelope)
    return {"chips": chips, "envelope_s": envelope, "busy_s": busy,
            "boundary_s": boundary, "upstream_s": upstream,
            "dispatch_s": dispatch, "edge_s": edge,
            "collective_exposed_s": exposed, "collective_hidden_s": hidden,
            "dispatch_by_span": {"RUN stage_1_bwd": dispatch}}


# two traced steps of 1.0 s each; mesh 1 has one chip here, so that the
# mean over chips is not the mean over meshes
ACCOUNT = {
    "mesh 0": _row(2, 2.0, 1.50, 0.08, 0.30, 0.04, 0.08, exposed=0.40),
    "mesh 1": _row(1, 2.0, 1.70, 0.06, 0.20, 0.02, 0.02, exposed=0.10),
}


# ---- the entries ------------------------------------------------------------

def test_the_six_entries_follow_the_accepted_ones_in_order():
    names = [e["name"] for e in BENCH["per_layer"]]
    # after the newest metric the benchmark had (PR 47's), next to one
    # another; a later PR may append after them
    at = names.index("latent_select_decode_roofline_pct")
    assert names[at + 1:at + 1 + len(NEW)] == NEW
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", NEW)
def test_an_entry_is_a_share_of_the_four_chip_cells_steps(name):
    names = [e["name"] for e in BENCH["per_layer"]]
    entry = dict(BENCH["per_layer"][names.index(name)])
    # a later PR may append cells to the list, and nothing else
    assert entry.pop("workloads")[:1] == [CELL]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": RUNTIME,
                     "moves": "train_tokens_per_s"}
    # the layer is one the accepted benchmark names, letter for letter
    assert RUNTIME in {e["layer"] for e in
                       BENCH["per_layer"][:names.index(NEW[0])]}
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == "train_tokens_per_s")
    assert CELL in moved["workloads"]
    assert callable(run.metric_reader(name))


def test_no_other_cell_reports_them():
    for cell in BENCH["workloads"]:
        reported = {m["name"] for m in
                    run.metrics_of(BENCH, "per_layer", cell["name"])}
        assert (set(NEW) <= reported) == (cell["name"] == CELL)
        assert cell["name"] == CELL or not set(NEW) & reported


# ---- the readers ------------------------------------------------------------

@pytest.mark.parametrize("name, value", [
    # mesh 0 idles 0.50 of 2.0 s, mesh 1 0.30
    ("mesh_idle_max_pct", 25.0),
    ("pipeline_bubble_pct", 100 * (0.30 / 2 + 0.20 / 2) / 2),
    ("dispatch_starved_pct", 100 * (0.04 / 2 + 0.02 / 2) / 2),
    ("edge_exposed_pct", 100 * (0.08 / 2 + 0.02 / 2) / 2),
    ("step_boundary_idle_pct", 100 * (0.08 / 2 + 0.06 / 2) / 2),
    # over the three chips: two at 0.40 of 2.0 s, one at 0.10
    ("collective_exposed_pct", 100 * (2 * 0.20 + 0.05) / 3),
])
def test_a_reader_on_a_table_written_out(monkeypatch, name, value):
    _with_account(monkeypatch, ACCOUNT)
    assert run.metric_reader(name)({}) == pytest.approx(value)


def test_the_causes_and_the_busy_share_add_up_to_the_whole(monkeypatch):
    _with_account(monkeypatch, ACCOUNT)
    causes = sum(run.metric_reader(n)({}) for n in NEW[1:5])
    busy = 100 * sum(r["busy_s"] / r["envelope_s"]
                     for r in ACCOUNT.values()) / len(ACCOUNT)
    assert causes + busy == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_to_read(monkeypatch, name):
    read = run.metric_reader(name)
    # no capture was made (--trace 0)
    _with_account(monkeypatch, None)
    assert pipeline_parts.table() is None and read({}) is None
    # a capture that traced no pipeshard step: every one-chip cell
    _with_account(monkeypatch, {})
    assert read({}) is None
    # a capture whose account is still to be made, with no pipeline kept
    # and no device event (the CPU): made empty, nothing read
    monkeypatch.setattr(ttrace, "_LAST_CAPTURE", ttrace.Capture(
        "/nowhere", [], 0.0, _offset_us=0.0, _device_time=dt.empty_table()))
    assert read({}) is None
    assert ttrace.last_capture().pipeline_time() == {}
    # a program from before the captures kept an account (the parent
    # commit under this PR's benchmark files)
    monkeypatch.delattr(ttrace.Capture, "pipeline_time")
    assert read({}) is None
