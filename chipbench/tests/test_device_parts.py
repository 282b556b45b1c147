"""The six readers of device time by part of the model (PR 34) and their
entries in ``BENCHMARK.json``.  The readers ask the program for its newest
capture's table (``telemetry.trace.last_capture().device_time()``); here
that capture is made by hand: none, one of the CPU (no TPU plane), a table
written out, and the trace recorded on the chip with its HLO text
(``tests/runtime/data``, ``tests/runtime/record_device_trace.py``)."""
import gzip
import json
import os
import sys

import pytest

from alpa_tpu.telemetry import device_time as dt
from alpa_tpu.telemetry import trace as ttrace
from chipbench import run

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
DATA = os.path.join(run.ROOT, "tests", "runtime", "data")
NEW = ["dense_prefill_ms", "decode_weights_ms", "decode_attention_ms",
       "decode_head_ms", "train_attention_share_pct", "chip_busy_min_pct"]
OPT = ["opt-1.3b.saturated", "opt-1.3b.steady", "opt-1.3b.longprompt",
       "opt-1.3b.steady-b"]
SERVING = OPT + ["trinity-mini-1chip.mixed", "deepseek-v2-1chip.longdoc"]
TRAINING = ["gpt-1.3b-1chip.train", "gpt-1.3b-4chip.pipeshard",
            "olmoe-1b-7b-1chip.train"]
KERNELS = "Kernels: the XLA programs"
RUNTIME = "Runtime: mesh_executable, pipeshard_executable, runtime_emitter"


def _with_capture(monkeypatch, table):
    """The program's newest capture is one whose table is ``table``."""
    capture = None if table is None else ttrace.Capture(
        "/nowhere", [], 0.0, _offset_us=0.0, _device_time=table)
    monkeypatch.setattr(ttrace, "_LAST_CAPTURE", capture)


def _entry(runs, run_s, parts, mixed_s=0.0):
    return {"runs": runs, "run_s": run_s, "parts": parts,
            "mixed_s": mixed_s, "inherited_s": 0.0,
            "unscoped_s": parts.get(dt.UNSCOPED, 0.0)}


# ---- the entries ------------------------------------------------------------

def test_the_six_entries_are_appended_in_order():
    names = [e["name"] for e in BENCH["per_layer"]]
    at = names.index("admit_host_ms")
    # after the newest metric the benchmark had (PR 33's), and last
    assert names[at + 1:] == NEW


@pytest.mark.parametrize("name, unit, better, layer, moves, cells", [
    # dense admissions fall into the traced seconds of these two (as
    # ``admit_host_ms`` lists them): the steady cells trace their drain
    ("dense_prefill_ms", "ms", "lower", KERNELS, "gap_p99_ms",
     ["opt-1.3b.saturated", "opt-1.3b.longprompt"]),
    ("decode_weights_ms", "ms", "lower", KERNELS, "out_tokens_per_s", OPT),
    ("decode_attention_ms", "ms", "lower", KERNELS, "out_tokens_per_s",
     OPT),
    ("decode_head_ms", "ms", "lower", KERNELS, "out_tokens_per_s", SERVING),
    ("train_attention_share_pct", "%", "lower", KERNELS,
     "train_tokens_per_s", TRAINING),
    ("chip_busy_min_pct", "%", "higher", RUNTIME, "train_tokens_per_s",
     ["gpt-1.3b-4chip.pipeshard"]),
])
def test_an_entry_moves_a_metric_that_lists_its_cells(name, unit, better,
                                                      layer, moves, cells):
    at = [e["name"] for e in BENCH["per_layer"]].index(name)
    entry = dict(BENCH["per_layer"][at])
    # a later PR may append cells to the list, and nothing else
    assert entry.pop("workloads")[:len(cells)] == cells
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": moves}
    first_new = [e["name"] for e in BENCH["per_layer"]].index(NEW[0])
    assert layer in {e["layer"] for e in BENCH["per_layer"][:first_new]}
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == moves)
    assert set(cells) <= set(moved["workloads"])
    assert callable(run.metric_reader(name))


# ---- nothing to read ----------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_without_a_capture(monkeypatch, name):
    _with_capture(monkeypatch, None)
    assert run.metric_reader(name)({}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_on_a_cpu_rehearsal(monkeypatch, name):
    """A capture of the CPU has no TPU plane: the table holds no chip."""
    table = dt.empty_table((1_000, 3_000_001_000))
    assert table["programs"] == {}
    _with_capture(monkeypatch, table)
    assert run.metric_reader(name)({}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_from_a_program_without_the_table(
        monkeypatch, name):
    """The parent commit's ``telemetry.trace`` has no ``last_capture``,
    and its ``telemetry`` no ``device_time`` to import."""
    monkeypatch.delattr(ttrace, "last_capture")
    monkeypatch.setitem(sys.modules, "alpa_tpu.telemetry.device_time", None)
    assert run.metric_reader(name)({}) is None


@pytest.mark.parametrize("name", NEW[:4])
def test_a_serving_reader_gives_nothing_where_its_program_did_not_run(
        monkeypatch, name):
    table = dt.empty_table((0, 3_000_000_000))
    table["busy_s"][0] = 2.9
    table["programs"][0] = {"jit_chunk_prefill": _entry(
        9, [0.3] * 9, {"attention": 1.0, "moe": 1.7})}
    _with_capture(monkeypatch, table)
    assert run.metric_reader(name)({}) is None


@pytest.mark.parametrize("name", NEW[:5])
def test_a_reader_gives_nothing_from_a_stale_program(monkeypatch, name):
    """More than a tenth of the device time under no ``op_name``: a
    program read back from a cache that an earlier tree filled."""
    parts = {"mlp": 0.5, "attention": 0.35, dt.UNSCOPED: 0.15}
    table = dt.empty_table((0, 3_000_000_000))
    table["busy_s"][0] = 2.9
    table["programs"][0] = {
        "jit_decode": _entry(100, [0.0101] * 100, parts),
        "jit_prefill": _entry(4, [0.07] * 4, dict(parts))}
    _with_capture(monkeypatch, table)
    assert run.metric_reader(name)({}) is None
    for entry in table["programs"][0].values():
        entry["parts"][dt.UNSCOPED] = entry["unscoped_s"] = 0.05
    assert run.metric_reader(name)({}) is not None


# ---- a table written out -----------------------------------------------------

def _serving_table():
    table = dt.empty_table((0, 3_000_000_000))
    table["busy_s"][0] = 2.95
    table["programs"][0] = {
        "jit_decode": _entry(200, [0.0119] * 200, {
            "mlp": 0.8578, "projection": 0.3857, "attention": 0.4441,
            "attention.cache_write": 0.3846, "embed": 0.1884,
            "head": 0.0623, "norm": 0.0003, dt.UNSCOPED: 0.0363},
            mixed_s=1.2478),
        "jit_prefill": _entry(5, [0.07134, 0.07133, 0.07135, 0.07134,
                                  0.07136], {"attention": 0.1872,
                                             "mlp": 0.0934,
                                             "projection": 0.0513,
                                             "head": 0.0149,
                                             dt.UNSCOPED: 0.0046}),
        "jit_scatter_row": _entry(5, [0.0018] * 5, {dt.UNSCOPED: 0.0089})}
    return table


@pytest.mark.parametrize("name, value", [
    ("dense_prefill_ms", 71.34),
    ("decode_weights_ms", (0.8578 + 0.3857) / 200 * 1e3),
    ("decode_attention_ms", (0.4441 + 0.3846) / 200 * 1e3),
    ("decode_head_ms", (0.0623 + 0.1884) / 200 * 1e3),
])
def test_a_serving_reader_reads_its_program_and_its_parts(monkeypatch, name,
                                                          value):
    _with_capture(monkeypatch, _serving_table())
    assert run.metric_reader(name)({}) == pytest.approx(value)


def _training_table():
    """Two chips of a pipeline: each its stage programs."""
    table = dt.empty_table((0, 2_000_000_000))
    table["busy_s"] = {0: 1.6, 1: 1.2}
    table["programs"][0] = {
        "jit_stage_0_fwd": _entry(8, [0.05] * 8, {
            "attention": 0.12, "mlp": 0.2, "projection": 0.08}),
        "jit_stage_0_bwd": _entry(8, [0.14] * 8, {
            "attention": 0.36, "mlp": 0.5, "projection": 0.2,
            dt.UNSCOPED: 0.03}),
        "jit_apply_grad_0": _entry(1, [0.1], {"outside_model": 0.1})}
    table["programs"][1] = {
        "jit_stage_1_bwd": _entry(8, [0.14] * 8, {
            "attention": 0.3, "mlp": 0.5, "head": 0.3, "loss": 0.1})}
    return table


def test_the_training_share_is_the_mean_over_chips_of_busy_time(monkeypatch):
    _with_capture(monkeypatch, _training_table())
    read = run.metric_reader("train_attention_share_pct")
    assert read({}) == pytest.approx(
        100 * ((0.12 + 0.36) / 1.6 + 0.3 / 1.2) / 2)


def test_the_least_busy_chip_over_the_window(monkeypatch):
    _with_capture(monkeypatch, _training_table())
    assert run.metric_reader("chip_busy_min_pct")({}) == \
        pytest.approx(100 * 1.2 / 2.0)


# ---- the trace recorded on the chip ---------------------------------------------

@pytest.fixture(scope="module")
def recorded_table():
    with gzip.open(os.path.join(DATA, "toy_decode.hlo.json.gz"), "rt") as f:
        texts = json.load(f)
    marker, chips = dt.read_profile(
        os.path.join(DATA, "toy_decode.xplane.pb"), ttrace.CAPTURE_MARKER)
    table = dt.reduce_events(
        chips, marker,
        lambda name: [dt.instruction_parts(t) for t in texts.get(name, ())])
    return table, chips, marker


def test_the_serving_readers_on_the_recorded_trace(monkeypatch,
                                                   recorded_table):
    """By hand: the six decodes' and the prefill's seconds from the
    profiler's own ``XLA Modules`` line, the parts from the table."""
    table, chips, (lo, hi) = recorded_table
    _with_capture(monkeypatch, table)
    _ops, runs = chips[0]
    prefills = [e - s for name, s, e in runs
                if name == "jit_prefill" and lo <= s and e <= hi]
    assert len(prefills) == 1
    assert run.metric_reader("dense_prefill_ms")({}) == \
        pytest.approx(prefills[0] / 1e6)
    decode = table["programs"][0]["jit_decode"]
    assert decode["runs"] == 6
    assert decode["unscoped_s"] < 0.1 * sum(decode["parts"].values())
    ms = {part: 1e3 * s / 6 for part, s in decode["parts"].items()}
    assert run.metric_reader("decode_weights_ms")({}) == \
        pytest.approx(ms["projection"] + ms["mlp"])
    assert run.metric_reader("decode_attention_ms")({}) == \
        pytest.approx(ms["attention"] + ms["attention.cache_write"])
    assert run.metric_reader("decode_head_ms")({}) == \
        pytest.approx(ms["head"] + ms["embed"])
    # no part is read twice, and what the three leave is small
    read = sum(run.metric_reader(n)({}) for n in NEW[1:4])
    assert read == pytest.approx(
        sum(ms.values()) - ms["norm"] - ms[dt.UNSCOPED] -
        ms.get("block", 0.0) - ms.get(dt.OUTSIDE_MODEL, 0.0))


def test_the_chip_readers_on_the_recorded_trace(monkeypatch, recorded_table):
    table, _chips, (lo, hi) = recorded_table
    _with_capture(monkeypatch, table)
    assert run.metric_reader("chip_busy_min_pct")({}) == \
        pytest.approx(100 * table["busy_s"][0] / ((hi - lo) / 1e9))
    # the argmax and the increment the script jitted registered nothing:
    # a chip whose unscoped share is over a tenth gives no share
    programs = table["programs"][0]
    total = sum(sum(e["parts"].values()) for e in programs.values())
    unscoped = sum(e["unscoped_s"] for e in programs.values())
    share = run.metric_reader("train_attention_share_pct")({})
    if unscoped > 0.1 * total:
        assert share is None
    else:
        attention = sum(dt.part_seconds(e, "attention")
                        for e in programs.values())
        assert share == pytest.approx(100 * attention / table["busy_s"][0])
