"""``--trace 2`` against ``--trace 0`` on the cells of the CPU rehearsal:
one process that measures the window as an untraced run does and then
captures a traced part.  The same window (schedule, counts, checks), both
kinds of metric on one line, and the ``--trace 1`` line still of its old
form."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import run, traffic

CELLS = {c["name"]: c for c in
         run.load_json(run.HERE, "rehearsal.json")["workloads"]}
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
SECONDS = {"toy-gpt.train": 2, "toy-gpt-pipeshard.train": 2,
           "toy-opt.saturated": 3, "toy-opt.steady": 3}


def _run(cell, trace, seed=2147483659):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", str(seed), "--seconds", str(SECONDS[cell]),
         "--trace", str(trace)],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    return lines[-1], lines[:-1]


def _names(group, cell):
    return {m["name"] for m in run.metrics_of(BENCH, group,
                                              CELLS[cell]["as"])}


@pytest.fixture(scope="module", params=sorted(CELLS))
def pair(request):
    cell = request.param
    return cell, _run(cell, 0), _run(cell, 2)


def test_trace2_line_holds_both_kinds_of_metric(pair):
    cell, (line0, _), (line2, _) = pair
    assert set(line0) == set(line2)        # the keys of a --trace 0 line
    assert line0["correct"] and line2["correct"]
    assert line2["failed"] == 0
    assert set(line0["metrics"]) == _names("end_to_end", cell)
    assert _names("end_to_end", cell) <= set(line2["metrics"])
    per_layer = set(line2["metrics"]) - _names("end_to_end", cell)
    assert per_layer and per_layer <= _names("per_layer", cell)
    # the readers of the program's spans found the traced part
    wanted = {"toy-gpt.train": {"plan_s", "driver_launch_ms"},
              "toy-gpt-pipeshard.train": {
                  "plan_s", "pipeshard_ops_per_step", "reshard_mb_per_step",
                  "reshard_wait_ms_per_step", "reshard_busy_ms_per_step",
                  "driver_launch_ms"},
              "toy-opt.saturated": {"tick_ms", "tick_host_ms",
                                    "engine_occupancy_pct", "queue_wait_ms",
                                    "prefill_useful_pct"},
              "toy-opt.steady": {"tick_ms", "tick_host_ms", "queue_wait_ms",
                                 "prefill_useful_pct", "ttft_p90_ms"}}
    assert wanted[cell] <= per_layer
    metrics = {k: v["value"] for k, v in line2["metrics"].items()}
    if "tick_host_ms" in metrics:
        assert 0 < metrics["tick_host_ms"] < metrics["tick_ms"]
    if "prefill_useful_pct" in metrics:
        assert 0 < metrics["prefill_useful_pct"] <= 100


def test_trace2_measures_the_window_of_trace0(pair):
    cell, (line0, info0), (line2, info2) = pair
    mix = traffic.load_mix(CELLS[cell]["traffic"])
    if mix["kind"] == "open_loop":
        # the schedule is the seed's: the same requests were due
        assert line0["attempted"] == line2["attempted"] == \
            round(mix["rate_per_s"] * SECONDS[cell])
        assert line0["metrics"]["out_tokens_per_s"] == \
            line2["metrics"]["out_tokens_per_s"]
    else:
        # a closed loop and a training loop go as fast as the host lets
        # them: the counts agree as two untraced runs here do
        assert line2["attempted"] == pytest.approx(line0["attempted"],
                                                   rel=0.35)
    checks0 = next(i for i in info0 if i.get("info") == "checks")
    checks2 = next(i for i in info2 if i.get("info") == "checks")
    assert checks0["compiles_in_window"] == 0 == \
        checks2["compiles_in_window"]
    if "reference_loss" in checks0:     # the same weights and first batch
        assert checks0["first_loss"] == checks2["first_loss"]
    # the traced part is reported apart from the window
    assert not any(i.get("info") == "program_spans" for i in info0)
    spans = next(i for i in info2 if i.get("info") == "program_spans")
    # ... with the program's spans in it (ShardParallel's step has none)
    assert len(spans) > 1 or cell == "toy-gpt.train"


def test_trace1_line_keeps_its_form():
    line, info = _run("toy-opt.saturated", 1)
    assert line["correct"]
    assert set(line["metrics"]) <= _names("per_layer", "toy-opt.saturated")
    assert {"tick_ms", "tick_host_ms", "queue_wait_ms"} <= \
        set(line["metrics"])
