"""One hook-instrumented graph executor for all dispatch modes
(ISSUE 6 tentpole).

Oracle 1: numerics — with instrumentation ON (span tracing, a firing
fault site) the register and overlap graph replays must stay bit-exact
vs the sequential interpreter; instrumentation is compiled into the
replay plan as per-node hooks, not a reason to fall back.  Oracle 2:
mode selection — ``auto`` keeps the fast path under ``collect_trace``
(the tier-1 no-interpreter-fallback guard) and produces a valid
multi-track Chrome trace.  Oracle 3: the flight recorder — ring
wraparound, dump-on-exception, `trace_tool.py flight` readability.
Plus the static lowering-time hazard pass (`graph.check()`), the
runtime `SlotHazardChecker` hook, and the hooked-overhead regression
bound.
"""
import os

import numpy as np
import pytest

import alpa_tpu
import jax
from alpa_tpu import PipeshardParallel, fault
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.telemetry import flight as tflight
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)


@pytest.fixture(autouse=True)
def _restore_globals():
    prev_mode = global_config.pipeline_dispatch_mode
    prev_collect = global_config.collect_trace
    prev_flight = global_config.flight_recorder
    yield
    global_config.pipeline_dispatch_mode = prev_mode
    global_config.collect_trace = prev_collect
    global_config.flight_recorder = prev_flight
    fault.set_retry_policy(None)


def _run_steps(mode, n_steps=2):
    global_config.pipeline_dispatch_mode = mode
    method = PipeshardParallel(
        num_micro_batches=2,
        layer_option=AutoLayerOption(layer_num=4),
        stage_option=UniformStageOption(num_stages=4))
    step = get_mlp_train_step(method, use_value_and_grad=False)
    state, batch = create_mlp_train_state_and_batch(
        batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
        num_layers=4, manual_pipeline_layer=False)
    val = None
    for _ in range(n_steps):
        state, val = step(state, batch)
    return state, val, step.get_last_executable()


def _assert_bitwise_equal(states_vals):
    (state_a, val_a), *rest = states_vals
    leaves_a = jax.tree_util.tree_leaves(state_a.params)
    assert leaves_a
    for state_b, val_b in rest:
        leaves_b = jax.tree_util.tree_leaves(state_b.params)
        assert len(leaves_a) == len(leaves_b)
        for x, y in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(val_a), np.asarray(val_b))


# ---------------------------------------------------------------------
# bit-exactness with instrumentation on
# ---------------------------------------------------------------------

def test_three_way_bitwise_with_tracing_on():
    """Registers and overlap stay bit-exact vs the interpreter with the
    trace hook compiled in — the replay plan changed, the numerics must
    not."""
    alpa_tpu.init("local")
    global_config.collect_trace = True
    ttrace.get_recorder().clear()
    state_s, val_s, ex_s = _run_steps("sequential")
    state_r, val_r, ex_r = _run_steps("registers")
    state_o, val_o, ex_o = _run_steps("overlap")
    assert ex_s.last_dispatch_stats["mode"] == "sequential"
    assert ex_r.last_dispatch_stats["mode"] == "registers"
    assert ex_o.last_dispatch_stats["mode"] == "overlap"
    assert "trace" in ex_r.last_dispatch_stats["hooks"]
    assert "trace" in ex_o.last_dispatch_stats["hooks"]
    _assert_bitwise_equal([(state_s, val_s), (state_r, val_r),
                           (state_o, val_o)])
    ttrace.get_recorder().clear()


def test_three_way_bitwise_with_firing_fault_site():
    """A stage_launch fault that fires once and is retried must leave
    every mode's numerics untouched — the fault hook preempts the real
    execution, so the retry replays an op that never ran."""
    alpa_tpu.init("local")
    fault.set_retry_policy(fault.RetryPolicy(max_attempts=3,
                                             base_delay=0.0))
    out = {}
    for mode in ("sequential", "registers", "overlap"):
        plan = fault.FaultPlan(
            fault.FaultSpec("stage_launch", kind="error", times=1))
        with plan:
            state, val, ex = _run_steps(mode)
        st = ex.last_dispatch_stats
        assert st["mode"] == mode, st
        assert plan.fired("stage_launch") == 1, (mode, plan.events)
        assert plan.retries.get("stage_launch", 0) >= 1, (mode,
                                                          plan.retries)
        if mode != "sequential":
            assert "fault" in st["hooks"], st
        out[mode] = (state, val)
    _assert_bitwise_equal([out["sequential"], out["registers"],
                           out["overlap"]])


def test_fault_site_hit_parity_with_interpreter():
    """Armed-but-never-firing sites must see the same number of
    matching fire() calls from the graph executor as from the
    interpreter — hook emission covers every RUN and cross-mesh
    RESHARD, including grouped ops (one fire per member)."""
    alpa_tpu.init("local")
    hits = {}
    for mode in ("sequential", "registers", "overlap"):
        plan = fault.FaultPlan(
            fault.FaultSpec("stage_launch", kind="error", after=10**9),
            fault.FaultSpec("cross_mesh_send", kind="error",
                            after=10**9))
        with plan:
            _run_steps(mode, n_steps=1)
        hits[mode] = (plan.hits("stage_launch"),
                      plan.hits("cross_mesh_send"))
    assert hits["registers"] == hits["sequential"], hits
    assert hits["overlap"] == hits["sequential"], hits
    assert hits["sequential"][0] > 0 and hits["sequential"][1] > 0, hits


# ---------------------------------------------------------------------
# tier-1 guard: `auto` no longer falls back to the interpreter
# ---------------------------------------------------------------------

def test_auto_keeps_fast_path_under_collect_trace():
    """The three-way mode fork is gone: with collect_trace=True, auto
    still lowers to the register/overlap graph executor and the dumped
    Chrome trace is valid and multi-track."""
    alpa_tpu.init("local")
    global_config.collect_trace = True
    ttrace.get_recorder().clear()
    _, _, ex = _run_steps("auto", n_steps=1)
    st = ex.last_dispatch_stats
    assert st["mode"] in ("registers", "overlap"), st
    assert st["mode"] not in ("sequential", "threaded"), st
    assert "trace" in st["hooks"], st

    trace = ttrace.get_recorder().to_chrome_trace()
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    begins = [e for e in events if e.get("ph") == "B"]
    named = spans or begins
    assert named, "collect_trace produced no instruction spans"
    names = {e["name"] for e in named}
    assert any(n.startswith("RUN") for n in names), names
    # multi-track: instructions land on distinct per-mesh tracks
    tids = {e.get("tid") for e in named}
    assert len(tids) > 1, tids
    ttrace.get_recorder().clear()


# ---------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------

def test_flight_ring_wraparound(tmp_path):
    rec = tflight.FlightRecorder(capacity=7)   # rounds up to 8
    assert rec.capacity == 8
    for i in range(20):
        rec.record("exec", f"RUN s{i}", i % 4, i, (i,), 10 * i,
                   10 * i + 5, "ok")
    evs = rec.snapshot()
    assert len(evs) == 8
    assert [e[0] for e in evs] == list(range(12, 20))   # last 8 seqs
    path = rec.dump(str(tmp_path / "flight.json"), reason="unit test")
    dump = tflight.load_dump(path)
    assert dump["reason"] == "unit test"
    assert dump["n_events"] == 8
    assert dump["first_seq"] == 12 and dump["last_seq"] == 19
    assert dump["events"][-1]["name"] == "RUN s19"


def test_flight_dump_on_step_exception(tmp_path):
    """An uncaught mid-step error auto-dumps the ring, and the dump is
    readable by the trace_tool flight subcommand."""
    alpa_tpu.init("local")
    global_config.flight_recorder = True
    global_config.flight_dump_dir = str(tmp_path)
    prev_rec = tflight.set_recorder(tflight.FlightRecorder(capacity=256))
    fault.set_retry_policy(None)    # NO_RETRY: the fault escapes
    try:
        plan = fault.FaultPlan(
            fault.FaultSpec("stage_launch", kind="error", times=1))
        with plan:
            with pytest.raises(fault.InjectedFault):
                _run_steps("registers", n_steps=1)
        path = tflight.last_dump_path()
        assert path is not None and os.path.dirname(path) == str(tmp_path)
        dump = tflight.load_dump(path)
        assert dump["events"], dump
        # the fault fired on the step's first instruction (empty ring at
        # fire time), so the step-raise trigger produced the dump; its
        # ring holds the failed instruction with its error outcome
        assert dump["reason"] in ("pipeshard step raised",
                                  "fault site fired: stage_launch "
                                  "(error)"), dump["reason"]
        outcomes = {e["outcome"] for e in dump["events"]}
        assert "error:InjectedFault" in outcomes, outcomes
        kinds = {e["kind"] for e in dump["events"]}
        assert "exec" in kinds
        import importlib
        trace_tool = importlib.import_module("scripts.trace_tool")
        trace_tool.main(["flight", path, "--last", "5"])
    finally:
        tflight.set_recorder(prev_rec)
        global_config.flight_dump_dir = None


def test_flight_hook_records_instruction_events():
    alpa_tpu.init("local")
    global_config.flight_recorder = True
    prev_rec = tflight.set_recorder(tflight.FlightRecorder(capacity=1024))
    try:
        _, _, ex = _run_steps("overlap", n_steps=1)
        st = ex.last_dispatch_stats
        assert "flight" in st["hooks"], st
        evs = tflight.get_recorder().snapshot()
        assert evs, "flight hook recorded nothing"
        names = {e[2] for e in evs}
        assert any(n.startswith("RUN") for n in names), names
        outcomes = {e[8] for e in evs}
        assert outcomes == {"ok"}, outcomes
    finally:
        tflight.set_recorder(prev_rec)


# ---------------------------------------------------------------------
# hazard checking: static pass + runtime hook
# ---------------------------------------------------------------------

def test_graph_check_passes_on_real_lowering():
    alpa_tpu.init("local")
    _, _, ex = _run_steps("registers", n_steps=1)
    prog = ex._register_programs["registers"]
    assert prog.graph is not None
    prog.graph.check()   # must not raise on a real compile


def test_graph_check_catches_broken_edges():
    """Corrupting the dependence edges of a real lowering must trip the
    static hazard pass with a slot-level diagnosis."""
    import dataclasses

    alpa_tpu.init("local")
    _, _, ex = _run_steps("registers", n_steps=1)
    graph = ex._register_programs["registers"].graph
    # drop every predecessor of a node that reads slots: now some read
    # has no edge to its writer (RAW) or a FREE loses its transfer edge
    victim = next(i for i, n in enumerate(graph.nodes)
                  if n.reads and graph.preds[i])
    broken_preds = list(graph.preds)
    broken_preds[victim] = ()
    broken = dataclasses.replace(graph, preds=broken_preds)
    with pytest.raises(RuntimeError, match="hazard|edge|slot"):
        broken.check()


def test_slot_hazard_checker_flags_bad_interleavings():
    from alpa_tpu.pipeline_parallel.runtime_emitter import (
        OpHook, SlotHazardChecker)

    def hook(kind, node, reads=(), writes=(), kills=()):
        return OpHook(kind=kind, name=f"n{node}", node=node, mesh=0,
                      reads=tuple(reads), writes=tuple(writes),
                      kills=tuple(kills),
                      slots=tuple(reads) + tuple(writes) + tuple(kills))

    # clean run: launch -> wait -> consume
    chk = SlotHazardChecker()
    chk.begin_step()
    chk.on_launch(hook("launch", 0, reads=[1], writes=[2]))
    chk.on_wait(hook("wait", 0, reads=[1], writes=[2]))
    chk.on_exec(hook("exec", 1, reads=[2]))
    chk.check()

    # read of an in-flight destination
    chk.begin_step()
    chk.on_launch(hook("launch", 0, reads=[1], writes=[2]))
    chk.on_exec(hook("exec", 1, reads=[2]))
    with pytest.raises(RuntimeError):
        chk.check()

    # FREE of an in-flight source
    chk.begin_step()
    chk.on_launch(hook("launch", 0, reads=[1], writes=[2]))
    chk.on_exec(hook("exec", 1, kills=[1]))
    with pytest.raises(RuntimeError):
        chk.check()


def test_race_hook_clean_on_real_program():
    """debug_dispatch_races is now a graph-node hook: a real lowering
    replayed with it enabled stays clean and stays on the fast path."""
    alpa_tpu.init("local")
    prev = global_config.debug_dispatch_races
    global_config.debug_dispatch_races = True
    try:
        _, _, ex = _run_steps("overlap", n_steps=2)
        st = ex.last_dispatch_stats
        assert st["mode"] == "overlap", st
        assert "race" in st["hooks"], st
    finally:
        global_config.debug_dispatch_races = prev


# ---------------------------------------------------------------------
# overhead regression: hooked < 2x unhooked register replay
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_hooked_overhead_under_two_x():
    """Per-instruction cost with every hook class compiled in (trace +
    armed fault sites + flight) must stay under 2x the raw register
    replay — hooks are per-node closures, not an interpreter."""
    alpa_tpu.init("local")
    global_config.pipeline_dispatch_mode = "registers"
    method = PipeshardParallel(
        num_micro_batches=2,
        layer_option=AutoLayerOption(layer_num=8),
        stage_option=UniformStageOption(num_stages=8))
    step = get_mlp_train_step(method, use_value_and_grad=True)
    state, batch = create_mlp_train_state_and_batch(
        batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
        num_layers=8)
    state, loss = step(state, batch)   # compile + lower
    float(loss)
    ex = step.get_last_executable()

    def best_stats(state):
        best = None
        for _ in range(5):
            state, loss = step(state, batch)
            float(loss)
            st = dict(ex.last_dispatch_stats)
            if best is None or st["per_inst_us"] < best["per_inst_us"]:
                best = st
        return best, state

    # hooks off: flight disabled too, so the replay takes the raw
    # closure loop
    global_config.flight_recorder = False
    off, state = best_stats(state)
    assert not off.get("hooks"), off
    # hooks on: trace + armed-not-firing fault plan + flight
    global_config.flight_recorder = True
    prev_enabled = ttrace.set_enabled(True)
    try:
        ttrace.get_recorder().clear()
        with fault.FaultPlan(fault.FaultSpec(
                "stage_launch", kind="error", after=10**9)):
            on, state = best_stats(state)
    finally:
        ttrace.set_enabled(prev_enabled)
    assert set(on["hooks"]) == {"trace", "fault", "flight"}, on
    assert on["per_inst_us"] < 2.0 * off["per_inst_us"], (on, off)
