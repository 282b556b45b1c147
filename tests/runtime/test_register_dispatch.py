"""Register-file dispatch fast path (ISSUE 2 tentpole).

Oracle 1: numerics — the register path must be bit-identical to the
sequential interpreter over multiple donated train steps (same RUN
executables, same resharding endpoints, only the dispatch machinery
differs).  Oracle 2: structure — the lowering covers every instruction,
resolves every (var, microbatch) key to a slot, and the executable
reports mode "registers" with stable per-call stats.
"""
import numpy as np
import pytest

import alpa_tpu
import jax
from alpa_tpu import PipeshardParallel
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)


@pytest.fixture(autouse=True)
def _restore_dispatch_mode():
    prev = global_config.pipeline_dispatch_mode
    yield
    global_config.pipeline_dispatch_mode = prev


def _fresh_step_and_state(num_layers=4, num_stages=4, num_micro_batches=2):
    method = PipeshardParallel(
        num_micro_batches=num_micro_batches,
        layer_option=AutoLayerOption(layer_num=num_layers),
        stage_option=UniformStageOption(num_stages=num_stages))
    step = get_mlp_train_step(method, use_value_and_grad=False)
    state, batch = create_mlp_train_state_and_batch(
        batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
        num_layers=num_layers, manual_pipeline_layer=False)
    return step, state, batch


def _run_steps(mode, n_steps=3):
    global_config.pipeline_dispatch_mode = mode
    step, state, batch = _fresh_step_and_state()
    val = None
    for _ in range(n_steps):
        state, val = step(state, batch)
    ex = step.get_last_executable()
    return state, val, ex


def test_register_path_matches_interpreter_bitwise():
    alpa_tpu.init("local")
    state_s, val_s, ex_s = _run_steps("sequential")
    state_r, val_r, ex_r = _run_steps("registers")
    assert ex_s.last_dispatch_stats["mode"] == "sequential"
    assert ex_r.last_dispatch_stats["mode"] == "registers"
    leaves_s = jax.tree_util.tree_leaves(state_s.params)
    leaves_r = jax.tree_util.tree_leaves(state_r.params)
    assert len(leaves_s) == len(leaves_r) > 0
    for a, b in zip(leaves_s, leaves_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(val_s), np.asarray(val_r))


def test_auto_mode_picks_overlap_when_eligible():
    """auto on a multi-mesh payload with cross-mesh RESHARDs upgrades to
    overlap dispatch (ISSUE 4); with overlap_resharding off it pins the
    synchronous register replay."""
    alpa_tpu.init("local")
    _, _, ex = _run_steps("auto", n_steps=1)
    assert ex.last_dispatch_stats["mode"] == "overlap"
    prev = global_config.overlap_resharding
    global_config.overlap_resharding = False
    try:
        _, _, ex = _run_steps("auto", n_steps=1)
        assert ex.last_dispatch_stats["mode"] == "registers"
    finally:
        global_config.overlap_resharding = prev


def test_lowering_covers_every_instruction():
    alpa_tpu.init("local")
    _, _, ex = _run_steps("registers", n_steps=1)
    prog = ex._register_program
    assert prog is not None
    assert prog.n_instructions == len(ex.instructions)
    # one op per original instruction, minus ops saved by coalescing
    assert len(prog.ops) <= prog.n_instructions
    if prog.n_coalesced_groups == 0:
        assert len(prog.ops) == prog.n_instructions
    by = prog.by_opcode
    assert set(by) == {"RUN", "RESHARD", "FREE"}
    assert sum(by.values()) == prog.n_instructions
    assert prog.num_slots > 0
    # every op's fingerprint input is stable across calls
    assert prog.fingerprint() == prog.fingerprint()


def test_register_stats_shape():
    alpa_tpu.init("local")
    _, _, ex = _run_steps("registers", n_steps=2)
    st = ex.last_dispatch_stats
    assert st["mode"] == "registers"
    assert st["per_inst_us"] > 0
    assert st["n_instructions"] == len(ex.instructions)


def test_planned_resharding_falls_back_to_interpreter():
    """The register path requires device_put resharding; "planned" mode
    must fall back to the interpreter even when registers is requested."""
    alpa_tpu.init("local")
    prev = global_config.resharding_execution
    global_config.resharding_execution = "planned"
    try:
        _, _, ex = _run_steps("auto", n_steps=1)
        assert ex.last_dispatch_stats["mode"] != "registers"
    finally:
        global_config.resharding_execution = prev


def _two_stage_step(n_micro_batches):
    step, state, batch = _fresh_step_and_state(2, 2, n_micro_batches)
    state, _ = step(state, batch)           # compile + lower
    jax.block_until_ready(state)
    return step, state, batch


@pytest.mark.parametrize("n_mb", [2, 4])
def test_ops_per_step_follow_the_instruction_list(n_mb):
    """The CPU twin of the benchmark's ``pipeshard_ops_per_step``: the
    op spans one step records are what the emitter's instruction list
    predicts for 2 stages and ``n_mb`` micro-batches, in the mode the
    four-chip cell runs (``auto`` -> ``overlap``)."""
    from alpa_tpu.telemetry import trace as ttrace
    alpa_tpu.init("local")
    step, state, batch = _two_stage_step(n_mb)
    ex = step.get_last_executable()
    prog = ex._register_programs["overlap"]
    by = prog.by_opcode
    # forward and backward of each stage a micro-batch and one
    # apply-grad a stage; one activation 0->1 and one gradient 1->0 a
    # micro-batch, every one of them between the meshes
    assert by["RUN"] == 2 * 2 * n_mb + 2
    assert by["RESHARD"] == prog.n_cross_mesh == 2 * n_mb
    assert sum(by.values()) == prog.n_instructions == len(ex.instructions)
    # overlap splits a cross-mesh RESHARD into its launch and its wait
    assert len(prog.ops) == prog.n_instructions + prog.n_cross_mesh

    prev = ttrace.set_enabled(True)
    try:
        ttrace.get_recorder().clear()
        state, _ = step(state, batch)
        jax.block_until_ready(state)
    finally:
        ttrace.set_enabled(prev)
    assert ex.last_dispatch_stats["n_ops"] == len(prog.ops)
    spans = ttrace.get_recorder().spans()
    [step_span] = [s for s in spans if s["name"] == "pipeshard.step"]
    end = step_span["ts_us"] + step_span["dur_us"]
    ops = [s for s in spans
           if s["category"] in ("instruction", "transfer") and
           step_span["ts_us"] <= s["ts_us"] <= end]
    # one span a RUN and a FREE; five a cross-mesh transfer (LAUNCH,
    # the pool's RESHARD with its wait and wire parts, WAIT)
    assert len(ops) == by["RUN"] + by["FREE"] + 5 * prog.n_cross_mesh


def test_replay_without_instrumentation_calls_no_hook(monkeypatch):
    """With tracing, fault sites, race checks and the flight recorder
    all off, a step replays the raw op closures: no hook is compiled or
    called, and nothing is recorded."""
    from alpa_tpu.telemetry import flight as tflight
    from alpa_tpu.telemetry import trace as ttrace
    alpa_tpu.init("local")
    global_config.pipeline_dispatch_mode = "registers"
    step, state, batch = _two_stage_step(2)
    prog = step.get_last_executable()._register_program

    def no_hooks(*args, **kwargs):
        raise AssertionError("a hook path ran with nothing to instrument")

    monkeypatch.setattr(global_config, "flight_recorder", False)
    monkeypatch.setattr(prog, "_execute_hooked", no_hooks)
    monkeypatch.setattr(prog, "_compile_hooks", no_hooks)
    assert not ttrace.enabled()
    ttrace.get_recorder().clear()
    flight_before = tflight.get_recorder().snapshot()
    state, _ = step(state, batch)
    jax.block_until_ready(state)
    assert step.get_last_executable().last_dispatch_stats["hooks"] == ()
    assert ttrace.get_recorder().spans() == []
    assert tflight.get_recorder().snapshot() == flight_before


class _Token:
    """Stands for one output of a dispatched RUN."""

    def __init__(self, log, name, deleted=False):
        self.log, self.name, self.deleted = log, name, deleted

    def is_deleted(self):
        return self.deleted

    def block_until_ready(self):
        self.log.append(self.name)


def test_run_ahead_settles_the_oldest_and_skips_a_donated_one():
    """A mesh's queue of dispatched RUNs: nothing waits until
    ``_RUN_AHEAD`` are out, then the oldest is waited for, unless a later
    RUN was given it to donate."""
    import collections
    from alpa_tpu.pipeline_parallel import runtime_emitter as re_
    waited, queue = [], collections.deque()
    for i in range(re_._RUN_AHEAD):
        re_._settle_run_ahead(queue)
        queue.append(_Token(waited, f"run{i}", deleted=(i == 1)))
    assert waited == []
    re_._settle_run_ahead(queue)
    assert waited == ["run0"] and len(queue) == re_._RUN_AHEAD - 1
    queue.append(_Token(waited, "run2"))
    re_._settle_run_ahead(queue)            # run1 was donated: no wait
    assert waited == ["run0"] and [t.name for t in queue] == ["run2"]


@pytest.mark.parametrize("mode", ["registers", "overlap"])
def test_a_step_leaves_at_most_run_ahead_runs_of_a_mesh_unsettled(mode):
    """The lowered program bounds how far the driver dispatches ahead of
    a mesh, in both lowered modes: each RUN op settles its mesh's queue
    before it dispatches, and stands in it by its smallest output."""
    from alpa_tpu.pipeline_parallel import runtime_emitter as re_
    alpa_tpu.init("local")
    _, _, ex = _run_steps(mode, n_steps=2)
    prog = ex._register_programs[mode]
    assert sorted(prog.run_ahead) == list(range(4))      # four stages
    for queue in prog.run_ahead.values():
        assert 1 <= len(queue) <= re_._RUN_AHEAD
        for token in queue:
            assert isinstance(token, jax.Array)
