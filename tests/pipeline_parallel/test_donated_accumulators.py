"""A backward stage's donated gradient accumulators keep the sharding their
gradients are produced in (ISSUE 50): the planner is handed the (accumulator,
sum) pairs, prices the way from each product to its accumulator's spec, and
the update programs are planned with the sums' and the parameters' shardings
given.  The ``toy-gpt-pipeshard`` shapes on four virtual devices, two stages
of two.
"""
import json
import os

import jax
import numpy as np
import pytest

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.pipeline_parallel.layer_construction import ManualLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.testing import (assert_allclose, donated_accumulator_faults,
                              get_gpt_train_step)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def toy_pipeshard_step(batch_size=4, num_micro_batches=2):
    """The rehearsal cell's model and method (``chipbench/configs/
    toy-gpt-pipeshard.json``, ``traffic/toy-lm.json``)."""
    from chipbench import program
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "toy-gpt-pipeshard.json")) as f:
        config = json.load(f)
    knobs = config["train"]
    stages = knobs["parallel"]["stages"]
    gcfg = program.gpt_config(
        config, attention_impl=knobs["attention_impl"],
        remat_blocks=knobs["remat_blocks"],
        pipeline_boundary_every=config["num_hidden_layers"] // stages)
    method = PipeshardParallel(
        num_micro_batches=num_micro_batches,
        pipeline_schedule=knobs["parallel"]["schedule"],
        layer_option=ManualLayerOption(),
        stage_option=UniformStageOption(num_stages=stages))
    return gcfg, method, batch_size


@pytest.fixture(scope="module")
def toy():
    """One pipeshard step and one single-device step from the same state,
    and what the tests below read of them."""
    alpa_tpu.init("local", devices=jax.devices()[:4])
    gcfg, method, batch_size = toy_pipeshard_step()
    step, create_state, batch = get_gpt_train_step(gcfg, batch_size, method)
    serial, _, _ = get_gpt_train_step(gcfg, batch_size)
    was = ttrace.set_enabled(True)
    ttrace.get_recorder().clear()
    try:
        executable, _ = step.get_executable(
            jax.eval_shape(create_state),
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        spans = ttrace.get_recorder().spans()
    finally:
        ttrace.set_enabled(was)
        ttrace.get_recorder().clear()
    new_p, loss_p = step(create_state(), batch)
    new_s, loss_s = serial(create_state(), batch)
    found = {
        "executable": executable,
        "spans": [s for s in spans if s["name"] == "ilp-solve"],
        "all_spans": spans,
        "loss": (float(loss_s), float(loss_p)),
        "params": (jax.device_get(new_s.params),
                   jax.device_get(new_p.params)),
        "report": executable.get_resharding_report(),
    }
    yield found
    alpa_tpu.shutdown()


def test_backward_stages_gather_no_sum_and_updates_one_a_leaf(toy):
    assert donated_accumulator_faults(toy["executable"]) == []


def test_step_equals_the_single_device_step(toy):
    assert_allclose(toy["loss"][0], toy["loss"][1], 2e-3, 2e-3)
    assert_allclose(toy["params"][0], toy["params"][1], 5e-3, 5e-3)


@pytest.mark.parametrize("stage", ["stage_0_bwd", "stage_1_bwd"])
def test_solve_span_says_what_became_of_the_pairs(toy, stage):
    span, = [s for s in toy["spans"]
             if (s["args"] or {}).get("stage") == stage]
    args = span["args"]
    exec_, = [e for e in toy["executable"].stage_execs if e.name == stage]
    assert args["alias_pairs"] == len(exec_.donated_pairs()) > 0
    assert 0 < args["alias_sharded"] <= args["alias_pairs"]
    assert args["alias_reshard_bytes"] == 0
    assert exec_.plan_stats == {k: args[k] for k in exec_.plan_stats}
    assert (f"{stage}: {args['alias_pairs']} donated pairs, "
            f"{args['alias_sharded']} sharded, 0 B") in toy["report"]


def test_report_reads_the_pairs_from_the_spans(toy):
    """What ``perf_tool.py analyze`` prints of a saved trace."""
    from alpa_tpu.telemetry import perf
    donated = perf.donated_from_spans(toy["spans"])
    assert {"stage_0_bwd", "stage_1_bwd", "apply_grad_0",
            "apply_grad_1"} <= set(donated)
    for name in ("stage_0_bwd", "stage_1_bwd"):
        assert perf.format_alias_stats(name, donated[name]) in toy["report"]


def test_state_is_created_where_the_step_reads_it():
    """``CreateStateParallel`` on a pipeshard step: every leaf is made on
    its mesh in the sharding the step reads it with (the optimizer's
    moments of a sharded sum among them), and holds what a plain call of
    the init function gives."""
    from alpa_tpu.create_state_parallel import CreateStateParallel
    alpa_tpu.init("local", devices=jax.devices()[:4])
    gcfg, method, batch_size = toy_pipeshard_step()
    step, create_state, batch = get_gpt_train_step(gcfg, batch_size, method)
    abstract = (jax.eval_shape(create_state), jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
    state = alpa_tpu.parallelize(
        create_state, method=CreateStateParallel(step, abstract),
        batch_argnums=())()
    executable, _ = step.get_executable(*abstract)
    leaves = jax.tree_util.tree_leaves(state)
    placed = sharded = 0
    for v, x in zip(executable.global_invars, leaves):
        if v in executable.input_place:
            _, sharding = executable.input_place[v][0]
            assert x.sharding.is_equivalent_to(sharding, x.ndim), v
            placed += 1
            sharded += not x.sharding.is_fully_replicated
    assert placed > 0 and sharded > 0
    assert_allclose(jax.device_get(create_state().params),
                    jax.device_get(state.params), 1e-6, 1e-6)
    new_state, loss = step(state, batch)
    assert np.isfinite(float(loss))
