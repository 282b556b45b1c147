"""A value one program hands another on the same mesh is planned in the
sharding it is produced in (ISSUE 52): a backward stage is planned with
what its forward stage writes and reads given, unification finds nothing
to move, and the forward stage gathers nothing for a reader that would
have had it whole at no price.  The ``toy-gpt-pipeshard`` shapes on four
virtual devices, two stages of two (``test_donated_accumulators.py``'s
cell and fixture).
"""
import pytest

from alpa_tpu.telemetry import perf
from alpa_tpu.testing import (assert_allclose, donated_accumulator_faults,
                              handed_over_faults)
from tests.pipeline_parallel.test_donated_accumulators import (  # noqa: F401
    toy)

STAGES = ("stage_0_fwd", "stage_1_fwd", "stage_0_bwd", "stage_1_bwd")


def _stage(toy, name):
    stage, = [e for e in toy["executable"].stage_execs if e.name == name]
    return stage


def test_what_a_forward_stage_hands_over_arrives_as_it_left(toy):
    assert handed_over_faults(toy["executable"]) == []
    assert donated_accumulator_faults(toy["executable"]) == []


@pytest.mark.parametrize("name", STAGES)
def test_unification_moves_nothing_of_a_stage(toy, name):
    stage = _stage(toy, name)
    assert stage.unify_overrides == 0
    for planned, made in zip(stage.planned_in, stage.in_shardings):
        assert made is planned or made.is_equivalent_to(
            planned, len(planned.spec))


@pytest.mark.parametrize("name", STAGES)
def test_solve_span_counts_the_given_inputs(toy, name):
    span, = [s for s in toy["spans"]
             if (s["args"] or {}).get("stage") == name]
    args, stage = span["args"], _stage(toy, name)
    if name.endswith("_fwd"):
        # planned first on its mesh: nothing was decided before it
        assert (args["given_in"], args["given_sharded"],
                args["given_reshard_bytes"]) == (0, 0, 0)
    else:
        forward = _stage(toy, name.replace("_bwd", "_fwd"))
        handed = [v for v in stage.invars
                  if v in forward.invars or v in forward.outvars]
        assert args["given_in"] == len(handed) > 0
        assert 0 < args["given_sharded"] <= args["given_in"]
        assert args["given_reshard_bytes"] >= 0
    assert stage.plan_stats == {k: args[k] for k in stage.plan_stats}
    line = (f"{name}: {args['given_in']} inputs given, "
            f"{args['given_sharded']} sharded, "
            f"{args['given_reshard_bytes']} B a run to re-lay them out for "
            "their readers; 0 shardings moved from the plan by unification")
    assert line in toy["report"]


def test_a_forward_stage_writes_what_its_plan_has(toy):
    """Every output a backward stage reads on the mesh is compiled in the
    sharding the forward stage's own solution has it leave in, and some of
    them leave sharded."""
    sharded = 0
    for name in ("stage_0_fwd", "stage_1_fwd"):
        forward = _stage(toy, name)
        backward = _stage(toy, name.replace("_fwd", "_bwd"))
        for k, v in enumerate(forward.outvars):
            if v not in backward.invars or forward.planned_out[k] is None:
                continue
            assert forward.pinned_out[v] is forward.planned_out[k]
            assert forward.out_shardings[k].is_equivalent_to(
                forward.planned_out[k], len(v.aval.shape))
            sharded += not forward.planned_out[k].is_fully_replicated
    assert sharded > 0


def test_report_reads_the_given_inputs_from_the_spans(toy):
    """What ``perf_tool.py analyze`` prints of a saved trace: the solves'
    counters and unification's, by program."""
    found = perf.donated_from_spans(toy["all_spans"])
    assert set(STAGES) <= set(found)
    for name in STAGES:
        assert found[name]["unify_overrides"] == 0
        for line in perf.format_plan_stats(name, found[name]):
            assert line in toy["report"]


def test_step_equals_the_single_device_step(toy):
    assert_allclose(toy["loss"][0], toy["loss"][1], 2e-3, 2e-3)
    assert_allclose(toy["params"][0], toy["params"][1], 5e-3, 5e-3)
