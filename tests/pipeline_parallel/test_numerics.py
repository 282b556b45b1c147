"""Numerics certification (ISSUE 14 tentpole).

Oracle 1: the precision-flow abstract interpretation certifies a
hand-built quantized 2-mesh plan with the exact composed error bound
(``1/254`` of block max per int8 hop) and nothing but the per-hop
notes; a full-precision plan certifies with zero findings.  Oracle 2:
every mutation class is caught with its named finding — a quantized
weight edge (numerics.lossy-weight-path), quantized optimizer state
reached through a donated RUN (numerics.lossy-opt-state-path), a
composed bound over the budget (numerics.budget-exceeded), a
below-fp32 accumulator (numerics.bf16-accumulation warning) — and the
severities route through ``verify_model``'s merged verdict.  Oracle 3:
the committed fixture certifies deterministically, the perf gate pins
its exact bound/finding counts, and ``verify_tool.py numerics`` emits
the stable ``alpa-numerics/v1`` schema.  Oracle 4: on a real 2-mesh
pipeline the default knobs (quantization off) yield zero ``numerics.*``
findings, ``verify_plans_numerics="error"`` blocks the launch of an
over-budget quantized plan independently of ``verify_plans``, warm
restarts replay the identical verdict and re-export the gauges, and
``numerics.txt`` lands in the debug dump.
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.analysis import model_check as mc
from alpa_tpu.analysis import numerics as num
from alpa_tpu.analysis import plan_verifier as pv
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "results",
                       "numerics_fixture_plan.json")

INT8_HOP = 1.0 / 254.0   # == reshard_codec.ERROR_BOUND["int8"]


@pytest.fixture(autouse=True)
def _restore_globals():
    prev = (global_config.pipeline_dispatch_mode,
            global_config.verify_plans,
            global_config.verify_plans_numerics,
            global_config.numerics_error_budget,
            global_config.reshard_quantize,
            global_config.reshard_quantize_min_bytes,
            global_config.compile_cache_dir)
    yield
    (global_config.pipeline_dispatch_mode,
     global_config.verify_plans,
     global_config.verify_plans_numerics,
     global_config.numerics_error_budget,
     global_config.reshard_quantize,
     global_config.reshard_quantize_min_bytes,
     global_config.compile_cache_dir) = prev
    from alpa_tpu.compile_cache import reset_compile_cache
    reset_compile_cache()


def _compile_pipeline(num_stages=2, mode="registers"):
    alpa_tpu.init("local")
    global_config.pipeline_dispatch_mode = mode
    method = PipeshardParallel(
        num_micro_batches=2,
        layer_option=AutoLayerOption(layer_num=4),
        stage_option=UniformStageOption(num_stages=num_stages))
    step = get_mlp_train_step(method, use_value_and_grad=False)
    state, batch = create_mlp_train_state_and_batch(
        batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
        num_layers=4, manual_pipeline_layer=False)
    state, _ = step(state, batch)
    return step.get_last_executable(), state, batch, step


# ---------------------------------------------------------------------
# oracle 1 + 2: hand-built 2-mesh models
# ---------------------------------------------------------------------

_F32 = "float32"
_AVAL = ((4, 4), _F32)
_PREC = {"n_matmul": 1, "n_reduce": 0, "n_cast": 0,
         "min_accum": "float32", "below_fp32_accum": False}


def _slots():
    return {
        0: pv.SlotModel(0, "x@m0", 0, 0, (4, 4), _F32, 64,
                        preplaced=True, provenance="activation"),
        1: pv.SlotModel(1, "w@m0", -1, 0, (4, 4), _F32, 64,
                        preplaced=True, provenance="param"),
        2: pv.SlotModel(2, "h0@m0", 0, 0, (4, 4), _F32, 64),
        3: pv.SlotModel(3, "h0@m1", 0, 1, (4, 4), _F32, 64),
        4: pv.SlotModel(4, "out@m1", 0, 1, (4, 4), _F32, 64,
                        protected=True),
    }


def _ops():
    return [
        pv.OpModel(0, "RUN", 0, reads=(0, 1), writes=(2,),
                   in_avals=(_AVAL, _AVAL), out_avals=(_AVAL,),
                   precision=dict(_PREC), label="RUN stage0"),
        pv.OpModel(1, "RESHARD", 0, reads=(2,), writes=(3,),
                   edge=(0, 1), cross=True, nbytes=64,
                   strategy="quantized", codec="int8", groupable=False,
                   label="RESHARD h0 0->1 [int8]"),
        pv.OpModel(2, "RUN", 1, reads=(3,), writes=(4,),
                   in_avals=(_AVAL,), out_avals=(_AVAL,),
                   precision=dict(_PREC), label="RUN stage1"),
        pv.OpModel(3, "FREE", 0, kills=(2,), label="FREE h0@m0"),
        pv.OpModel(4, "FREE", 1, kills=(3,), label="FREE h0@m1"),
    ]


def _model(ops, slots=None, streams=None, deps=None):
    return pv.PlanModel(
        ops=ops, slots=slots or _slots(), num_meshes=2,
        streams=streams or [[0, 1, 3], [2, 4]],
        deps=deps if deps is not None else {2: {1}})


def _codes(res):
    return [f.code for f in res.findings]


def test_clean_quantized_model_certifies_with_exact_bound():
    res = num.check_numerics(_model(_ops()))
    assert res.ok, res.format()
    # one lossy hop -> one per-hop note, nothing else
    assert _codes(res) == ["numerics.quantized-reduction"]
    st = res.stats
    assert st["max_error_bound"] == INT8_HOP    # exact, not approx
    assert st["lossy_edges"] == {"int8": 1}
    assert st["n_lossy_collectives"] == 1
    assert st["n_bf16_reductions"] == 0
    [row] = st["bound_table"]                   # protected outputs only
    assert row["var"] == "out@m1"
    assert row["provenance"] == "activation"
    assert row["storage"] == "float32" and row["accum"] == "float32"
    assert row["bound"] == INT8_HOP
    assert list(row["hops"]) == ["0->1:int8"]


def test_full_precision_model_has_zero_findings():
    ops = _ops()
    ops[1] = dataclasses.replace(ops[1], strategy=None, codec=None,
                                 groupable=True)
    res = num.check_numerics(_model(ops))
    assert res.ok and not res.findings, res.format()
    assert res.stats["max_error_bound"] == 0.0
    assert res.stats["lossy_edges"] == {}
    [row] = res.stats["bound_table"]
    assert row["bound"] == 0.0 and not row["hops"]


def test_mutation_quantized_weight_edge_is_lossy_weight_path():
    ops = _ops()
    ops[1] = dataclasses.replace(ops[1], weight=True)
    res = num.check_numerics(_model(ops))
    assert not res.ok
    assert "numerics.lossy-weight-path" in _codes(res), res.format()
    [f] = [f for f in res.findings
           if f.code == "numerics.lossy-weight-path"]
    assert "int8" in f.message and f.op == 1


def test_mutation_donated_opt_state_is_lossy_opt_state_path():
    """Provenance flows through a RUN only via *donated* inputs: an
    in-place optimizer update keeps opt_state provenance, so quantizing
    its output is the named error."""
    slots = _slots()
    slots[0] = dataclasses.replace(slots[0], provenance="opt_state")
    ops = _ops()
    ops[0] = dataclasses.replace(ops[0], kills=(0,))    # donation
    res = num.check_numerics(_model(ops, slots=slots))
    assert not res.ok
    assert "numerics.lossy-opt-state-path" in _codes(res), res.format()


def test_read_only_param_input_does_not_taint_activations():
    """The counterpart of the donation rule: stage0 *reads* the param
    slot (no donation), so its output is a fresh activation and the
    quantized hop is merely the per-hop note."""
    res = num.check_numerics(_model(_ops()))
    assert res.ok
    assert "numerics.lossy-weight-path" not in _codes(res)
    [row] = res.stats["bound_table"]
    assert row["provenance"] == "activation"


def test_mutation_fp8_hop_exceeds_default_budget():
    ops = _ops()
    ops[1] = dataclasses.replace(ops[1], codec="fp8")
    res = num.check_numerics(_model(ops))        # 0.07 > 0.05 default
    assert not res.ok
    assert "numerics.budget-exceeded" in _codes(res), res.format()
    assert res.stats["max_error_bound"] == 0.07
    # a loosened budget clears it (the knob keys the verdict cache)
    res2 = num.check_numerics(_model(ops), budget=0.1)
    assert res2.ok
    assert "numerics.budget-exceeded" not in _codes(res2)


def test_mutation_bf16_accumulation_is_warning():
    ops = _ops()
    ops[2] = dataclasses.replace(
        ops[2], precision={"n_matmul": 1, "n_reduce": 2, "n_cast": 0,
                           "min_accum": "bfloat16",
                           "below_fp32_accum": True})
    res = num.check_numerics(_model(ops))
    assert res.ok                       # warning-class, not error
    assert "numerics.bf16-accumulation" in _codes(res), res.format()
    assert res.stats["n_bf16_reductions"] == 1
    [row] = res.stats["bound_table"]
    assert row["accum"] == "bfloat16"


def test_verify_model_merges_numerics_severities():
    """The sixth analysis routes through the shared verdict: errors
    block, warnings warn, per-hop records land as notes, and the stats
    section is attached verbatim."""
    ops = _ops()
    ops[1] = dataclasses.replace(ops[1], weight=True)
    ops[2] = dataclasses.replace(
        ops[2], precision=dict(_PREC, min_accum="bfloat16",
                               below_fp32_accum=True))
    verdict = pv.verify_model(_model(ops), numerics=True)
    assert not verdict.ok
    assert "numerics.lossy-weight-path" in {f.code for f in
                                            verdict.errors}
    assert "numerics.bf16-accumulation" in {f.code for f in
                                            verdict.warnings}
    assert "numerics.quantized-reduction" in {f.code for f in
                                              verdict.notes}
    assert verdict.stats["numerics"]["lossy_edges"] == {"int8": 1}
    # ... and numerics=False leaves the verdict numerics-free
    clean = pv.verify_model(_model(_ops()), numerics=False)
    assert "numerics" not in clean.stats
    assert not [f for f in clean.findings()
                if f.code.startswith("numerics.")]


# ---------------------------------------------------------------------
# oracle 3: committed fixture, perf gate, tooling schema
# ---------------------------------------------------------------------

def test_fixture_certifies_and_perf_gate_pins_it():
    model, hooks, _ = mc.load_fixture(FIXTURE)
    res = num.check_numerics(model, hooks=hooks)
    assert res.ok, res.format()
    assert _codes(res) == ["numerics.quantized-reduction"] * 2
    assert res.stats["max_error_bound"] == 2 * INT8_HOP
    assert res.stats["lossy_edges"] == {"int8": 2}
    [row] = res.stats["bound_table"]
    assert row["var"] == "out" and list(row["hops"]) == \
        ["0->1:int8", "1->0:int8"]
    # the full six-analysis verdict is clean (the fixture is a real,
    # well-formed plan, not just a numerics prop)
    verdict = pv.verify_model(model, hooks=hooks, numerics=True)
    assert verdict.ok and not verdict.warnings, verdict.format_table()
    from benchmark.perf_gate import gate
    gv = gate({
        "numerics.findings_total": float(len(res.findings)),
        "numerics.lossy_edges":
            float(sum(res.stats["lossy_edges"].values())),
        "numerics.max_error_bound": float(res.stats["max_error_bound"]),
    })
    checked = {c["metric"] for c in gv["checks"]}
    assert {"numerics.findings_total", "numerics.lossy_edges",
            "numerics.max_error_bound"} <= checked
    assert gv["pass"], gv


def test_export_metrics_sets_gauges_from_stats():
    model, hooks, _ = mc.load_fixture(FIXTURE)
    res = num.check_numerics(model, hooks=hooks)
    num._MAX_BOUND.set(0.0)
    num.export_metrics(res.stats)
    assert num._MAX_BOUND.value == 2 * INT8_HOP
    assert num._LOSSY_EDGES.labels("int8").value == 2.0
    # SET (not inc): a replay exports the identical values
    num.export_metrics(res.stats)
    assert num._LOSSY_EDGES.labels("int8").value == 2.0


def test_verify_tool_numerics_schema_and_exit_status():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("scripts", "verify_tool.py"),
         "numerics", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, check=False)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["schema"] == "alpa-numerics/v1"
    assert doc["ok"] is True
    assert doc["stats"]["max_error_bound"] == 2 * INT8_HOP
    assert {f["code"] for f in doc["findings"]} == \
        {"numerics.quantized-reduction"}
    assert all(f["severity"] == "note" for f in doc["findings"])
    # an unmeetable budget flips ok and the exit status
    out = subprocess.run(
        [sys.executable, os.path.join("scripts", "verify_tool.py"),
         "numerics", "--error-budget", "1e-4", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, check=False)
    assert out.returncode == 1, out.stderr
    doc = json.loads(out.stdout)
    assert doc["ok"] is False
    assert "numerics.budget-exceeded" in {f["code"]
                                          for f in doc["findings"]}


# ---------------------------------------------------------------------
# oracle 4: real 2-mesh pipeline end to end
# ---------------------------------------------------------------------

def test_default_knobs_produce_zero_numerics_findings():
    """Quantization is off by default: the certification runs (stats
    attach) but every bound is 0.0 and no numerics.* finding fires."""
    ex, *_ = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    assert verdict is not None and verdict.ok
    st = verdict.stats["numerics"]
    assert st["max_error_bound"] == 0.0
    assert st["lossy_edges"] == {}
    assert st["n_tracked"] > 0
    assert not [f for f in verdict.findings()
                if f.code.startswith("numerics.")]


def test_numerics_off_skips_analysis_entirely():
    global_config.verify_plans_numerics = "off"
    ex, *_ = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    assert verdict is not None and verdict.ok
    assert "numerics" not in verdict.stats


def test_quantized_pipeline_certifies_then_error_mode_blocks_launch():
    """With the codec on, real cross-stage activations pick up composed
    int8 bounds (certified under the default budget); tightening the
    budget under verify_plans_numerics='error' refuses the launch with
    PlanVerificationError — independently of verify_plans (left at
    'warn')."""
    global_config.reshard_quantize = "int8"
    global_config.reshard_quantize_min_bytes = 1
    ex, state, batch, step = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    st = verdict.stats["numerics"]
    assert sum(st["lossy_edges"].values()) >= 1, st
    assert st["max_error_bound"] >= INT8_HOP
    assert verdict.ok, verdict.format_table()   # activations may lose
    # tighten below one int8 hop; the budget keys the verdict cache, so
    # re-lowering re-runs the analysis instead of replaying the pass
    global_config.numerics_error_budget = 1e-4
    global_config.verify_plans_numerics = "error"
    assert global_config.verify_plans == "warn"
    ex._register_programs = {}
    ex._register_program = None
    try:
        with pytest.raises(pv.PlanVerificationError) as exc_info:
            step(state, batch)
        assert "numerics.budget-exceeded" in str(exc_info.value)
    finally:
        ex._register_programs = {}
        ex._register_program = None


def test_warm_restart_replays_verdict_and_reexports_gauges(tmp_path):
    from alpa_tpu.compile_cache import (get_compile_cache,
                                        reset_compile_cache)
    global_config.compile_cache_dir = str(tmp_path)
    global_config.reshard_quantize = "int8"
    global_config.reshard_quantize_min_bytes = 1
    reset_compile_cache()
    ex, *_ = _compile_pipeline(num_stages=2)
    cold = ex._register_programs["registers"].verdict
    assert cold.stats["numerics"]["lossy_edges"], cold.stats
    # warm restart: wipe the lowering and the in-memory tier
    reset_compile_cache()
    ex._register_programs = {}
    ex._register_program = None
    num._MAX_BOUND.set(0.0)
    ex._ensure_lowered("registers")
    warm = ex._register_programs["registers"].verdict
    assert warm.to_dict() == cold.to_dict()
    # the cache-hit path re-exports the gauges from the replayed stats
    assert num._MAX_BOUND.value == \
        cold.stats["numerics"]["max_error_bound"]
    stats = get_compile_cache().stats()["namespaces"]["plan_verdict"]
    assert stats["hits"] >= 1, stats


def test_numerics_txt_in_debug_dump(tmp_path):
    from alpa_tpu.monitoring import dump_debug_info
    global_config.reshard_quantize = "int8"
    global_config.reshard_quantize_min_bytes = 1
    ex, *_ = _compile_pipeline(num_stages=2)
    dump_debug_info(ex, str(tmp_path))
    path = tmp_path / "numerics.txt"
    assert path.exists()
    text = path.read_text()
    assert "numerics certification" in text
    assert "int8=" in text and "per-output bounds:" in text
