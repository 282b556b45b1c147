"""Translation validation (ISSUE 15 tentpole).

Oracle 1: the symbolic executor certifies a hand-built 2-mesh
4-microbatch accumulation plan — every protected output's term graph
equals the serially-composed reference, using exactly the documented
axioms (accumulation reassociation, resharding identity).  Oracle 2:
every mutation class is caught with its named finding and a rendered
term-diff witness — swapped same-shape operands
(equiv.output-mismatch), a dropped microbatch contribution
(equiv.dropped-microbatch), a duplicated accumulation edge
(equiv.duplicated-accumulation), a read of a donated slot after its
update consumed it (equiv.stale-operand) — and the severities route
through ``verify_model``'s merged verdict.  Oracle 3: the committed
fixture matches the in-test generator byte for byte, certifies
deterministically (the perf gate pins its exact term count), and
``verify_tool.py equiv`` emits the stable ``alpa-equiv/v1`` schema.
Oracle 4: on a real 2-mesh pipeline the default knobs prove every
protected output with zero ``equiv.*`` findings,
``verify_plans_equiv="error"`` blocks the launch of a tampered
reference independently of ``verify_plans``, warm restarts replay the
byte-identical cached verdict, and ``equiv.txt`` lands in the debug
dump.
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.analysis import equivalence as eq
from alpa_tpu.analysis import model_check as mc
from alpa_tpu.analysis import plan_verifier as pv
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "results",
                       "equiv_fixture_plan.json")


@pytest.fixture(autouse=True)
def _restore_globals():
    prev = (global_config.pipeline_dispatch_mode,
            global_config.verify_plans,
            global_config.verify_plans_equiv,
            global_config.equiv_term_budget,
            global_config.compile_cache_dir)
    yield
    (global_config.pipeline_dispatch_mode,
     global_config.verify_plans,
     global_config.verify_plans_equiv,
     global_config.equiv_term_budget,
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
# the hand-built 2-mesh 4-microbatch plan (== the committed fixture)
# ---------------------------------------------------------------------
#
# Shape: stage0 on mesh 0 maps each microbatch x -> h; h reshards to
# mesh 1; stage1 accumulates gradient contributions in place (slot 15,
# donated accumulator); apply consumes the summed gradient and the
# donated weight into the protected updated weight (slot 16).

_S0 = "stage0#fix0seed"
_S1 = "stage1#fix1seed"
_AP = "apply#fixapseed"
N_MB = 4
_F32 = "float32"
_AVAL = ((4, 4), _F32)
_PREC = {"n_matmul": 1, "n_reduce": 0, "n_cast": 0,
         "min_accum": "float32", "below_fp32_accum": False}


def _fixture_slots():
    s = {}
    for mb in range(N_MB):
        s[mb] = pv.SlotModel(mb, "x", mb, 0, (4, 4), _F32, 64,
                             preplaced=True, provenance="activation")
    s[4] = pv.SlotModel(4, "w0", -1, 0, (4, 4), _F32, 64,
                        preplaced=True, provenance="param")
    for mb in range(N_MB):
        s[5 + mb] = pv.SlotModel(5 + mb, "h", mb, 0, (4, 4), _F32, 64)
        s[9 + mb] = pv.SlotModel(9 + mb, "h", mb, 1, (4, 4), _F32, 64)
    s[13] = pv.SlotModel(13, "w1", -1, 1, (4, 4), _F32, 64,
                         preplaced=True, provenance="param")
    s[14] = pv.SlotModel(14, "g1", -1, 1, (4, 4), _F32, 64,
                         preplaced=True, provenance="gradient")
    s[15] = pv.SlotModel(15, "gsum", -1, 1, (4, 4), _F32, 64,
                         protected=True, provenance="gradient")
    s[16] = pv.SlotModel(16, "w1_new", -1, 1, (4, 4), _F32, 64,
                         protected=True, provenance="param")
    return s


def _fixture_ops():
    ops = []
    for mb in range(N_MB):
        b = 5 * mb
        ops.append(pv.OpModel(
            b + 0, "RUN", 0, reads=(mb, 4), writes=(5 + mb,),
            in_avals=(_AVAL, _AVAL), out_avals=(_AVAL,),
            precision=dict(_PREC),
            equiv={"stage": _S0, "mb": mb, "donate": [], "acc": {}},
            label=f"RUN stage0 mb{mb}"))
        # the RESHARD lives on the destination stream (its RECV half);
        # the model checker interleaves the SEND into the source stream
        ops.append(pv.OpModel(
            b + 1, "RESHARD", 1, reads=(5 + mb,), writes=(9 + mb,),
            edge=(0, 1), cross=True, nbytes=64,
            label=f"RESHARD h mb{mb} 0->1"))
        acc_slot = 14 if mb == 0 else 15
        ops.append(pv.OpModel(
            b + 2, "RUN", 1, reads=(9 + mb, 13, acc_slot),
            writes=(15,), kills=(acc_slot,),
            in_avals=(_AVAL, _AVAL, _AVAL), out_avals=(_AVAL,),
            precision=dict(_PREC),
            equiv={"stage": _S1, "mb": mb, "donate": [2],
                   "acc": {"0": 2}},
            label=f"RUN stage1 mb{mb}"))
        ops.append(pv.OpModel(b + 3, "FREE", 0, kills=(5 + mb,),
                              label=f"FREE h@m0 mb{mb}"))
        ops.append(pv.OpModel(b + 4, "FREE", 1, kills=(9 + mb,),
                              label=f"FREE h@m1 mb{mb}"))
    ops.append(pv.OpModel(
        5 * N_MB, "RUN", 1, reads=(15, 13), writes=(16,), kills=(13,),
        in_avals=(_AVAL, _AVAL), out_avals=(_AVAL,),
        precision=dict(_PREC),
        equiv={"stage": _AP, "mb": -1, "donate": [1], "acc": {}},
        label="RUN apply"))
    return ops


def _fixture_reference():
    apps = []
    for mb in range(N_MB):
        apps.append({"stage": _S0, "mb": mb, "donate": [], "acc": {},
                     "in": [["x", mb], ["w0", -1]],
                     "out": [["h", mb]]})
    for mb in range(N_MB):
        apps.append({"stage": _S1, "mb": mb, "donate": [2],
                     "acc": {"0": 2},
                     "in": [["h", mb], ["w1", -1],
                            ["g1" if mb == 0 else "gsum", -1]],
                     "out": [["gsum", -1]]})
    apps.append({"stage": _AP, "mb": -1, "donate": [1], "acc": {},
                 "in": [["gsum", -1], ["w1", -1]],
                 "out": [["w1_new", -1]]})
    return {"format": "alpa-equiv-reference/v1", "apps": apps,
            "num_microbatches": N_MB}


def _fixture_model(ops=None):
    streams = [[], []]
    ops = ops if ops is not None else _fixture_ops()
    for op in ops:
        streams[op.mesh].append(op.idx)
    deps = {}
    for mb in range(N_MB):
        b = 5 * mb
        deps[b + 1] = {b + 0}       # SEND waits for the h producer
        deps[b + 3] = {b + 1}       # FREE h@m0 waits for the SEND
    return pv.PlanModel(
        ops=ops, slots=_fixture_slots(), num_meshes=2,
        streams=streams, deps=deps, reference=_fixture_reference())


def _codes(res):
    return [f.code for f in res.findings]


# ---------------------------------------------------------------------
# oracle 1: the clean plan proves
# ---------------------------------------------------------------------

def test_clean_plan_proves_every_protected_output():
    res = eq.check_equiv(_fixture_model())
    assert res.ok and not res.findings, res.format()
    st = res.stats
    assert st["n_outputs"] == 2 and st["n_proved"] == 2
    assert st["num_microbatches"] == N_MB
    assert st["n_apps"] == 2 * N_MB + 1
    assert st["axioms_used"] == [eq.AXIOM_ACC, eq.AXIOM_RESHARD]
    assert not st["partial"]
    by_var = {r["var"]: r for r in st["per_output"]}
    assert by_var["gsum"]["status"] == "proved"
    assert by_var["w1_new"]["status"] == "proved"
    # the accumulated output's proof used both axioms
    assert by_var["gsum"]["axioms"] == \
        [eq.AXIOM_ACC, eq.AXIOM_RESHARD]


def test_sum_terms_are_order_insensitive_by_construction():
    """Reassociation/commutation is baked into term identity: any
    member order and nesting of the same multiset interns to one id."""
    t = eq.TermTable()
    a, b, c = (t.leaf(v, 0) for v in "abc")
    assert t.sum_((a, t.sum_((b, c)))) == t.sum_((t.sum_((c, a)), b))
    # ... but a genuine multiset difference is a different term
    assert t.sum_((a, b)) != t.sum_((a, b, b))


def test_candidate_schedule_order_does_not_matter():
    """The proof is schedule-independent: reversing the interleaving of
    the two mesh streams (the flat emission order stays topological)
    yields the identical stats."""
    res = eq.check_equiv(_fixture_model())
    model = _fixture_model()
    # drop all FREEs of mesh-0 h slots to the very end: a legal
    # reordering (no op reads them afterwards)
    frees = [op for op in model.ops
             if op.kind == "FREE" and op.mesh == 0]
    rest = [op for op in model.ops
            if not (op.kind == "FREE" and op.mesh == 0)]
    model2 = dataclasses.replace(model, ops=rest + frees)
    res2 = eq.check_equiv(model2)
    assert res2.ok
    assert res2.stats["n_terms"] == res.stats["n_terms"]
    assert res2.stats["n_proved"] == res.stats["n_proved"]


def test_budget_exhaustion_degrades_to_partial_note():
    res = eq.check_equiv(_fixture_model(), budget=5)
    assert res.ok                     # note-severity: partial, not false
    assert _codes(res) == ["equiv.budget-exhausted"]
    assert res.stats["partial"] is True
    assert res.stats["n_terms"] <= 5


# ---------------------------------------------------------------------
# oracle 2: mutation classes
# ---------------------------------------------------------------------

def test_mutation_swapped_operands_is_output_mismatch():
    ops = _fixture_ops()
    # stage0 mb0 reads (x, w0) -> wire them backwards (same shapes,
    # so the typing pass cannot see it; only the proof can)
    ops[0] = dataclasses.replace(ops[0], reads=(4, 0))
    res = eq.check_equiv(_fixture_model(ops))
    assert not res.ok
    assert "equiv.output-mismatch" in _codes(res), res.format()
    f = next(f for f in res.findings
             if f.code == "equiv.output-mismatch")
    assert "reference computes" in f.message \
        and "the plan computes" in f.message
    by_var = {r["var"]: r for r in res.stats["per_output"]}
    assert by_var["gsum"]["status"] == "mismatched"
    assert "witness" in by_var["gsum"]


def test_mutation_dropped_microbatch_is_named():
    ops = _fixture_ops()
    model = _fixture_model(ops)
    # stage1 mb2 accumulates into a scratch slot instead of the real
    # accumulator (and stops donating it): mb2's contribution is lost
    model.slots[17] = pv.SlotModel(17, "scratch", -1, 1, (4, 4), _F32,
                                   64)
    ops[12] = dataclasses.replace(ops[12], reads=(11, 13, 15),
                                  writes=(17,), kills=())
    res = eq.check_equiv(model)
    assert not res.ok
    assert "equiv.dropped-microbatch" in _codes(res), res.format()
    f = next(f for f in res.findings
             if f.code == "equiv.dropped-microbatch")
    assert "missing accumulation member" in f.message
    assert ".mb2(" in f.message       # names the lost contribution


def test_mutation_duplicated_accumulation_is_named():
    ops = _fixture_ops()
    # replace the mb2 h-free with a second mb2 accumulation: the
    # gradient is counted twice
    ops[14] = dataclasses.replace(
        ops[14], kind="RUN", reads=(11, 13, 15), writes=(15,),
        kills=(15,), in_avals=(_AVAL, _AVAL, _AVAL),
        out_avals=(_AVAL,), precision=dict(_PREC),
        equiv={"stage": _S1, "mb": 2, "donate": [2], "acc": {"0": 2}},
        label="RUN stage1 mb2 (dup)")
    res = eq.check_equiv(_fixture_model(ops))
    assert not res.ok
    assert "equiv.duplicated-accumulation" in _codes(res), res.format()
    f = next(f for f in res.findings
             if f.code == "equiv.duplicated-accumulation")
    assert "surplus accumulation member" in f.message
    assert ".mb2(" in f.message


def test_mutation_read_after_donation_is_stale_operand():
    ops = _fixture_ops()
    # stage1 mb1 reads the *initial* accumulator slot — consumed by
    # mb0's donating update — instead of the live running sum
    ops[7] = dataclasses.replace(ops[7], reads=(10, 13, 14),
                                 kills=(14,))
    res = eq.check_equiv(_fixture_model(ops))
    assert not res.ok
    [f] = [f for f in res.findings
           if f.code == "equiv.stale-operand"]
    assert f.op == 7
    assert "consumed at op 2" in f.message
    # downstream outputs are poisoned, not double-reported
    by_var = {r["var"]: r for r in res.stats["per_output"]}
    assert by_var["gsum"]["status"] == "stale"
    assert by_var["w1_new"]["status"] == "stale"
    assert _codes(res) == ["equiv.stale-operand"]


def test_quant_axiom_without_certificate_is_unproven_output():
    """A quantized hop is identity-within-bound — admissible only when
    the numerics certificate backs it; without one the proof degrades
    to the warning-severity unproven finding."""
    ops = _fixture_ops()
    ops[1] = dataclasses.replace(ops[1], strategy="quantized",
                                 codec="int8", groupable=False)
    res = eq.check_equiv(_fixture_model(ops), numerics_ok=True)
    assert res.ok and not res.findings, res.format()
    assert eq.AXIOM_QUANT in res.stats["axioms_used"]
    res2 = eq.check_equiv(_fixture_model(ops), numerics_ok=None)
    assert res2.ok                    # warning-class, not error
    assert "equiv.unproven-output" in _codes(res2), res2.format()
    by_var = {r["var"]: r for r in res2.stats["per_output"]}
    assert by_var["gsum"]["status"] == "unproven"


def test_verify_model_merges_equiv_severities():
    ops = _fixture_ops()
    ops[0] = dataclasses.replace(ops[0], reads=(4, 0))
    verdict = pv.verify_model(_fixture_model(ops), equiv=True)
    assert not verdict.ok
    assert "equiv.output-mismatch" in {f.code for f in verdict.errors}
    assert verdict.stats["equiv"]["n_proved"] < 2
    # ... and equiv=False leaves the verdict equivalence-free
    clean = pv.verify_model(_fixture_model(), equiv=False)
    assert "equiv" not in clean.stats
    assert not [f for f in clean.findings()
                if f.code.startswith("equiv.")]


# ---------------------------------------------------------------------
# oracle 3: committed fixture, perf gate, tooling schema
# ---------------------------------------------------------------------

def test_committed_fixture_matches_generator():
    """The committed JSON is exactly what the in-test builder
    serializes to — regenerate with
    ``json.dump(mc.model_to_dict(_fixture_model()), ..., indent=1,
    sort_keys=True)`` when the plan shape changes."""
    with open(FIXTURE, encoding="utf-8") as f:
        committed = json.load(f)
    generated = json.loads(json.dumps(mc.model_to_dict(
        _fixture_model())))     # tuples -> lists, like the file
    assert committed == generated


def test_fixture_certifies_and_perf_gate_pins_it():
    model, hooks, _ = mc.load_fixture(FIXTURE)
    res = eq.check_equiv(model, hooks=hooks)
    assert res.ok and not res.findings, res.format()
    assert res.stats["n_proved"] == 2
    # hash-consing is deterministic: the exact term count is pinned
    assert res.stats["n_terms"] == 20
    # the full seven-analysis verdict is clean (the fixture is a real,
    # well-formed plan, not just an equivalence prop)
    verdict = pv.verify_model(model, hooks=hooks, model_check=True,
                              numerics=True, equiv=True)
    assert verdict.ok and not verdict.warnings, verdict.format_table()
    from benchmark.perf_gate import gate
    gv = gate({"equiv.terms": float(res.stats["n_terms"])})
    checked = {c["metric"] for c in gv["checks"]}
    assert "equiv.terms" in checked
    assert gv["pass"], gv


def test_fixture_roundtrips_with_reference():
    model, hooks, window = mc.load_fixture(FIXTURE)
    assert model.reference is not None
    assert model.reference["format"] == "alpa-equiv-reference/v1"
    d = mc.model_to_dict(model, hooks=hooks, overlap_window=window)
    model2, _, _ = mc.model_from_dict(d)
    assert model2.reference == model.reference
    assert eq.reference_digest(model2.reference) == \
        eq.reference_digest(model.reference)


def test_export_metrics_counts_and_sets_gauge():
    res = eq.check_equiv(_fixture_model())
    before = eq._EQUIV_TOTAL.labels("ok").value
    eq._TERMS_TOTAL.set(0.0)
    eq.export_metrics(res.stats, "ok")
    assert eq._EQUIV_TOTAL.labels("ok").value == before + 1
    assert eq._TERMS_TOTAL.value == float(res.stats["n_terms"])
    # SET (not inc): a replay exports the identical gauge value
    eq.export_metrics(res.stats, "ok")
    assert eq._TERMS_TOTAL.value == float(res.stats["n_terms"])
    # a skipped run leaves the gauge untouched
    eq.export_metrics(None, "skipped")
    assert eq._TERMS_TOTAL.value == float(res.stats["n_terms"])


def test_verify_tool_equiv_schema_and_exit_status(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("scripts", "verify_tool.py"),
         "equiv", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, check=False)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["schema"] == "alpa-equiv/v1"
    assert doc["ok"] is True and doc["findings"] == []
    assert doc["stats"]["n_proved"] == 2
    assert doc["stats"]["n_terms"] == 20
    # a mutated fixture flips ok, names the finding, and exits 1
    with open(FIXTURE, encoding="utf-8") as f:
        d = json.load(f)
    [op0] = [o for o in d["ops"] if o["idx"] == 0]
    op0["reads"] = [4, 0]
    bad = tmp_path / "bad_fixture.json"
    bad.write_text(json.dumps(d))
    out = subprocess.run(
        [sys.executable, os.path.join("scripts", "verify_tool.py"),
         "equiv", "--fixture", str(bad), "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, check=False)
    assert out.returncode == 1, out.stderr
    doc = json.loads(out.stdout)
    assert doc["ok"] is False
    codes = {f["code"] for f in doc["findings"]}
    assert "equiv.output-mismatch" in codes
    assert all(f["severity"] == "error" for f in doc["findings"]
               if f["code"] == "equiv.output-mismatch")


# ---------------------------------------------------------------------
# oracle 4: real 2-mesh pipeline end to end
# ---------------------------------------------------------------------

def test_default_knobs_prove_real_pipeline_outputs():
    """Default verify_plans_equiv='warn': the validation runs at
    lowering time, proves every protected output of the real 2-stage
    MLP pipeline, and raises zero equiv.* findings."""
    ex, *_ = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    assert verdict is not None and verdict.ok
    st = verdict.stats["equiv"]
    assert st["n_outputs"] > 0
    assert st["n_proved"] == st["n_outputs"], st
    assert not st["partial"]
    assert eq.AXIOM_ACC in st["axioms_used"]
    assert not [f for f in verdict.findings()
                if f.code.startswith("equiv.")]


def test_equiv_off_skips_analysis_entirely():
    global_config.verify_plans_equiv = "off"
    ex, *_ = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    assert verdict is not None and verdict.ok
    assert "equiv" not in verdict.stats


def test_tampered_reference_blocks_launch_in_error_mode(monkeypatch):
    """A lowering that no longer matches its reference decomposition
    must not launch under verify_plans_equiv='error' — independently of
    verify_plans (left at 'warn').  The tampered reference hashes to a
    different cache key, so the cached clean verdict cannot mask it."""
    ex, state, batch, step = _compile_pipeline(num_stages=2)
    orig = eq.build_reference

    def tampered(instructions, num_microbatches=0):
        ref = orig(instructions, num_microbatches)
        ref["apps"] = ref["apps"][:-1]    # drop the last stage app
        return ref

    monkeypatch.setattr(eq, "build_reference", tampered)
    global_config.verify_plans_equiv = "error"
    assert global_config.verify_plans == "warn"
    ex._register_programs = {}
    ex._register_program = None
    try:
        with pytest.raises(pv.PlanVerificationError) as exc_info:
            step(state, batch)
        assert "translation validation failed" in str(exc_info.value)
        assert "equiv." in str(exc_info.value)
    finally:
        ex._register_programs = {}
        ex._register_program = None


def test_warm_restart_replays_byte_identical_verdict(tmp_path):
    from alpa_tpu.compile_cache import (get_compile_cache,
                                        reset_compile_cache)
    global_config.compile_cache_dir = str(tmp_path)
    reset_compile_cache()
    ex, *_ = _compile_pipeline(num_stages=2)
    cold = ex._register_programs["registers"].verdict
    assert cold.stats["equiv"]["n_proved"] > 0, cold.stats
    # warm restart: wipe the lowering and the in-memory tier
    reset_compile_cache()
    ex._register_programs = {}
    ex._register_program = None
    eq._TERMS_TOTAL.set(0.0)
    ex._ensure_lowered("registers")
    warm = ex._register_programs["registers"].verdict
    assert warm.to_dict() == cold.to_dict()
    assert json.dumps(warm.to_dict(), sort_keys=True, default=str) == \
        json.dumps(cold.to_dict(), sort_keys=True, default=str)
    # the cache-hit path re-exports the terms gauge from replayed stats
    assert eq._TERMS_TOTAL.value == \
        float(cold.stats["equiv"]["n_terms"])
    stats = get_compile_cache().stats()["namespaces"]["plan_verdict"]
    assert stats["hits"] >= 1, stats


def test_equiv_txt_in_debug_dump(tmp_path):
    from alpa_tpu.monitoring import dump_debug_info
    ex, *_ = _compile_pipeline(num_stages=2)
    dump_debug_info(ex, str(tmp_path))
    path = tmp_path / "equiv.txt"
    assert path.exists()
    text = path.read_text()
    assert "translation validation" in text
    assert "proved equivalent to the source jaxpr" in text
    assert "per-output proofs:" in text
