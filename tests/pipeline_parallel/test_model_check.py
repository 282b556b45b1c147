"""Explicit-state plan model checker (ISSUE 13 tentpole).

Oracle 1: the committed 2-mesh fixture plan is proven deadlock- and
hazard-free under BOTH channel semantics (buffered and rendezvous)
within the state budget, with the partial-order reduction ratio
reported and every ``fault.KNOWN_SITES`` site classified.  Oracle 2: a
pinned plan that passes the Kahn-based deadlock analysis is rejected
by the model checker with a rendered counterexample schedule (FIFO
channel reorder — invisible to the happens-before DAG).  Oracle 3:
seeded plan mutations (dropped FREE, swapped cross-stream RESHARD
pair, corrupted channel edge, shrunken in-flight window) are each
caught by a named finding.  Oracle 4: the classification feeds
``fault.call_with_retry`` — under verify_plans="error" a statically
unsafe site refuses real-error retries while injected faults stay
retryable.  Oracle 5: a live 2-mesh lowering is model-checked end to
end in fixture mode (the default) within the wall-clock budget, and
the perf gate pins the fixture's exact state count.
"""
import dataclasses
import json
import os
import random
import subprocess
import sys

import pytest

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.analysis import model_check as mc
from alpa_tpu.analysis import plan_verifier as pv
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.runtime_emitter import OpHook
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "results",
                       "model_check_fixture_plan.json")


@pytest.fixture(autouse=True)
def _restore_globals():
    prev_mode = global_config.pipeline_dispatch_mode
    prev_verify = global_config.verify_plans
    prev_mc = global_config.verify_plans_model_check
    prev_dir = global_config.compile_cache_dir
    yield
    global_config.pipeline_dispatch_mode = prev_mode
    global_config.verify_plans = prev_verify
    global_config.verify_plans_model_check = prev_mc
    global_config.compile_cache_dir = prev_dir
    from alpa_tpu import fault
    fault.install_retry_classification(None)
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


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------
# oracle 1: the committed fixture is proven clean under both semantics
# ---------------------------------------------------------------------

def test_committed_fixture_passes_both_semantics():
    model, hooks, window = mc.load_fixture(FIXTURE)
    result = mc.check_model(model, hooks=hooks, overlap_window=window)
    st = result.stats
    assert st["semantics"] == {"buffered": "pass", "rendezvous": "pass"}
    assert not st["partial"], "fixture must fit the default budget"
    assert result.ok, result.format()
    # exhaustive exploration actually happened, and POR actually reduced
    assert st["states"] > 0 and st["transitions"] > 0
    assert st["por_commits"] > 0
    assert 0.0 < st["reduction_ratio"] < 1.0, st
    assert st["counterexample"] is None
    # the declared overlap window is honored as a model property
    assert st["declared_window"] == window == 2
    assert st["max_inflight"] == 2
    # every registered fault site gets a classification
    from alpa_tpu import fault
    sites = st["retry_sites"]
    assert set(sites) == set(fault.KNOWN_SITES)
    for ent in sites.values():
        assert ent["classification"] in ("safe", "unsafe", "unreachable")
    assert sites["stage_launch"]["classification"] == "unsafe"
    assert "unsafe-donation" in sites["stage_launch"]["reasons"]
    assert sites["cross_mesh_send"]["classification"] == "unsafe"
    assert "fifo-reorder" in sites["cross_mesh_send"]["reasons"]
    assert sites["probe"]["classification"] == "unreachable"
    # retry findings are descriptive notes, not errors
    assert _codes(result.findings) == {"retry.unsafe-donation",
                                       "retry.fifo-reorder"}
    # the human-readable report carries the headline numbers
    text = result.format()
    assert "buffered=pass" in text and "rendezvous=pass" in text
    assert "reduction_ratio" in text and "retry sites" in text


def test_idempotent_plan_classifies_site_safe():
    """A plan whose only hooked ops are idempotent singletons with no
    channel overlap gets its site classified ``safe``."""
    model, _hooks, _w = mc.load_fixture(FIXTURE)
    hooks = [OpHook("exec", "RUN stage1", 1, 0, writes=(3,),
                    slots=(3,), fault_site="stage_launch",
                    members=(1,))]
    _findings, sites = mc.classify_retry_sites(model, hooks)
    assert sites["stage_launch"] == {"classification": "safe",
                                     "reasons": [], "hooks": 1}


def test_budget_exhaustion_is_partial_never_a_false_verdict():
    model, hooks, window = mc.load_fixture(FIXTURE)
    result = mc.check_model(model, hooks=hooks, overlap_window=window,
                            budget=3)
    assert result.stats["partial"] is True
    assert "model.budget-exhausted" in _codes(result.findings)
    # budget exhaustion alone is a note: no error-severity finding
    assert result.ok, result.format()
    assert "partial" in set(result.stats["semantics"].values())


# ---------------------------------------------------------------------
# oracle 2: Kahn passes, the model checker catches the FIFO deadlock
# ---------------------------------------------------------------------

_F32 = "float32"


def _slot(s, var, mesh, **kw):
    return pv.SlotModel(s, var, 0, mesh, (4, 4), _F32, 64, 64, **kw)


def _kahn_blind_deadlock_model():
    """One producer RUN writes two payloads; both RESHARD onto the same
    (0, 1) channel; the destination stream receives them in the
    OPPOSITE order of the sends.  The happens-before DAG is acyclic
    (each RECV waits only on the producer), so Kahn's algorithm — and
    the production-order channel heuristic, which sees identical
    producer positions — pass; but a FIFO channel delivers op1's
    payload first, which receiver-first-op2 can never accept."""
    slots = {0: _slot(0, "x", 0, preplaced=True),
             1: _slot(1, "a", 0), 2: _slot(2, "b", 0),
             3: _slot(3, "a'", 1), 4: _slot(4, "b'", 1),
             5: _slot(5, "y", 1, protected=True)}
    ops = [
        pv.OpModel(0, "RUN", 0, reads=(0,), writes=(1, 2),
                   label="RUN produce"),
        pv.OpModel(1, "RESHARD", 1, reads=(1,), writes=(3,),
                   edge=(0, 1), cross=True, nbytes=64,
                   label="RESHARD a 0->1"),
        pv.OpModel(2, "RESHARD", 1, reads=(2,), writes=(4,),
                   edge=(0, 1), cross=True, nbytes=64,
                   label="RESHARD b 0->1"),
        pv.OpModel(3, "RUN", 1, reads=(3, 4), writes=(5,),
                   label="RUN consume"),
    ]
    return pv.PlanModel(ops=ops, slots=slots, num_meshes=2,
                        streams=[[0], [2, 1, 3]],
                        deps={1: {0}, 2: {0}}, mode="registers")


def test_kahn_passes_but_model_checker_catches_fifo_deadlock():
    model = _kahn_blind_deadlock_model()
    # the pre-existing four analyses accept this plan...
    verdict = pv.verify_model(model)
    assert verdict.ok, verdict.format_table()
    assert not any(c.startswith("deadlock.") for c in
                   _codes(verdict.findings())), verdict.format_table()
    # ...the model checker rejects it under BOTH semantics
    result = mc.check_model(model)
    assert result.stats["semantics"]["buffered"] == "deadlock"
    assert result.stats["semantics"]["rendezvous"] == "deadlock"
    assert "model.deadlock" in _codes(result.findings)
    assert mc.severity_of("model.deadlock") == "error"
    # the counterexample is a rendered instruction schedule naming the
    # blocked receive and the channel state that blocks it
    trace = result.stats["counterexample"]
    assert trace, result.format()
    text = result.format()
    assert "counterexample" in text
    assert "FIFO head" in text, text
    # merged through verify_model the finding is an error -> not ok
    verdict = pv.verify_model(model, model_check=True)
    assert not verdict.ok
    assert "model.deadlock" in _codes(verdict.errors)
    assert verdict.stats["model_check"]["counterexample"]


def test_rendezvous_only_deadlock_is_a_warning():
    """Clean under buffered channels, deadlocked under rendezvous: the
    plan silently relies on staging memory — reported as a warning."""
    slots = {0: _slot(0, "x", 0, preplaced=True),
             1: _slot(1, "a", 0), 2: _slot(2, "b", 0),
             3: _slot(3, "a'", 1), 4: _slot(4, "b'", 1),
             5: _slot(5, "w", 0), 6: _slot(6, "y", 1, protected=True)}
    ops = [
        pv.OpModel(0, "RUN", 0, reads=(0,), writes=(1, 2),
                   label="RUN produce"),
        pv.OpModel(1, "RESHARD", 1, reads=(1,), writes=(3,),
                   edge=(0, 1), cross=True, label="RESHARD a 0->1"),
        pv.OpModel(2, "RESHARD", 1, reads=(2,), writes=(4,),
                   edge=(0, 1), cross=True, label="RESHARD b 0->1"),
        pv.OpModel(3, "RUN", 0, writes=(5,), label="RUN x"),
        pv.OpModel(4, "RUN", 1, writes=(), label="RUN w"),
        pv.OpModel(5, "RUN", 1, reads=(3, 4), writes=(6,),
                   label="RUN consume"),
    ]
    model = pv.PlanModel(ops=ops, slots=slots, num_meshes=2,
                         streams=[[0, 3], [4, 1, 2, 5]],
                         deps={1: {0}, 2: {0}, 4: {3}},
                         mode="registers")
    result = mc.check_model(model)
    assert result.stats["semantics"]["buffered"] == "pass"
    assert result.stats["semantics"]["rendezvous"] == "deadlock"
    assert "model.rendezvous-deadlock" in _codes(result.findings)
    assert result.ok, "rendezvous-only deadlock must not be an error"
    verdict = pv.verify_model(model, model_check=True)
    assert verdict.ok
    assert "model.rendezvous-deadlock" in _codes(verdict.warnings)


# ---------------------------------------------------------------------
# oracle 3: seeded mutation fuzz on the committed fixture
# ---------------------------------------------------------------------

def _mutate_drop_free(model, hooks, window, rng):
    idx = rng.choice([i for i, op in enumerate(model.ops)
                      if op.kind == "FREE"])
    model.ops[idx] = dataclasses.replace(model.ops[idx], kills=())
    return model, hooks, window, "liveness.leak"


def _mutate_swap_recv_pair(model, hooks, window, rng):
    dst = list(model.streams[1])
    i, j = dst.index(2), dst.index(3)
    dst[i], dst[j] = dst[j], dst[i]
    model.streams[1] = dst
    return model, hooks, window, "model.deadlock"


def _mutate_corrupt_channel_edge(model, hooks, window, rng):
    idx = rng.choice([i for i, op in enumerate(model.ops)
                      if op.kind == "RESHARD"])
    model.ops[idx] = dataclasses.replace(model.ops[idx], edge=(1, 0))
    return model, hooks, window, "model.channel-endpoint"


def _mutate_shrink_window(model, hooks, window, rng):
    return model, hooks, 1, "model.inflight-exceeds-window"


_MUTATIONS = [_mutate_drop_free, _mutate_swap_recv_pair,
              _mutate_corrupt_channel_edge, _mutate_shrink_window]


def test_seeded_mutation_fuzz_every_class_is_caught():
    """Each mutation class, applied with rng-chosen targets, must be
    named by SOME analysis — the deterministic seed keeps failures
    reproducible."""
    rng = random.Random(0)
    seen = set()
    for round_no in range(12):
        mutate = rng.choice(_MUTATIONS)
        model, hooks, window = mc.load_fixture(FIXTURE)
        model, hooks, window, expected = mutate(model, hooks, window,
                                               rng)
        verdict = pv.verify_model(model, hooks=hooks, model_check=True,
                                  overlap_window=window)
        assert expected in _codes(verdict.findings()), (
            f"round {round_no}: mutation {mutate.__name__} not caught;"
            f"\n{verdict.format_table()}")
        seen.add(mutate.__name__)
    assert len(seen) == len(_MUTATIONS), (
        f"seed must exercise every mutation class, got {seen}")


# ---------------------------------------------------------------------
# oracle 4: static retry classification gates call_with_retry
# ---------------------------------------------------------------------

def test_statically_unsafe_site_refuses_real_error_retries():
    from alpa_tpu import fault
    policy = fault.RetryPolicy(max_attempts=3, base_delay=0.0,
                               max_delay=0.0, jitter=0.0)
    fault.install_retry_classification(
        {"stage_launch": {"classification": "unsafe",
                          "reasons": ["unsafe-donation"], "hooks": 1}})
    try:
        # under verify_plans=error the static proof wins: one attempt
        global_config.verify_plans = "error"
        attempts = []

        def boom():
            attempts.append(1)
            raise ValueError("real failure")

        with pytest.raises(ValueError):
            fault.call_with_retry(boom, policy=policy,
                                  site="stage_launch", idempotent=True)
        assert len(attempts) == 1, "retry must be refused"

        # injected faults remain retryable: they fire BEFORE the op
        attempts.clear()

        def injected_then_ok():
            attempts.append(1)
            if len(attempts) == 1:
                raise fault.InjectedFault("stage_launch", "injected")
            return "ok"

        assert fault.call_with_retry(
            injected_then_ok, policy=policy, site="stage_launch",
            idempotent=False) == "ok"
        assert len(attempts) == 2

        # under warn the caller's idempotent declaration still rules
        global_config.verify_plans = "warn"
        attempts.clear()
        with pytest.raises(ValueError):
            fault.call_with_retry(boom, policy=policy,
                                  site="stage_launch", idempotent=True)
        assert len(attempts) == 3, "warn mode must retry as declared"
    finally:
        fault.install_retry_classification(None)
    assert fault.get_retry_classification() == {}


# ---------------------------------------------------------------------
# oracle 5: live end-to-end lowering, knob, metrics, dump, CLI, gate
# ---------------------------------------------------------------------

def test_live_two_mesh_lowering_is_model_checked_end_to_end():
    import time
    t0 = time.perf_counter()
    ex, *_ = _compile_pipeline(num_stages=2)
    wall = time.perf_counter() - t0
    prog = ex._register_programs["registers"]
    verdict = prog.verdict
    assert verdict is not None and verdict.ok, verdict.format_table()
    st = verdict.stats.get("model_check")
    assert st, ("fixture mode is the default: a 2-mesh tier-1 plan "
                "must be model-checked")
    assert st["semantics"]["buffered"] == "pass", verdict.format_table()
    assert st["semantics"]["rendezvous"] == "pass"
    assert not st["partial"]
    assert st["n_channels"] >= 1, "2-mesh plan must have a channel"
    assert st["states"] > 0 and 0.0 < st["reduction_ratio"] <= 1.0
    # the walk itself is milliseconds; the whole compile+step stays
    # well inside the tier-1 wall-clock budget
    assert st["seconds"] < 5.0, st
    assert wall < 120.0, wall
    # real plans classify their reachable sites (donated apply-grad
    # RUNs make stage_launch unsafe)
    from alpa_tpu import fault
    sites = st["retry_sites"]
    assert set(sites) == set(fault.KNOWN_SITES)
    assert sites["stage_launch"]["classification"] == "unsafe"
    # ...and the classification is installed into fault.py
    assert fault.get_retry_classification()[
        "stage_launch"]["classification"] == "unsafe"
    # counters registered and incremented
    from alpa_tpu.telemetry.metrics import get_registry
    text = get_registry().to_prometheus_text()
    assert "alpa_model_check_states_total" in text
    assert 'alpa_plan_model_check_total{result="ok"}' in text
    # the verdict table renders the model-check line
    assert "model check:" in verdict.format_table()


def test_partition_streams_channel_metadata_and_independence():
    """The stream partitioner reports per-edge FIFO channel membership
    in send order, and the op-independence predicate agrees with the
    access-conflict oracle on every real instruction pair."""
    import itertools
    from alpa_tpu.pipeline_parallel.runtime_emitter import (
        PipelineInstType, instruction_accesses, instructions_independent,
        partition_streams)
    ex, *_ = _compile_pipeline(num_stages=2)
    insts = list(ex.instructions)
    st = partition_streams(insts, 2)
    expected = {}
    for i, inst in enumerate(insts):
        if inst.opcode == PipelineInstType.RESHARD and \
                inst.src_mesh != inst.dst_mesh:
            expected.setdefault(
                (inst.src_mesh, inst.dst_mesh), []).append(i)
    assert expected, "2-mesh plan must cross meshes"
    assert st.channels == expected
    # the model passed to the checker carries the same channel map
    prog = ex._register_programs["registers"]
    assert prog.verdict.stats["model_check"]["n_channels"] == \
        len(expected)
    n_indep = n_conflict = 0
    for a, b in itertools.combinations(insts[:20], 2):
        ind = instructions_independent(a, b)
        assert ind == instructions_independent(b, a), "must be symmetric"
        conflict = any(
            ka != "read" or kb != "read"
            for k1, ka in instruction_accesses(a)
            for k2, kb in instruction_accesses(b) if k1 == k2)
        assert ind == (not conflict), (a, b)
        n_indep += int(ind)
        n_conflict += int(not ind)
    assert n_indep > 0 and n_conflict > 0, (n_indep, n_conflict)


def test_model_check_off_knob_skips_the_analysis():
    global_config.verify_plans_model_check = "off"
    ex, *_ = _compile_pipeline(num_stages=2)
    verdict = ex._register_programs["registers"].verdict
    assert verdict is not None and verdict.ok
    assert "model_check" not in verdict.stats


def test_model_check_text_in_debug_dump(tmp_path):
    from alpa_tpu.monitoring import dump_debug_info
    ex, *_ = _compile_pipeline(num_stages=2)
    dump_debug_info(ex, str(tmp_path))
    path = tmp_path / "model_check.txt"
    assert path.exists()
    text = path.read_text()
    assert "model check: buffered=pass" in text, text
    assert "retry sites" in text


def test_verify_tool_modelcheck_cli_on_committed_fixture():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "verify_tool.py"),
         "modelcheck", "--json"],
        capture_output=True, text=True, check=False, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["schema"] == "alpa-model-check/v1"
    assert out["ok"] is True
    assert out["stats"]["semantics"] == {"buffered": "pass",
                                         "rendezvous": "pass"}
    assert {f["code"] for f in out["findings"]} == {
        "retry.unsafe-donation", "retry.fifo-reorder"}
    assert all(f["severity"] == "note" for f in out["findings"])


def test_perf_gate_pins_fixture_state_count():
    """Exploration is deterministic: the committed baseline pins the
    exact state count (ratio 1.0)."""
    from benchmark.perf_gate import gate
    model, hooks, window = mc.load_fixture(FIXTURE)
    result = mc.check_model(model, hooks=hooks, overlap_window=window)
    verdict = gate({"modelcheck.states": float(result.stats["states"])})
    checked = {c["metric"] for c in verdict["checks"]}
    assert "modelcheck.states" in checked
    assert verdict["pass"], verdict


def test_fixture_roundtrip_serialization():
    model, hooks, window = mc.load_fixture(FIXTURE)
    d = mc.model_to_dict(model, hooks=hooks, overlap_window=window)
    assert d["format"] == "alpa-model-check-plan/v1"
    with open(FIXTURE, encoding="utf-8") as f:
        committed = json.load(f)
    # normalize tuples -> lists the way the committed file was written
    assert json.loads(json.dumps(d)) == committed, \
        "fixture round-trip must be lossless"
