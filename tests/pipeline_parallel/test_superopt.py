"""Certified plan superoptimization (ISSUE 17).

Oracle 1 (satellite 1): the DAG re-simulator's per-mesh simulated
peak-live-bytes pins bit-for-bit against the static liveness analysis'
``alpa_plan_peak_bytes`` on the committed 2-mesh fixture when both walk
the same serial order.  Oracle 2 (satellite 2): ``reshard_group_extent``
is the one grouping-legality oracle — its documented semantics (FREE
hopping, blocked slots, groupable-only multi-member, the
``superopt_max_group`` fission cap) hold on synthetic records, and the
registers-mode coalescer consumes it (fingerprint determinism over real
programs is covered by the compile-cache tests).  Oracle 3 (satellite
3): every adversarial fuzz class — reorder across a RAW edge, sink a
FREE past a live consumer, fuse a quantized edge into a batched group,
drop a microbatch accumulation RUN — is rejected by the verdict gate
with its named finding.  Oracle 4: on a real 2-mesh pipeline,
``superopt_mode=auto`` recovers a hazard-legal deoptimized plan with a
strict simulated critical-path AND peak-bytes improvement, training-step
outputs bitwise identical across baseline / deoptimized / rewritten
plans, and a warm restart replays the accepted rewrite from the compile
cache with zero search and an identical plan fingerprint.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import alpa_tpu
from alpa_tpu.analysis import plan_verifier as pv
from alpa_tpu.analysis import superopt as so
from alpa_tpu.analysis.critical_path import MemSpec, simulate_dag, whatif
from alpa_tpu.analysis.model_check import model_from_dict
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.runtime_emitter import (
    OpHook, PipelineInstType, PipelineInstruction, instruction_accesses)

from tests.pipeline_parallel.test_apply_grad_placement import (
    tied_lm_state_and_batch, tied_lm_train_step)
from tests.pipeline_parallel.test_plan_verifier import _compile_pipeline

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "benchmark", "results", "model_check_fixture_plan.json")


@pytest.fixture(autouse=True)
def _restore_globals():
    prev = {k: getattr(global_config, k) for k in (
        "pipeline_dispatch_mode", "verify_plans", "compile_cache_dir",
        "superopt_mode", "superopt_beam_width", "superopt_step_budget",
        "superopt_verify_budget", "superopt_max_group")}
    yield
    for k, v in prev.items():
        setattr(global_config, k, v)
    from alpa_tpu.compile_cache import reset_compile_cache
    reset_compile_cache()


# ---------------------------------------------------------------------
# satellite 1: simulated peaks pin against static liveness
# ---------------------------------------------------------------------

def _fixture_model():
    with open(FIXTURE, encoding="utf-8") as f:
        model, _hooks, _window = model_from_dict(json.load(f))
    return model


def _mem_from_model(model) -> MemSpec:
    writes = [list(op.writes) for op in model.ops]
    kills = [list(op.kills) for op in model.ops]
    slots = (model.slots.values() if isinstance(model.slots, dict)
             else model.slots)
    nbytes = {s.slot: float(s.nbytes) for s in slots}
    mesh_of = {s.slot: s.mesh for s in slots}
    written, preplaced = set(), set()
    for op in model.ops:
        for s in list(op.reads) + list(op.kills):
            if s not in written:
                preplaced.add(s)
        written.update(op.writes)
    return MemSpec(writes=writes, kills=kills, nbytes=nbytes,
                   mesh_of=mesh_of, num_meshes=model.num_meshes,
                   preplaced=frozenset(preplaced))


def test_simulated_peaks_match_static_liveness():
    """simulate_dag over the committed fixture, serialized in program
    order, reproduces check_liveness' per-mesh static peak bytes
    bit-for-bit — the two peak-live-bytes computations agree."""
    model = _fixture_model()
    n = len(model.ops)
    mem = _mem_from_model(model)
    durs = [1.0] * n
    preds = [set() if i == 0 else {i - 1} for i in range(n)]
    makespan, finish, peaks = simulate_dag(durs, preds, mem)
    assert makespan == float(n)
    assert len(finish) == n

    findings, stats = pv.check_liveness(model)
    assert not [f for f in findings if f.severity == "error"]
    static = stats["peak_bytes"]
    static_list = [static[str(m)] for m in range(model.num_meshes)] \
        if isinstance(static, dict) else list(static)
    assert list(peaks) == static_list, \
        f"simulated {peaks} != static {static_list}"
    assert static_list == [128.0, 192.0]    # pin the committed fixture


def test_whatif_returns_peaks_with_mem():
    mem = MemSpec(writes=[[0], [1], []], kills=[[], [0], [1]],
                  nbytes={0: 10.0, 1: 4.0}, mesh_of={0: 0, 1: 0},
                  num_meshes=1, preplaced=frozenset())
    durs = [2.0, 3.0, 1.0]
    preds = [set(), {0}, {1}]
    makespan, finish, peaks = simulate_dag(durs, preds, mem)
    assert makespan == 6.0
    # op 1 kills slot 0 before writing slot 1 (the liveness analysis'
    # within-op order), so the two never overlap
    assert peaks == [10.0]
    out = whatif(durs, preds, {1}, mem=mem)
    assert isinstance(out, tuple)
    ms2, peaks2 = out
    assert ms2 == 3.0
    assert peaks2 == [10.0]
    # without mem, whatif keeps its scalar contract
    assert whatif(durs, preds, {1}) == 3.0


# ---------------------------------------------------------------------
# layouts: serializable rewrite decisions
# ---------------------------------------------------------------------

def _toy_instructions():
    run = PipelineInstruction(PipelineInstType.RUN, info="r0")
    free = PipelineInstruction(
        PipelineInstType.FREE,
        free_keys=[("a", 0, 0), ("b", 0, 0)], info="f")
    run2 = PipelineInstruction(PipelineInstType.RUN, info="r1")
    return [run, free, run2]


def test_layout_check_apply_and_free_split():
    insts = _toy_instructions()
    ident = so.identity_layout(3)
    so.check_layout(insts, ident)
    assert so.apply_layout(insts, ident) == insts

    # free split: each key position emitted once, as its own FREE
    split = [0, ["free", 1, [0]], 2, ["free", 1, [1]]]
    so.check_layout(insts, split)
    out = so.apply_layout(insts, split)
    assert [o.opcode for o in out] == [
        PipelineInstType.RUN, PipelineInstType.FREE,
        PipelineInstType.RUN, PipelineInstType.FREE]
    assert out[1].free_keys == [("a", 0, 0)]
    assert out[3].free_keys == [("b", 0, 0)]

    # clone duplicates a RUN without consuming the original
    clone = [0, 1, 2, ["clone", 0]]
    so.check_layout(insts, clone)
    assert so.apply_layout(insts, clone)[3].info == "r0"

    with pytest.raises(ValueError, match="drops"):
        so.check_layout(insts, [0, 1])              # RUN 2 missing
    with pytest.raises(ValueError, match="twice"):
        so.check_layout(insts, [0, 0, 1, 2])        # RUN emitted twice
    with pytest.raises(ValueError, match="twice"):
        so.check_layout(insts, [0, 1, ["free", 1, [0]], 2])
    with pytest.raises(ValueError, match="non-RUN"):
        so.check_layout(insts, [0, 1, 2, ["clone", 1]])
    with pytest.raises(ValueError, match="out of range"):
        so.check_layout(insts, [0, ["free", 1, [5]], 2])


# ---------------------------------------------------------------------
# satellite 2: the shared grouping-legality oracle
# ---------------------------------------------------------------------

def _rec(kind, edge=None, ss=0, ds=1, groupable=True, slots=()):
    if kind == "RESHARD":
        return {"kind": kind, "edge": edge, "ss": ss, "ds": ds,
                "groupable": groupable}
    if kind == "FREE":
        return {"kind": kind, "slots": tuple(slots)}
    return {"kind": kind}


def test_reshard_group_extent_semantics():
    e, f = (0, 1), (1, 0)
    # adjacent same-edge groupables group; a FREE between them is
    # hopped and counted (it enabled the later member)
    recs = [_rec("RESHARD", e, 0, 1), _rec("FREE", slots=(9,)),
            _rec("RESHARD", e, 2, 3), _rec("RESHARD", f, 4, 5)]
    members, hopped, hops, nxt = so.reshard_group_extent(recs, 0)
    assert members == [0, 2] and hopped == [1] and hops == 1
    assert nxt == 3                 # different edge ends the group

    # a FREE of a later member's own slot blocks it from joining
    recs = [_rec("RESHARD", e, 0, 1), _rec("FREE", slots=(2,)),
            _rec("RESHARD", e, 2, 3)]
    members, hopped, hops, nxt = so.reshard_group_extent(recs, 0)
    assert members == [0] and hops == 0

    # non-groupable (quantized/collective) transfers never join a
    # multi-member group — in either position
    recs = [_rec("RESHARD", e, 0, 1),
            _rec("RESHARD", e, 2, 3, groupable=False)]
    assert so.reshard_group_extent(recs, 0)[0] == [0]
    recs = [_rec("RESHARD", e, 0, 1, groupable=False),
            _rec("RESHARD", e, 2, 3)]
    assert so.reshard_group_extent(recs, 0)[0] == [0]

    # a RUN ends the group; trailing FREEs are not charged as hops
    recs = [_rec("RESHARD", e, 0, 1), _rec("RESHARD", e, 2, 3),
            _rec("FREE", slots=(9,)), _rec("RUN"),
            _rec("RESHARD", e, 4, 5)]
    members, hopped, hops, nxt = so.reshard_group_extent(recs, 0)
    assert members == [0, 1] and hopped == [2] and hops == 0
    assert nxt == 3


def test_reshard_group_extent_fission_cap():
    e = (0, 1)
    recs = [_rec("RESHARD", e, 2 * i, 2 * i + 1) for i in range(3)]
    # uncapped: one 3-member group
    assert so.reshard_group_extent(recs, 0)[0] == [0, 1, 2]
    # superopt_max_group=2: the group splits and the caller resumes at
    # the first excluded member
    members, _, _, nxt = so.reshard_group_extent(recs, 0, max_members=2)
    assert members == [0, 1] and nxt == 2
    assert so.reshard_group_extent(recs, 2, max_members=2)[0] == [2]


def test_coalescer_honors_fission_knob():
    """The registers-mode coalescer consumes the shared oracle: the
    superopt_max_group knob caps real batched groups at lowering time
    without changing instruction semantics."""
    ex, *_ = _compile_pipeline(num_stages=2)
    base = ex._register_programs["registers"]
    lower = ex._make_lowerer("registers")
    global_config.superopt_max_group = 1
    capped = lower(ex.instructions)
    assert max((len(h.members) for h in capped.hooks
                if getattr(h, "members", None)), default=1) <= 1
    # group membership is a replay batching decision, not a semantic
    # one: the capped program touches the same slots
    assert capped.num_slots == base.num_slots
    assert capped.verdict is not None and not capped.verdict.errors


# ---------------------------------------------------------------------
# deoptimize / score / search (pure, over a real compiled plan)
# ---------------------------------------------------------------------

def _compile_tied_lm():
    """The two-stage Adam step of a tied-embedding LM (registers mode).
    The recovery tests run on it and not on ``_compile_pipeline``'s MLP:
    its activations (4 x 8 x 64 logits a micro-batch) outweigh its
    parameters, so FREEs deferred to the end do raise the simulated peak,
    and the tied table's gradient crosses beside the activation's, so the
    baseline already holds a grouped transfer and a regrouping is no new
    finding.  (Until ISSUE 25 the MLP had both by accident: its momentum
    products crossed 0->1 in a row before ``apply_grad_1``.)"""
    alpa_tpu.init("local")
    global_config.pipeline_dispatch_mode = "registers"
    step = tied_lm_train_step()
    step(*tied_lm_state_and_batch())
    return step.get_last_executable(), step


def test_deoptimize_is_legal_and_search_recovers():
    ex, _ = _compile_tied_lm()
    insts = list(ex.instructions)
    cm = so._CostModel()
    nm = ex.num_meshes
    base = so.score_instructions(insts, nm, cm)

    pess = so.deoptimize_instructions(insts, cm)
    assert [id(x) for x in pess] != [id(x) for x in insts]
    worse = so.score_instructions(pess, nm, cm)
    assert worse.makespan_us > base.makespan_us + 1e-9
    assert worse.total_peak > base.total_peak + 1e-9

    # the pessimized order is hazard-legal: re-lowering it introduces
    # no new finding vs the baseline verdict
    lower = ex._make_lowerer("registers")
    baseline_prog = ex._register_programs["registers"]
    pess_prog = lower(pess)
    assert so.verdict_new_findings(
        baseline_prog.verdict, pess_prog.verdict) == []

    # search from the pessimized list strictly recovers BOTH objectives
    _, b2, best, log, cands = so.superopt_search(pess, nm, cm)
    assert cands, "no admissible strict improvement found"
    assert best.makespan_us < b2.makespan_us - 1e-9
    assert best.total_peak < b2.total_peak - 1e-9
    assert {e["family"] for e in log} >= {"reschedule", "free_motion"}


# ---------------------------------------------------------------------
# satellite 3: adversarial fuzz — every unsound rewrite class is
# rejected by the verdict gate with its named finding
# ---------------------------------------------------------------------

def _gate_names(ex, mutate):
    """Lower a mutated instruction list and return the gate's new
    (analysis, code) findings vs the compiled baseline."""
    baseline = ex._register_programs["registers"]
    insts = list(ex.instructions)
    mutated = mutate(insts)
    lower = ex._make_lowerer("registers")
    prog = lower(mutated)
    return so.verdict_new_findings(baseline.verdict, prog.verdict)


def test_fuzz_reorder_across_raw_edge_rejected():
    ex, *_ = _compile_pipeline(num_stages=2)

    def mutate(insts):
        j = next(i for i, x in enumerate(insts)
                 if x.opcode == PipelineInstType.RESHARD and
                 x.src_mesh != x.dst_mesh)
        return [insts[j]] + insts[:j] + insts[j + 1:]

    new = _gate_names(ex, mutate)
    assert ("deadlock", "deadlock.recv-before-send") in new, new


def test_fuzz_free_before_consumer_rejected():
    ex, *_ = _compile_pipeline(num_stages=2)

    def mutate(insts):
        # sink a FREE in front of the earliest reader of its keys
        for fi, x in enumerate(insts):
            if x.opcode != PipelineInstType.FREE:
                continue
            keys = {tuple(k) for k in x.free_keys}
            readers = [i for i in range(fi) if any(
                kind == "read" and tuple(k) in keys
                for k, kind in instruction_accesses(insts[i]))]
            writers = [i for i in range(fi) if any(
                kind == "write" and tuple(k) in keys
                for k, kind in instruction_accesses(insts[i]))]
            if readers and writers and min(writers) < min(readers):
                e = min(readers)
                out = insts[:fi] + insts[fi + 1:]
                out.insert(e, x)
                return out
        pytest.skip("no FREE with an earlier reader found")

    new = _gate_names(ex, mutate)
    assert ("liveness", "liveness.use-after-free") in new, new


def test_fuzz_drop_microbatch_accumulation_rejected():
    ex, *_ = _compile_pipeline(num_stages=2)

    def mutate(insts):
        # drop a grad-accumulation RUN (kills and rewrites the same key)
        for i, x in enumerate(insts):
            if x.opcode != PipelineInstType.RUN:
                continue
            acc = instruction_accesses(x)
            kills = {tuple(k) for k, kind in acc if kind == "kill"}
            writes = {tuple(k) for k, kind in acc if kind == "write"}
            if kills & writes:
                return insts[:i] + insts[i + 1:]
        pytest.skip("no accumulation RUN found")

    new = _gate_names(ex, mutate)
    assert any(a == "liveness" for a, _ in new), new
    assert ("liveness", "liveness.use-undefined") in new or \
        ("liveness", "liveness.free-undefined") in new, new


def test_fuzz_quantized_edge_fused_into_group_rejected():
    """Class (c) at the PlanModel level: batching a quantized transfer
    into a direct_p2p group is rejected by structure analysis."""
    model = _fixture_model()
    # the fixture's two same-edge RESHARDs, groupable direct_p2p
    ops = list(model.ops)
    ri = [i for i, o in enumerate(ops) if o.kind == "RESHARD"]
    assert len(ri) == 2
    for i in ri:
        ops[i] = dataclasses.replace(ops[i], strategy="direct_p2p",
                                     groupable=True)

    def hook(members):
        mem = [ops[m] for m in members]
        return OpHook(
            kind="launch", name="group", node=members[0],
            mesh=mem[0].mesh,
            reads=tuple(s for o in mem for s in o.reads),
            writes=tuple(s for o in mem for s in o.writes),
            kills=tuple(s for o in mem for s in o.kills),
            members=tuple(members))

    base_model = dataclasses.replace(model, ops=ops)
    base = pv.verify_model(base_model, hooks=[hook(ri)])
    assert "structure.group-nongroupable" not in \
        {f.code for f in base.findings()}

    # fuzz: fuse a quantized edge into the batched group
    bad_ops = list(ops)
    bad_ops[ri[1]] = dataclasses.replace(
        bad_ops[ri[1]], strategy="quantized", groupable=False)
    cand = pv.verify_model(dataclasses.replace(model, ops=bad_ops),
                           hooks=[hook(ri)])
    new = so.verdict_new_findings(base, cand)
    assert ("structure", "structure.group-nongroupable") in new, new


# ---------------------------------------------------------------------
# oracle 4: end-to-end auto recovery + bitwise outputs + warm replay
# ---------------------------------------------------------------------

def _param_leaves(state):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        state.params)]


def _reset_lowering(ex):
    """Forget every lowered program + slot table so the next launch
    re-lowers ex.instructions from scratch (the replan hot-swap path,
    plus the slot tables — the instruction ORDER changed, so slot
    numbering changes too)."""
    ex._register_programs.clear()
    ex._register_program = None
    ex._reg_input_loads = None
    ex._reg_const_loads = None
    ex._reg_acc_slots = None
    ex._reg_output_specs = None
    ex._superopt_outcome = None
    ex._superopt_instructions = None


def test_auto_recovers_deoptimized_plan_bitwise(tmp_path):
    from alpa_tpu.compile_cache import reset_compile_cache
    from alpa_tpu.telemetry.metrics import get_registry
    ex, step = _compile_tied_lm()
    global_config.compile_cache_dir = str(tmp_path)
    reset_compile_cache()

    s0, b0 = tied_lm_state_and_batch()
    ns0, _ = step(s0, b0)
    want = _param_leaves(ns0)
    assert any(bool(np.any(x)) for x in want)

    # adversarial baseline: hazard-legal deoptimized stream, hot-swapped
    ex.instructions = so.deoptimize_instructions(list(ex.instructions))
    _reset_lowering(ex)
    ex._ensure_lowered("registers")
    s1, b1 = tied_lm_state_and_batch()
    ns1, _ = step(s1, b1)
    assert all((a == b).all()
               for a, b in zip(want, _param_leaves(ns1))), \
        "deoptimized plan must stay semantically identical"

    # auto: search + verdict gate recover both objectives
    global_config.superopt_mode = "auto"
    _reset_lowering(ex)
    ex._ensure_lowered("registers")
    out = ex._superopt_outcome
    assert out is not None and out.accepted and out.searched
    assert not out.cache_hit
    assert out.critical_path_delta_us < 0
    assert out.peak_bytes_delta < 0
    assert out.fingerprint != out.baseline_fingerprint
    s2, b2 = tied_lm_state_and_batch()
    ns2, _ = step(s2, b2)
    assert all((a == b).all()
               for a, b in zip(want, _param_leaves(ns2))), \
        "rewritten plan must be bitwise identical to the baseline"

    # the decision is observable: metrics, superopt.txt, the cache
    snap = get_registry().snapshot()
    assert snap.get("alpa_superopt_rewrites_accepted_total", 0) >= 1
    assert snap.get("alpa_superopt_critical_path_delta_us", 0) < 0
    assert snap.get("alpa_superopt_peak_bytes_delta", 0) < 0
    text = ex.get_superopt_text()
    assert "accepted" in text and out.fingerprint[:8] in text
    decisions = so.load_cached_decisions()
    assert decisions and \
        decisions[0]["decision"]["fingerprint"] == out.fingerprint

    # suggest: same decision replayed from cache, but NOT applied
    global_config.superopt_mode = "suggest"
    _reset_lowering(ex)
    prog = ex._ensure_lowered("registers")
    out2 = ex._superopt_outcome
    assert out2.cache_hit and not out2.searched and out2.accepted
    assert ex._superopt_instructions is None
    assert prog.fingerprint() == out2.baseline_fingerprint

    # warm restart: fresh memory tier, disk cache replays with zero
    # search and the identical plan fingerprint
    reset_compile_cache()
    global_config.superopt_mode = "auto"
    _reset_lowering(ex)
    ex._ensure_lowered("registers")
    out3 = ex._superopt_outcome
    assert out3.cache_hit and not out3.searched and out3.accepted
    assert out3.fingerprint == out.fingerprint
    s3, b3 = tied_lm_state_and_batch()
    ns3, _ = step(s3, b3)
    assert all((a == b).all()
               for a, b in zip(want, _param_leaves(ns3)))

    # superopt.txt lands in the debug dump (dumping also ingests the
    # trace into the calibration store, so it comes after the
    # cache-replay legs — measured costs re-key the decision)
    from alpa_tpu import monitoring
    monitoring.dump_debug_info(ex, str(tmp_path / "dump"))
    assert (tmp_path / "dump" / "superopt.txt").exists()
