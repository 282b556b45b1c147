"""Overlap-aware pipeshard dispatch (ISSUE 4 tentpole).

Oracle 1: numerics — overlap mode must be bit-identical to the
sequential interpreter AND the synchronous register replay over donated
train steps (same RUN executables, same transfers; only launch timing
differs).  Oracle 2: the dataflow-graph replay itself — a seeded
randomized-topology fuzz drives arbitrary RUN/RESHARD/FREE programs
through :func:`schedule_overlap` and asserts the replay never issues an
op before its producers retired, never frees/overwrites a slot a live
transfer still uses, and never exceeds the in-flight window.
"""
import random

import numpy as np
import pytest

import alpa_tpu
import jax
from alpa_tpu import PipeshardParallel
from alpa_tpu.global_env import global_config
from alpa_tpu.pipeline_parallel.layer_construction import AutoLayerOption
from alpa_tpu.pipeline_parallel.runtime_emitter import (
    DataflowNode, InstructionDataflowGraph, schedule_overlap)
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)

@pytest.fixture(autouse=True)
def _restore_dispatch_mode():
    prev = global_config.pipeline_dispatch_mode
    yield
    global_config.pipeline_dispatch_mode = prev


def _run_steps(mode, n_steps=3):
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


# ---------------------------------------------------------------------
# end-to-end numerics
# ---------------------------------------------------------------------

def test_overlap_matches_interpreter_and_registers_bitwise():
    alpa_tpu.init("local")
    state_s, val_s, ex_s = _run_steps("sequential")
    state_r, val_r, ex_r = _run_steps("registers")
    state_o, val_o, ex_o = _run_steps("overlap")
    assert ex_s.last_dispatch_stats["mode"] == "sequential"
    assert ex_r.last_dispatch_stats["mode"] == "registers"
    assert ex_o.last_dispatch_stats["mode"] == "overlap"
    leaves_s = jax.tree_util.tree_leaves(state_s.params)
    leaves_r = jax.tree_util.tree_leaves(state_r.params)
    leaves_o = jax.tree_util.tree_leaves(state_o.params)
    assert len(leaves_s) == len(leaves_o) > 0
    for a, b in zip(leaves_s, leaves_o):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(leaves_r, leaves_o):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(val_s), np.asarray(val_o))
    np.testing.assert_array_equal(np.asarray(val_r), np.asarray(val_o))


def test_overlap_stats_shape():
    alpa_tpu.init("local")
    _, _, ex = _run_steps("overlap", n_steps=2)
    st = ex.last_dispatch_stats
    assert st["mode"] == "overlap"
    assert st["n_cross_mesh"] > 0
    assert 0 < st["n_launches"] <= st["n_cross_mesh"]
    assert 0 <= st["n_hoisted"] <= st["n_cross_mesh"]
    assert st["overlap_window"] >= 1
    assert 0.0 <= st["overlap_fraction"] <= 1.0
    assert st["transfer_busy_s"] >= 0.0
    assert st["wait_blocked_s"] >= 0.0
    # the two lowered modes share slot numbering (phase 1 is mode-free)
    ovl = ex._register_programs["overlap"]
    reg = ex._ensure_lowered("registers")
    assert ovl.slot_of == reg.slot_of
    assert ovl.n_instructions == reg.n_instructions
    assert ovl.graph is not None and reg.graph is not None
    assert ovl.graph.preds == reg.graph.preds


def test_overlap_stays_on_graph_executor_when_tracing():
    """Trace collection no longer forces the interpreter: spans are
    compiled into the replay plan as per-node hooks (ISSUE 6), so
    overlap keeps running on the graph executor with the trace hook
    reported in its dispatch stats."""
    alpa_tpu.init("local")
    prev = global_config.collect_trace
    global_config.collect_trace = True
    try:
        _, _, ex = _run_steps("overlap", n_steps=1)
        st = ex.last_dispatch_stats
        assert st["mode"] == "overlap"
        assert "trace" in st["hooks"]
    finally:
        global_config.collect_trace = prev


def test_overlap_debug_dump_counters():
    from alpa_tpu.monitoring import format_overlap_report, get_overlap_stats
    alpa_tpu.init("local")
    _, _, _ = _run_steps("overlap", n_steps=1)
    stats = get_overlap_stats()
    assert stats["runtime"]["steps"] >= 1
    assert stats["runtime"]["n_launches"] >= 1
    assert stats["planner"]["plans"] >= 0
    report = format_overlap_report()
    assert "overlap dispatch" in report
    assert "resharding planner" in report


# ---------------------------------------------------------------------
# randomized-topology fuzz of the graph replay (seeded)
# ---------------------------------------------------------------------

def _random_program(rng, n_ops):
    """A random SSA-style RUN/RESHARD/FREE program over integer slots."""
    nodes = []
    live = []
    next_slot = [0]

    def new_slot():
        s = next_slot[0]
        next_slot[0] += 1
        return s

    for idx in range(n_ops):
        c = rng.random()
        if not live or c < 0.45:
            k = min(len(live), rng.randrange(0, 3))
            reads = tuple(rng.sample(live, k)) if k else ()
            kills = ()
            if reads and rng.random() < 0.3:
                kills = (reads[rng.randrange(len(reads))],)
                for s in kills:
                    live.remove(s)
            writes = tuple(new_slot() for _ in range(rng.randrange(1, 3)))
            live.extend(writes)
            nodes.append(DataflowNode(idx, "RUN", reads=reads,
                                      writes=writes, kills=kills))
        elif c < 0.85:
            src = rng.choice(live)
            dst = new_slot()
            live.append(dst)
            edge = (rng.randrange(4), rng.randrange(4))
            nodes.append(DataflowNode(idx, "RESHARD", reads=(src,),
                                      writes=(dst,), edge=edge,
                                      cross_mesh=edge[0] != edge[1]))
        else:
            k = rng.randrange(1, min(3, len(live)) + 1)
            slots = tuple(rng.sample(live, k))
            for s in slots:
                live.remove(s)
            nodes.append(DataflowNode(idx, "FREE", kills=slots))
    return nodes


def _replay_and_check(nodes, graph, plan, window):
    """Simulate a schedule_overlap plan, asserting every replay
    invariant the real executor relies on."""
    issued, retired = set(), set()
    inflight = []
    for kind, i in plan:
        node = nodes[i]
        if kind in ("exec", "launch"):
            assert i not in issued, f"double issue of node {i}"
            for p in graph.preds[i]:
                assert p in retired, \
                    f"{kind} {i} before pred {p} retired (seed case)"
            issued.add(i)
        if kind == "exec":
            # no live transfer may still be using a slot this op
            # overwrites, frees, or (for writes) reads from
            touched = set(node.writes) | set(node.kills)
            for t in inflight:
                tn = nodes[t]
                assert not (set(tn.reads) & touched), \
                    f"exec {i} kills/overwrites slot a live transfer " \
                    f"{t} reads"
                assert not (set(tn.writes) &
                            (touched | set(node.reads))), \
                    f"exec {i} touches slot a live transfer {t} writes"
            retired.add(i)
        elif kind == "launch":
            assert node.cross_mesh, "only cross-mesh RESHARDs launch"
            inflight.append(i)
            assert len(inflight) <= window, "in-flight window exceeded"
        else:  # wait
            assert i in inflight, f"wait for non-inflight {i}"
            inflight.remove(i)
            retired.add(i)
    assert not inflight, "transfers left unwaited at end of plan"
    assert issued == set(range(len(nodes))), "nodes never issued"
    # non-transfer ops keep their flat relative order
    execs = [i for k, i in plan if k == "exec"]
    assert execs == sorted(execs)


def test_fuzz_graph_replay_invariants():
    for seed in range(25):
        rng = random.Random(1234 + seed)
        nodes = _random_program(rng, n_ops=40)
        graph = InstructionDataflowGraph.build(nodes)
        for window in (1, 2, 3, 5):
            plan, n_hoisted = schedule_overlap(graph, window)
            _replay_and_check(nodes, graph, plan, window)
            assert 0 <= n_hoisted <= graph.n_cross_mesh


def test_graph_edges_cover_donation_hazard():
    """A donating RUN must depend on every transfer reading the donated
    slot — the cross-thread hazard overlap mode introduces."""
    nodes = [
        DataflowNode(0, "RUN", writes=(0,)),
        DataflowNode(1, "RESHARD", reads=(0,), writes=(1,), edge=(0, 1),
                     cross_mesh=True),
        DataflowNode(2, "RUN", reads=(0,), writes=(2,), kills=(0,)),
        DataflowNode(3, "FREE", kills=(1,)),
    ]
    g = InstructionDataflowGraph.build(nodes)
    assert 1 in g.preds[2]          # donation waits for the transfer
    assert 1 in g.preds[3]          # FREE waits for the transfer's write
    plan, _ = schedule_overlap(g, 4)
    pos = {(k, i): p for p, (k, i) in enumerate(plan)}
    assert pos[("wait", 1)] < pos[("exec", 2)]
