"""A pipeshard step's account on the device's clock (ISSUE 49):
``perf.joined_from_capture`` gives each RUN op the interval its program ran
on its mesh's chips, ``perf._device_bubbles`` puts every idle instant of a
mesh down to one cause, and ``Capture.pipeline_time()`` sums both over the
traced steps.  The device events are made by hand: the ``chips`` dictionary
``device_time.read_profile`` returns is plain tuples, in nanoseconds; the
spans are the recorder's dictionaries, in microseconds, ``SHIFT`` behind
the profiler's clock.
"""
import types

import pytest

from alpa_tpu.pipeline_parallel.runtime_emitter import (
    DataflowNode, InstructionDataflowGraph, OpHook)
from alpa_tpu.telemetry import device_time as dt
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import perf
from alpa_tpu.telemetry import trace as ttrace

SHIFT = 1000.0      # microseconds to add to a span to reach the device clock
CHIPS = {0: [0, 1], 1: [2, 3]}


def _program(ops):
    """A lowered program from ``(name, mesh, reads, writes)`` a flat op:
    its hooks, its ``op_meta`` and the dataflow graph of its slots."""
    nodes, hooks, meta = [], [], []
    for i, (name, mesh, reads, writes) in enumerate(ops):
        kind = name.split()[0]
        edge = None
        if kind == "RESHARD":
            src, dst = name.split()[1].split("->")
            edge = (int(src), int(dst))
        nodes.append(DataflowNode(
            idx=i, kind=kind, reads=tuple(reads), writes=tuple(writes),
            edge=edge, cross_mesh=edge is not None and edge[0] != edge[1]))
        hooks.append(OpHook(kind="exec", name=name, node=i, mesh=mesh,
                            reads=tuple(reads), writes=tuple(writes),
                            members=(i,)))
        meta.append((name, "instruction", f"mesh {mesh}"))
    graph = InstructionDataflowGraph.build(nodes)
    graph.check()
    return types.SimpleNamespace(hooks=hooks, op_meta=meta, graph=graph)


def _span(name, start, end, category="runtime", track="driver", args=None):
    return {"name": name, "category": category, "ts_us": start,
            "dur_us": end - start, "tid": 0, "track": track, "args": args}


def _step_spans(program, start, placed, host, end):
    """The driver's spans of one step that begins at ``start``: its inputs
    placed by ``placed``, op *i* entered and left at ``host[i]``, the
    driver back at ``end``."""
    spans = [_span(perf.STEP_SPAN, start, end),
             _span(perf.PLACE_INPUTS_SPAN, start, placed)]
    for (name, category, track), (t0, t1) in zip(program.op_meta, host):
        spans.append(_span(name, t0, t1, category, track))
    return spans


def _capture(spans, runs, collectives=None, chips=CHIPS):
    """A capture of ``spans`` whose device events are ``runs``: ``{chip:
    [(program, start_us, end_us)]}`` on the device's clock, each run one
    event long but for the ``collectives`` (``{chip: [(instruction,
    start_us, end_us)]}``) inside them."""
    events = {}
    for chip, chip_runs in runs.items():
        ops = [("fusion.1", s * 1e3, e * 1e3) for _, s, e in chip_runs]
        ops += [(n, s * 1e3, e * 1e3)
                for n, s, e in (collectives or {}).get(chip, ())]
        events[chip] = (ops, [(p, s * 1e3, e * 1e3) for p, s, e in chip_runs])
    moving = {n: (dt.COLLECTIVE, None)
              for evs in (collectives or {}).values() for n, _, _ in evs}
    table = dt.reduce_events(events, None, lambda name: [
        {"fusion.1": ("mlp", None), **moving}])
    return ttrace.Capture("/nowhere", spans, 0.0, _offset_us=SHIFT,
                          _device_time=table)


def _both_chips(runs_by_mesh):
    """``{chip: runs}`` with each mesh's runs on both of its chips."""
    return {chip: list(runs) for mesh, runs in runs_by_mesh.items()
            for chip in CHIPS[mesh]}


def _account(capture, program, chips=CHIPS):
    """{mesh track: MeshBubbles} of every traced step, oldest first."""
    steps = perf.joined_from_capture(capture, program, chips)
    accounts = []
    for joined in steps:
        assert joined.source == "device", joined.notes
        causal, _ = perf._op_dependencies(program, len(joined.ops))
        accounts.append(perf._device_bubbles(joined, causal))
    return steps, accounts


# ---- (a) the identity, on a 1F1B step of two meshes and four micro-batches --

def _one_f_one_b(micro_batches=4):
    """(ops, the RUN ops' device seconds): stage 0 on mesh 0, stage 1 on
    mesh 1, the activation crossing 0->1 and its gradient 1->0, in the
    order a 1F1B driver replays them."""
    ops, slot = [], iter(range(10 ** 6))
    act0, act1, hid, grad1, grad0 = ({} for _ in range(5))

    def fwd(mb):
        act0[mb], act1[mb], hid[mb] = next(slot), next(slot), next(slot)
        ops.append(("RUN stage_0_fwd", 0, [], [act0[mb]]))
        ops.append(("RESHARD 0->1", 1, [act0[mb]], [act1[mb]]))
        ops.append(("RUN stage_1_fwd", 1, [act1[mb]], [hid[mb]]))

    def bwd(mb):
        grad1[mb], grad0[mb] = next(slot), next(slot)
        ops.append(("RUN stage_1_bwd", 1, [hid[mb]], [grad1[mb]]))
        ops.append(("RESHARD 1->0", 0, [grad1[mb]], [grad0[mb]]))
        ops.append(("RUN stage_0_bwd", 0, [grad0[mb], act0[mb]],
                    [next(slot)]))

    fwd(0)
    for mb in range(1, micro_batches):
        fwd(mb)
        bwd(mb - 1)
    bwd(micro_batches - 1)
    ops.append(("RUN apply_grad_0", 0, [], [next(slot)]))
    ops.append(("RUN apply_grad_1", 1, [], [next(slot)]))
    seconds = {"RUN stage_0_fwd": 30.0, "RUN stage_1_fwd": 40.0,
               "RUN stage_0_bwd": 70.0, "RUN stage_1_bwd": 65.0,
               "RUN apply_grad_0": 20.0, "RUN apply_grad_1": 25.0}
    return ops, seconds


def _simulate(program, seconds, start, placed_after=15.0, per_op=4.0,
              edge=3.0, launch=2.0):
    """One step as a chip would run it: the driver enters op after op,
    ``per_op`` microseconds each, from ``placed_after`` after ``start``; a
    RUN begins on its mesh once the mesh's previous run has ended, what it
    depends on has ended and crossed (``edge``), and its enqueue has
    returned (``launch``).  Returns (the driver's spans, {mesh: runs} on the
    device's clock, the driver's return)."""
    causal, _ = perf._op_dependencies(program, len(program.hooks))
    now = start + placed_after
    host, ends, free_at, runs = [], {}, {}, {0: [], 1: []}
    for i, hook in enumerate(program.hooks):
        host.append((now, now + per_op))
        now += per_op
        ready = max([ends[j] + edge for j in causal[i]] + [0.0])
        if hook.name.startswith("RUN "):
            begin = max(free_at.get(hook.mesh, 0.0), ready,
                        host[i][1] + SHIFT + launch)
            ends[i] = begin + seconds[hook.name]
            free_at[hook.mesh] = ends[i]
            runs[hook.mesh].append(("jit_" + hook.name[4:], begin, ends[i]))
        else:
            ends[i] = max(ready, host[i][1] + SHIFT)
    return (_step_spans(program, start, start + placed_after, host, now),
            runs, now)


def test_busy_and_the_four_causes_add_up_to_the_envelope():
    ops, seconds = _one_f_one_b()
    program = _program(ops)
    first, runs_1, back = _simulate(program, seconds, 100.0)
    # the second step begins after the first one's last run, as the
    # benchmark's does (it waits for the step); 37 us of the host between
    begin_2 = max(r[2] for rs in runs_1.values() for r in rs) - SHIFT + 37.0
    second, runs_2, _ = _simulate(program, seconds, begin_2)
    assert begin_2 > back
    capture = _capture(first + second, _both_chips(
        {m: runs_1[m] + runs_2[m] for m in (0, 1)}))
    steps, accounts = _account(capture, program)
    assert len(steps) == 2
    # a step's envelope: from its start to the next one's; the last one's
    # to the end of its last run
    assert steps[0].t0_us == pytest.approx(100.0 + SHIFT)
    assert steps[0].envelope_us == pytest.approx(begin_2 - 100.0)
    assert steps[1].t0_us + steps[1].envelope_us == pytest.approx(
        max(r[2] for rs in runs_2.values() for r in rs))
    for joined, account in zip(steps, accounts):
        assert set(account) == {"mesh 0", "mesh 1"}
        for b in account.values():
            assert b.busy_us + sum(b.idle_us.values()) == pytest.approx(
                joined.envelope_us, abs=1e-6)
            assert b.busy_us + b.warmup_us + b.steady_idle_us + \
                b.drain_us == pytest.approx(joined.envelope_us, abs=1e-6)
            assert all(us >= 0.0 for us in b.idle_us.values())
            assert b.idle_us["boundary"] >= 15.0 - 1e-6
        # the stages' seconds are the device's, not the enqueue's 4 us
        assert account["mesh 0"].busy_us == pytest.approx(
            4 * (30.0 + 70.0) + 20.0)
        assert account["mesh 1"].busy_us == pytest.approx(
            4 * (40.0 + 65.0) + 25.0)
        # mesh 1 waits for the first activation, mesh 0 for the last
        # gradient: the fill and the drain are the pipeline's own
        assert account["mesh 1"].idle_us["upstream"] >= 30.0
        assert account["mesh 0"].idle_us["upstream"] >= 65.0
    # the 37 us between the steps are the first step's boundary
    assert accounts[0]["mesh 0"].idle_us["boundary"] >= 15.0 + 37.0 - 1e-6


def test_pipeline_time_sums_the_steps_and_the_report_reads_the_device():
    ops, seconds = _one_f_one_b()
    program = _program(ops)
    spans, runs, _ = _simulate(program, seconds, 100.0)
    capture = _capture(spans, _both_chips(runs))
    assert capture.pipeline_time() == {}        # no pipeline was kept
    capture = ttrace.Capture(
        "/nowhere", spans, 0.0, _offset_us=SHIFT,
        _device_time=capture.device_time(),
        _pipelines=[{"program": program, "mesh_chips": CHIPS,
                     "run_programs": {}}])
    found = capture.pipeline_time()
    assert capture.pipeline_time() is found     # made once
    assert set(found) == {"mesh 0", "mesh 1"}
    for row in found.values():
        assert row["chips"] == 2
        assert row["busy_s"] + sum(
            row[f"{c}_s"] for c in perf.IDLE_CAUSES) == pytest.approx(
                row["envelope_s"], abs=1e-12)
    (joined,) = perf.joined_from_capture(capture, program, CHIPS)
    report = perf.build_step_report(joined, program=program)
    assert report.source == "device"
    assert "the device's clock" in report.format_text().splitlines()[0]
    assert "idle by cause" in report.format_text()
    row = report.to_dict()["bubbles"]["mesh 1"]
    assert row["idle_us"]["upstream"] == pytest.approx(
        found["mesh 1"]["upstream_s"] * 1e6, abs=1e-3)
    # the RUN ops carry device seconds: what calibration and MFU read
    assert {o.dur_us for o in joined.ops
            if o.name == "RUN stage_1_bwd"} == {65.0}
    assert {o.dur_us for o in joined.host_ops
            if o.name == "RUN stage_1_bwd"} == {4.0}
    perf.publish_report(report)
    text = tmetrics.get_registry().to_prometheus_text()
    assert 'alpa_step_idle_seconds{cause="upstream",mesh="1"}' in text or \
        'alpa_step_idle_seconds{mesh="1",cause="upstream"}' in text
    # the host's clock says so on its first line
    host = perf.build_step_report(perf._join_spans(spans, program),
                                  program=program)
    assert host.source == "trace"
    assert "the host's clock" in host.format_text().splitlines()[0]
    assert "idle by cause" not in host.format_text()


# ---- (b) one case a cause -----------------------------------------------------

TWO_MESHES = [("RUN a", 0, [], [0]), ("RESHARD 0->1", 1, [0], [1]),
              ("RUN b", 1, [1], [2])]
ONE_MESH = [("RUN a", 0, [], [0]), ("RUN b", 0, [], [1])]


@pytest.mark.parametrize("ops, placed, host, runs, cause, us", [
    # a late producer: b's enqueue returned long before a ended, and b
    # began the instant a ended; mesh 0 then waits for b, the step's last
    (TWO_MESHES, 0.0, [(0, 5), (5, 8), (8, 12)],
     {0: [("jit_a", 0.0, 100.0)], 1: [("jit_b", 100.0, 160.0)]},
     "upstream", 100.0 + 60.0),
    # a late enqueue: a ended at 100, the driver came back with b at 150
    (ONE_MESH, 0.0, [(0, 5), (140, 150)],
     {0: [("jit_a", 0.0, 100.0), ("jit_b", 150.0, 200.0)]},
     "dispatch", 50.0),
    # a late start after both: b enqueued by 20, a ended at 100, b began
    # at 130
    (ONE_MESH, 0.0, [(0, 5), (10, 20)],
     {0: [("jit_a", 0.0, 100.0), ("jit_b", 130.0, 200.0)]},
     "edge", 30.0),
    # a long place-inputs: the one program began as it returned
    ([("RUN a", 0, [], [0])], 80.0, [(80, 80)],
     {0: [("jit_a", 80.0, 150.0)]},
     "boundary", 80.0),
])
def test_one_cause_alone(ops, placed, host, runs, cause, us):
    program = _program(ops)
    # the device's instants above are microseconds after the step's start
    spans = _step_spans(program, 0.0, placed, host, host[-1][1])
    on_device = {m: [(p, s + SHIFT, e + SHIFT) for p, s, e in rs]
                 for m, rs in runs.items()}
    capture = _capture(spans, _both_chips(on_device))
    _, (account,) = _account(capture, program)
    total = dict.fromkeys(perf.IDLE_CAUSES, 0.0)
    for b in account.values():
        for c, v in b.idle_us.items():
            total[c] += v
    assert total.pop(cause) == pytest.approx(us)
    assert all(v == pytest.approx(0.0, abs=1e-9) for v in total.values()), \
        total


def test_dispatch_is_named_by_the_shortest_span_open():
    program = _program(ONE_MESH)
    spans = _step_spans(program, 0.0, 0.0, [(0, 5), (100, 150)], 150.0)
    # from 110 to 140 the driver stood in the other mesh's queue
    spans.append(_span(perf.RUN_AHEAD_SPAN, 110.0, 140.0,
                       args={"mesh": 1}))
    capture = _capture(spans, _both_chips(
        {0: [("jit_a", SHIFT, SHIFT + 100.0),
             ("jit_b", SHIFT + 150.0, SHIFT + 200.0)]}))
    _, (account,) = _account(capture, program)
    b = account["mesh 0"]
    assert b.idle_us["dispatch"] == pytest.approx(50.0)
    assert b.dispatch_by_span == pytest.approx({
        "RUN b": 20.0, "pipeshard.run-ahead (mesh 1)": 30.0})


# ---- (c) the k-th run of a program is the k-th RUN op of it ------------------

def test_kth_run_over_the_meshs_chips_and_a_count_that_differs():
    program = _program([("RUN a", 0, [], [0]), ("RUN a", 0, [], [1]),
                        ("RUN b", 1, [], [2])])
    spans = _step_spans(program, 0.0, 0.0, [(0, 2), (2, 4), (4, 6)], 6.0)
    runs = {0: [("jit_a", SHIFT + 10, SHIFT + 50),
                ("jit_a", SHIFT + 60, SHIFT + 90)],
            1: [("jit_a", SHIFT + 12, SHIFT + 55),
                ("jit_a", SHIFT + 58, SHIFT + 88)],
            2: [("jit_b", SHIFT + 7, SHIFT + 30)],
            3: [("jit_b", SHIFT + 9, SHIFT + 33)]}
    (joined,) = perf.joined_from_capture(_capture(spans, runs), program,
                                         CHIPS)
    assert joined.source == "device" and not joined.notes
    at = [(o.t0_us - SHIFT, o.t1_us - SHIFT) for o in joined.ops]
    # earliest start, latest end over the mesh's two chips
    assert at == [(10, 55), (58, 90), (7, 33)]
    assert [(o.t0_us - SHIFT, o.t1_us - SHIFT)
            for o in joined.host_ops] == [(0, 2), (2, 4), (4, 6)]
    # a program's name the executable gives wins over ``jit_<stage>``
    renamed = {c: [("jit_other", s, e) if p == "jit_b" else (p, s, e)
                   for p, s, e in rs] for c, rs in runs.items()}
    (joined,) = perf.joined_from_capture(
        _capture(spans, renamed), program, CHIPS, {"RUN b": "jit_other"})
    assert joined.source == "device"
    # chip 1 ran the program once more than mesh 0 has RUN ops of it: no
    # account, and the report says why
    runs[1].append(("jit_a", SHIFT + 95, SHIFT + 99))
    capture = _capture(spans, runs)
    (joined,) = perf.joined_from_capture(capture, program, CHIPS)
    assert joined.source == "trace" and joined.host_ops is None
    assert len(joined.notes) == 1
    assert "chip 1 ran jit_a 3 times" in joined.notes[0]
    assert "2 RUN a ops" in joined.notes[0]
    report = perf.build_step_report(joined, program=program)
    assert report.source == "trace" and report.notes == joined.notes
    assert all(b.idle_us is None for b in report.bubbles.values())
    kept = ttrace.Capture(
        "/nowhere", spans, 0.0, _offset_us=SHIFT,
        _device_time=capture.device_time(),
        _pipelines=[{"program": program, "mesh_chips": CHIPS,
                     "run_programs": {}}])
    assert kept.pipeline_time() == {}


def test_a_capture_with_no_device_event_joins_on_the_hosts_clock():
    program = _program(ONE_MESH)
    spans = _step_spans(program, 0.0, 0.0, [(0, 5), (5, 9)], 9.0)
    capture = ttrace.Capture("/nowhere", spans, 0.0, _offset_us=SHIFT,
                             _device_time=dt.empty_table())
    (joined,) = perf.joined_from_capture(capture, program, CHIPS)
    assert joined.source == "trace" and joined.notes == []
    assert [o.t0_us for o in joined.ops] == [0, 5]
    # no trace at all: the same, and nothing raises
    gone = ttrace.Capture("/nowhere", spans, 0.0)
    (joined,) = perf.joined_from_capture(gone, program, CHIPS)
    assert joined.source == "trace"
    assert perf.joined_from_capture(
        ttrace.Capture("/nowhere", [], 0.0), program, CHIPS) == []


# ---- (d) collectives: exposed against hidden ---------------------------------

def test_collectives_exposed_and_hidden():
    events = [("all-gather-start.1", 10.0, 12.0),
              ("all-gather-done.1", 50.0, 55.0),    # compute from 12 to 50
              ("all-reduce.2", 60.0, 80.0)]         # synchronous
    exposed, hidden = perf._collective_time(events, 0.0, 100.0)
    assert exposed == pytest.approx(2.0 + 5.0 + 20.0)
    assert hidden == pytest.approx(38.0)
    # clipped to the envelope; a -done whose -start the envelope cut pairs
    # with nothing
    exposed, hidden = perf._collective_time(events[1:], 52.0, 70.0)
    assert exposed == pytest.approx(3.0 + 10.0)
    assert hidden == 0.0
    # paired by number, else by kind with the oldest; two in flight at
    # once are hidden once (never more than the envelope); the TPU
    # compiler's name for a wrapped one; a fusion that holds a collective
    # is exposed and is no half of a pair
    exposed, hidden = perf._collective_time(
        [("async-collective-start.3", 0.0, 1.0),
         ("async-collective-start.4", 1.0, 2.0),
         ("all-reduce-scatter-fusion-start", 2.0, 3.0),
         ("async-collective-done.7", 10.0, 11.0),
         ("async-collective-done.4", 20.0, 21.0)], 0.0, 30.0)
    assert exposed == pytest.approx(5.0)
    assert hidden == pytest.approx(20.0 - 1.0)


def test_the_table_keeps_the_instants_and_the_account_reads_them():
    program = _program([("RUN a", 0, [], [0])])
    spans = _step_spans(program, 0.0, 0.0, [(0, 2)], 2.0)
    run = ("jit_a", SHIFT + 10.0, SHIFT + 110.0)
    moving = [("all-gather-start.1", SHIFT + 20.0, SHIFT + 22.0),
              ("all-gather-done.1", SHIFT + 60.0, SHIFT + 65.0),
              ("all-reduce.2", SHIFT + 70.0, SHIFT + 90.0)]
    capture = _capture(spans, {0: [run], 1: [run]},
                       collectives={0: moving, 1: moving[2:]},
                       chips={0: [0, 1]})
    table = capture.device_time()
    entry = table["programs"][0]["jit_a"]
    assert entry["run_us"] == [(SHIFT + 10.0, SHIFT + 110.0)]
    assert entry["run_s"] == pytest.approx([100e-6])
    # only the collectives, in order, on the profiler's clock
    assert table["collectives"][0] == moving
    assert table["collectives"][1] == moving[2:]
    assert entry["parts"][dt.COLLECTIVE] == pytest.approx(27e-6)
    # a prefetch's copy-done that feeds a collective inherits the part and
    # is no collective: in ``parts``, not among the intervals
    inherits = dt.reduce_events(
        {0: ([("all-reduce.2", 10, 30), ("copy-done.3", 30, 40)],
             [("jit_a", 0, 50)])}, (0, 50),
        lambda name: [{"all-reduce.2": (dt.COLLECTIVE, None),
                       "copy-done.3": (dt.COLLECTIVE, dt.INHERITED)}])
    assert inherits["programs"][0]["jit_a"]["parts"] == pytest.approx(
        {dt.COLLECTIVE: 30e-9})
    assert inherits["collectives"][0] == [("all-reduce.2", 0.01, 0.03)]
    _, (account,) = _account(capture, program, {0: [0, 1]})
    b = account["mesh 0"]
    assert b.n_chips == 2
    # means over the mesh's two chips: (27 + 20) / 2 and (38 + 0) / 2
    assert b.collective_exposed_us == pytest.approx(23.5)
    assert b.collective_hidden_us == pytest.approx(19.0)


# ---- the span of a RUN that waits for its mesh's queue ------------------------

class _Token:
    def __init__(self, ready):
        self.ready, self.waited = ready, 0

    def is_deleted(self):
        return False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited += 1


@pytest.mark.parametrize("tracing, ready, spans", [
    (True, False, 1), (True, True, 0), (False, False, 0)])
def test_run_ahead_span_only_when_tracing_and_blocking(tracing, ready,
                                                       spans):
    import collections
    from alpa_tpu.pipeline_parallel import runtime_emitter as re_
    rec = ttrace.TraceRecorder()
    old, was = ttrace.set_recorder(rec), ttrace.set_enabled(tracing)
    try:
        tokens = [_Token(ready) for _ in range(re_._RUN_AHEAD)]
        re_._settle_run_ahead(collections.deque(tokens), mesh=1)
    finally:
        ttrace.set_enabled(was)
        ttrace.set_recorder(old)
    assert tokens[0].waited == 1
    found = [s for s in rec.spans() if s["name"] == perf.RUN_AHEAD_SPAN]
    assert len(found) == spans
    for s in found:
        # not an op of the program: ``pipeshard_ops_per_step`` counts the
        # categories ``instruction`` and ``transfer``
        assert s["category"] == "runtime" and s["args"] == {"mesh": 1}
