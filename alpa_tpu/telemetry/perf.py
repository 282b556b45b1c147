"""Post-step performance analysis: StepPerfReport (ISSUE 9 tentpole).

Joins the raw telemetry PRs 5–6 collect — per-op trace spans from the
hooked graph executor (``op_meta``), or flight-ring events when full
tracing is off — back against the lowering-time
:class:`~alpa_tpu.pipeline_parallel.runtime_emitter.
InstructionDataflowGraph`, and turns one step's stream into answers:

* **critical path** — the measured longest chain through the step
  (:mod:`alpa_tpu.analysis.critical_path`), with a what-if re-simulator
  over the dependency DAG ("if this RESHARD were free, step −X%");
* **bubble accounting** — per-mesh busy/warmup/steady-idle/drain
  decomposition of the step envelope, keyed against the
  ``PipelineSchedule``'s expected warmup/drain depth, plus
  exposed-vs-hidden transfer time (extending PR 4's
  ``overlap_fraction``) split into queue-wait vs wire time by the
  ``reshard.wait`` / ``reshard.wire`` child spans;
* **MFU attribution** — per-stage analytic FLOPs
  (``util.jaxpr_eqn_flops`` over the stage's closed jaxpr) over measured
  RUN span time and the chip peak (``device_peak_tflops`` knob /
  ``ALPA_TPU_DEVICE_PEAK_TFLOPS``, auto-detected from
  ``TPU_GENERATION_SPECS`` otherwise).

Published to the central metrics registry as ``alpa_stage_mfu{stage}``,
``alpa_step_bubble_fraction{mesh}`` and ``alpa_critical_path_us``;
surfaced as ``perf_report.txt`` in debug dumps,
``PipeshardDriverExecutable.get_perf_report()``, and
``scripts/perf_tool.py``.  This module is also the home of the
library's peak-FLOPs/MFU formula.
"""
import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from alpa_tpu.analysis.critical_path import (
    CriticalPathReport, TimedOp, measured_critical_path, simulate_dag,
    whatif as _whatif_dag)
from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import device_time as _device_time
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry.trace import CAPTURE_MARKER

__all__ = [
    "device_peak_tflops", "peak_flops_info", "stage_flops",
    "compute_mfu", "mfu_from_time",
    "JoinedStep", "MeshBubbles", "TransferBreakdown", "StageMfu",
    "StepPerfReport",
    "joined_from_recorder", "joined_from_flight", "joined_from_capture",
    "pipeline_time", "IDLE_CAUSES", "spans_from_chrome",
    "build_step_report", "report_from_trace",
    "publish_report", "record_gate_verdict",
]


########################################
# the one peak-FLOPs / MFU formula (satellite S1)
########################################


def peak_flops_info(generation: Optional[str] = None) -> Dict[str, Any]:
    """Resolve the chip peak used for MFU: the ``device_peak_tflops``
    knob (``ALPA_TPU_DEVICE_PEAK_TFLOPS``) when set, else the detected
    TPU generation's published bf16 peak."""
    override = float(getattr(global_config, "device_peak_tflops", 0.0)
                     or 0.0)
    if override > 0:
        return {"generation": generation or "override",
                "peak_bf16_tflops": override}
    from alpa_tpu.mesh_profiling import (TPU_GENERATION_SPECS,
                                         detect_tpu_generation)
    gen = generation or detect_tpu_generation()
    return {"generation": gen,
            "peak_bf16_tflops": TPU_GENERATION_SPECS[gen]
            ["peak_bf16_tflops"]}


def device_peak_tflops(generation: Optional[str] = None) -> float:
    return peak_flops_info(generation)["peak_bf16_tflops"]


def compute_mfu(tflops_per_chip: float,
                peak_tflops: Optional[float] = None) -> float:
    """achieved TFLOPS per chip / peak TFLOPS per chip."""
    peak = peak_tflops if peak_tflops else device_peak_tflops()
    return tflops_per_chip / peak if peak > 0 else 0.0


def mfu_from_time(flops: float, seconds: float, n_devices: int,
                  peak_tflops: Optional[float] = None) -> float:
    """MFU from raw measurements: total model FLOPs over ``seconds``
    spread across ``n_devices`` chips."""
    if seconds <= 0 or n_devices <= 0:
        return 0.0
    return compute_mfu(flops / seconds / n_devices / 1e12, peak_tflops)


def stage_flops(closed_jaxpr) -> float:
    """Analytic FLOPs of one stage invocation (``util.jaxpr_eqn_flops``
    summed over the stage's closed jaxpr).  The backward stage of
    rematerialised blocks holds its work in ``remat2`` equations (jax's
    ``checkpoint``), which that function counts as one elementwise
    operation (it also prices stages for the planner, so it stays as it
    is): here their bodies count, the recomputation included."""
    from alpa_tpu.util import jaxpr_eqn_flops

    def flops(eqn):
        if eqn.primitive.name == "remat2":
            return sum(flops(e) for e in eqn.params["jaxpr"].eqns)
        return jaxpr_eqn_flops(eqn)

    return float(sum(flops(eqn) for eqn in closed_jaxpr.jaxpr.eqns))


########################################
# joining spans / flight events back to the lowered program
########################################


@dataclasses.dataclass
class JoinedStep:
    """One step's op samples on a common time axis, pre-report.

    A RUN is an asynchronous enqueue: under ``"trace"`` and ``"flight"`` an
    op's interval is the driver's, from when it entered the op to when the
    enqueue returned, which says what the host did and little of what the
    chips did.  Under ``"device"`` (:func:`joined_from_capture`) every
    instant is on the profiler's clock, a RUN op's interval is that of its
    program's run on its mesh's chips, and the fields below the line hold
    what the account of idle time needs beside it."""
    ops: List[TimedOp]
    t0_us: float
    envelope_us: float
    pool_spans: List[Dict[str, Any]]     # alpa-overlap-* track spans
    source: str                          # "trace" | "flight" | "device"
    aligned: bool                        # ops joined 1:1 to program hooks
    notes: List[str] = dataclasses.field(default_factory=list)
    # ---- source == "device" alone ----
    # the driver's instants of the same ops (entered, enqueue returned)
    host_ops: Optional[List[TimedOp]] = None
    # when the step's ``pipeshard.place-inputs`` returned
    inputs_placed_us: float = 0.0
    # (name, start, end) of every span of the capture that touches the
    # envelope: what the driver was in while a chip waited for it
    host_spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    mesh_chips: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)            # track -> the mesh's chips
    # chip -> [(instruction, start, end)] of its collective events
    collectives: Dict[int, List[tuple]] = dataclasses.field(
        default_factory=dict)


STEP_SPAN = "pipeshard.step"
PLACE_INPUTS_SPAN = "pipeshard.place-inputs"
RUN_AHEAD_SPAN = "pipeshard.run-ahead"
# why a mesh idles, in the order an instant is tried against them
IDLE_CAUSES = ("boundary", "upstream", "dispatch", "edge")


def _kind_from_name(name: str) -> str:
    if name.startswith("LAUNCH"):
        return "launch"
    if name.startswith("WAIT"):
        return "wait"
    return "exec"


def last_step_start(spans: Sequence[Dict[str, Any]]) -> Optional[float]:
    """``ts_us`` of the newest ``pipeshard.step`` span, or None: a capture
    holds the recorder's last step where the two agree."""
    return max((s["ts_us"] for s in spans if s["name"] == STEP_SPAN),
               default=None)


def _join_spans(spans: Sequence[Dict[str, Any]],
                program=None, step=None) -> Optional[JoinedStep]:
    """Window the span list to the envelope of ``step`` (a
    ``pipeshard.step`` span; the last one where none is given) and
    align the per-op spans positionally against the program's
    ``op_meta``/``hooks`` (both are emitted in replay order)."""
    w0 = w1 = None
    if step is None:
        steps = [s for s in spans if s["name"] == STEP_SPAN]
        step = max(steps, key=lambda s: s["ts_us"]) if steps else None
    if step is not None:
        w0, w1 = step["ts_us"], step["ts_us"] + step["dur_us"]

    def in_window(s):
        return w0 is None or (s["ts_us"] >= w0 - 1.0 and
                              s["ts_us"] + s["dur_us"] <= w1 + 1.0)

    op_spans = sorted(
        (s for s in spans
         if s["category"] in ("instruction", "transfer") and
         (s.get("track") or "").startswith("mesh") and in_window(s)),
        key=lambda s: (s["ts_us"], s["ts_us"] + s["dur_us"]))
    if not op_spans:
        return None
    pool = [s for s in spans
            if (s.get("track") or "").startswith("alpa-overlap") and
            in_window(s)]
    hooks = getattr(program, "hooks", None) if program is not None \
        else None
    meta = getattr(program, "op_meta", None) if program is not None \
        else None
    aligned = (hooks is not None and meta is not None and
               len(op_spans) == len(meta) and
               all(s["name"] == m[0]
                   for s, m in zip(op_spans, meta)))
    ops = []
    for i, s in enumerate(op_spans):
        kind = hooks[i].kind if aligned else _kind_from_name(s["name"])
        ops.append(TimedOp(idx=i, name=s["name"], kind=kind,
                           track=s["track"], t0_us=s["ts_us"],
                           t1_us=s["ts_us"] + s["dur_us"]))
    if w0 is None:
        w0 = min(o.t0_us for o in ops)
        w1 = max(o.t1_us for o in ops)
    return JoinedStep(ops=ops, t0_us=w0, envelope_us=w1 - w0,
                      pool_spans=pool, source="trace", aligned=aligned)


def joined_from_recorder(rec, program=None) -> Optional[JoinedStep]:
    """Join the live trace recorder's spans (preferred source)."""
    return _join_spans(rec.spans(), program)


def joined_from_flight(events: Sequence[Any],
                       program=None) -> Optional[JoinedStep]:
    """Fallback join over flight-ring events (full tracing off).

    Events are ``(seq, kind, name, mesh, node, slots, t0, t1, outcome)``
    tuples (``flight._FIELDS``) or equivalent dicts from a dump."""
    rows = []
    for e in events:
        if isinstance(e, dict):
            rows.append((e["kind"], e["name"], e["mesh"],
                         e["t_start_us"], e["t_end_us"]))
        else:
            rows.append((e[1], e[2], e[3], e[6], e[7]))
    if not rows:
        return None
    hooks = getattr(program, "hooks", None) if program is not None \
        else None
    if hooks and len(rows) >= len(hooks):
        # the ring holds many steps; the trailing len(ops) events are
        # the last replay (each step appends exactly one event per op)
        tail = rows[-len(hooks):]
        if all(r[1] == h.name for r, h in zip(tail, hooks)):
            rows = tail
    aligned = bool(hooks) and len(rows) == len(hooks) and \
        all(r[1] == h.name for r, h in zip(rows, hooks))
    ops = []
    for i, (kind, name, mesh, t0, t1) in enumerate(rows):
        k = hooks[i].kind if aligned else (
            kind if kind in ("exec", "launch", "wait")
            else _kind_from_name(name))
        ops.append(TimedOp(idx=i, name=name, kind=k,
                           track=f"mesh {mesh}", t0_us=t0, t1_us=t1))
    w0 = min(o.t0_us for o in ops)
    w1 = max(o.t1_us for o in ops)
    return JoinedStep(ops=ops, t0_us=w0, envelope_us=w1 - w0,
                      pool_spans=[], source="flight", aligned=aligned)


def _is_run(op: TimedOp) -> bool:
    return op.kind == "exec" and op.name.startswith("RUN ")


def joined_from_capture(capture, program, mesh_chips: Dict[int, Sequence[int]],
                        run_programs: Optional[Dict[str, str]] = None
                        ) -> List[JoinedStep]:
    """Join every ``pipeshard.step`` of a
    :class:`~alpa_tpu.telemetry.trace.Capture` on the device's clock: one
    :class:`JoinedStep` a traced step, oldest first.

    Each step is joined as :func:`joined_from_recorder` joins the last one,
    every instant shifted by ``capture.offset_us()``; then each RUN op takes
    the interval its program ran on its mesh's chips: the k-th ``RUN
    stage_0_bwd`` on track ``mesh 0`` inside the step is the k-th run of
    ``jit_stage_0_bwd`` on each of that mesh's chips inside it, from the
    earliest start to the latest end over them
    (``Capture.device_time()``'s ``run_us``).  ``mesh_chips`` maps a mesh's
    index to its chips' ids, ``run_programs`` a RUN op's name to the name
    the profiler gives its program's runs (``jit_`` and the stage's name
    where it says nothing).  The ops that run no program keep the driver's
    instants, and ``host_ops`` keeps them for the RUN ops too.  A step's
    envelope runs from its ``pipeshard.step`` span's start to the next
    step's, and the last traced step's to the end of its last run: the span
    itself ends when the driver returns, before the chips do.

    Where a capture holds no device event (the CPU), the spans do not line
    up with ``program``, or a chip ran a program another number of times
    than the step has RUN ops for it, the step is joined on the host's
    clock (``source == "trace"``) and its ``notes`` say why: a count that
    does not match gives no account, never a guessed one."""
    steps = sorted((s for s in capture.spans if s["name"] == STEP_SPAN),
                   key=lambda s: s["ts_us"])
    try:
        table = capture.device_time()
        shift = capture.offset_us() if table["programs"] else None
    except (FileNotFoundError, ValueError):     # no trace, or no marker
        table, shift = None, None
    joined = []
    for k, step in enumerate(steps):
        host = _join_spans(capture.spans, program, step)
        if host is None:
            continue
        if shift is not None:
            until = (steps[k + 1]["ts_us"] + shift if k + 1 < len(steps)
                     else None)
            host = _on_device_clock(host, capture, table, shift, until,
                                    mesh_chips, run_programs or {})
        joined.append(host)
    return joined


def _on_device_clock(host: JoinedStep, capture, table, shift: float,
                     until: Optional[float], mesh_chips, run_programs
                     ) -> JoinedStep:
    """``host`` with its RUN ops on the device's clock (see
    :func:`joined_from_capture`), or ``host`` itself with a note."""
    if not host.aligned:
        host.notes.append(
            "device join: the spans did not align 1:1 with the lowered "
            "program; the report reads the host's clock")
        return host
    t0 = host.t0_us + shift
    shifted = [dataclasses.replace(o, t0_us=o.t0_us + shift,
                                   t1_us=o.t1_us + shift)
               for o in host.ops]
    # (track, program) -> the RUN ops of it, in the driver's order
    asked: Dict[Tuple[str, str], List[int]] = {}
    for o in shifted:
        if _is_run(o):
            name = run_programs.get(o.name, "jit_" + o.name[4:])
            asked.setdefault((o.track, name), []).append(o.idx)
    tracks = {f"mesh {m}": list(chips) for m, chips in mesh_chips.items()}
    ops = list(shifted)
    for (track, name), idxs in asked.items():
        found = []          # a chip: its runs of the program in the step
        for chip in tracks.get(track, ()):
            runs = table["programs"].get(chip, {}).get(name, {}).get(
                "run_us", ())
            found.append([r for r in runs if t0 <= r[0] and
                          (until is None or r[0] < until)])
            if len(found[-1]) != len(idxs):
                host.notes.append(
                    f"device join: chip {chip} ran {name} "
                    f"{len(found[-1])} times inside the step and {track} "
                    f"has {len(idxs)} {shifted[idxs[0]].name} ops; the "
                    "report reads the host's clock")
                return host
        if not found:
            host.notes.append(f"device join: {track} has no chips in "
                              f"{sorted(tracks)}; the report reads the "
                              "host's clock")
            return host
        for k, i in enumerate(idxs):
            ops[i] = dataclasses.replace(
                ops[i], t0_us=min(runs[k][0] for runs in found),
                t1_us=max(runs[k][1] for runs in found))
    end = until if until is not None else max(
        (o.t1_us for o in ops if _is_run(o)), default=t0)
    placed = [s for s in capture.spans if s["name"] == PLACE_INPUTS_SPAN and
              host.t0_us <= s["ts_us"] <= host.t0_us + host.envelope_us]
    return JoinedStep(
        ops=ops, t0_us=t0, envelope_us=end - t0,
        pool_spans=[dict(s, ts_us=s["ts_us"] + shift)
                    for s in host.pool_spans],
        source="device", aligned=True, notes=host.notes, host_ops=shifted,
        inputs_placed_us=(placed[0]["ts_us"] + placed[0]["dur_us"] + shift
                          if placed else t0),
        host_spans=[(_span_label(s), s["ts_us"] + shift,
                     s["ts_us"] + s["dur_us"] + shift)
                    for s in capture.spans
                    if s["name"] != CAPTURE_MARKER and s["dur_us"] > 0 and
                    s["ts_us"] + shift < end and
                    s["ts_us"] + s["dur_us"] + shift > t0],
        mesh_chips=tracks,
        collectives={chip: [c for c in events if c[2] > t0 and c[1] < end]
                     for chip, events in table["collectives"].items()})


def _span_label(span: Dict[str, Any]) -> str:
    """A span's name, a driver's wait for a mesh's queue with the mesh."""
    if span["name"] == RUN_AHEAD_SPAN and span.get("args"):
        return f"{RUN_AHEAD_SPAN} (mesh {span['args'].get('mesh')})"
    return span["name"]


def _op_dependencies(program, n_ops: int
                     ) -> Tuple[Dict[int, set], List[set]]:
    """Map dataflow-graph edges into op space.

    Returns ``(causal, sim_preds)``: ``causal[i]`` are the ops whose
    *retirement* (exec, or the wait of a launched transfer) gates op
    ``i`` — used by the measured walk; ``sim_preds`` additionally
    carries same-mesh issue order (each mesh is one serial instruction
    stream) and launch→wait edges — the re-simulation model."""
    graph, hooks = program.graph, program.hooks
    retire: Dict[int, int] = {}
    launch_of: Dict[int, int] = {}
    for i, h in enumerate(hooks):
        if h.kind in ("exec", "wait"):
            for m in h.members:
                retire[m] = i
        if h.kind == "launch":
            for m in h.members:
                launch_of[m] = i
    causal: Dict[int, set] = {i: set() for i in range(n_ops)}
    for i, h in enumerate(hooks):
        for m in h.members:
            for p in graph.preds[m]:
                j = retire.get(p)
                if j is not None and j != i:
                    causal[i].add(j)
        if h.kind == "wait":
            j = launch_of.get(h.members[0])
            if j is not None and j != i:
                causal[i].add(j)
    sim_preds = [set(causal[i]) for i in range(n_ops)]
    last_on_mesh: Dict[int, int] = {}
    for i, h in enumerate(hooks):
        p = last_on_mesh.get(h.mesh)
        if p is not None:
            sim_preds[i].add(p)
        last_on_mesh[h.mesh] = i
    return causal, sim_preds


########################################
# report pieces
########################################


@dataclasses.dataclass
class MeshBubbles:
    """One mesh's share of the step envelope."""
    mesh: str
    envelope_us: float
    busy_us: float
    warmup_us: float          # idle before the mesh's first op
    steady_idle_us: float     # gaps between ops
    drain_us: float           # idle after the mesh's last op
    n_ops: int
    stream_wait_us: float     # driver time blocked in WAIT ops here
    sched_warmup_ticks: Optional[int] = None
    sched_drain_ticks: Optional[int] = None
    sched_num_clock: Optional[int] = None
    # ---- on the device's clock alone (a report whose source is "device"):
    # every idle instant of the envelope by cause (``IDLE_CAUSES``), so
    # that busy_us + sum(idle_us.values()) == envelope_us
    idle_us: Optional[Dict[str, float]] = None
    # the ``dispatch`` part by the shortest span open on the host then
    dispatch_by_span: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    n_chips: int = 0
    # mean over the mesh's chips: the op line in a collective, and between
    # an asynchronous collective's ``-start`` and its ``-done``
    collective_exposed_us: float = 0.0
    collective_hidden_us: float = 0.0

    def fractions(self) -> Dict[str, float]:
        e = self.envelope_us or 1.0
        return {"busy": self.busy_us / e,
                "warmup": self.warmup_us / e,
                "steady_idle": self.steady_idle_us / e,
                "drain": self.drain_us / e}

    @property
    def bubble_fraction(self) -> float:
        """1 − busy/envelope: the alpa_step_bubble_fraction gauge."""
        if self.envelope_us <= 0:
            return 0.0
        return max(0.0, 1.0 - self.busy_us / self.envelope_us)


@dataclasses.dataclass
class TransferBreakdown:
    """Exposed vs hidden transfer time (extends PR 4's
    overlap_fraction) with S2's queue-wait/wire split."""
    pool_busy_us: float = 0.0     # pool-side transfer occupancy
    wire_us: float = 0.0          # reshard.wire child spans
    queue_wait_us: float = 0.0    # reshard.wait child spans (scheduler
                                  # backpressure, NOT network time)
    exposed_wait_us: float = 0.0  # driver blocked in WAIT ops
    hidden_us: float = 0.0        # pool busy the driver never saw
    overlap_fraction: float = 1.0


@dataclasses.dataclass
class StageMfu:
    stage: str
    flops_per_run: float
    n_runs: int
    run_time_us: float
    n_devices: int
    peak_tflops: float
    tflops_per_chip: float
    mfu: float


@dataclasses.dataclass
class StepPerfReport:
    # the clock the RUN ops were read on: "device" (a capture's device
    # events) or the host's, around an enqueue ("trace" | "flight")
    source: str
    mode: Optional[str]
    envelope_us: float
    n_ops: int
    aligned: bool                 # dataflow graph joined (vs track-only)
    critical_path: CriticalPathReport
    bubbles: Dict[str, MeshBubbles]
    transfers: TransferBreakdown
    stages: Dict[str, StageMfu]
    notes: List[str] = dataclasses.field(default_factory=list)
    # program -> what its planner did with the pairs that share a donated
    # buffer and with the inputs it was handed as given, and what
    # unification took from its plan (``solver.alias_stats``,
    # ``solver.given_stats``, ``unify_overrides``; from the ``ilp-solve``
    # and ``unify-shardings`` spans' args)
    donated: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    # re-simulation model (kept for whatif; not part of the text report)
    sim_durs_us: List[float] = dataclasses.field(
        default_factory=list, repr=False)
    sim_preds: List[tuple] = dataclasses.field(
        default_factory=list, repr=False)
    sim_ops: List[TimedOp] = dataclasses.field(
        default_factory=list, repr=False)

    # ---- what-if re-simulation --------------------------------------

    def whatif(self, zero: str = "reshard",
               name_substr: Optional[str] = None) -> Dict[str, Any]:
        """Re-simulate the DAG with an op class made free.

        ``zero``: "reshard"/"transfer" (launch+wait+RESHARD execs),
        "run", "free", or "name" with ``name_substr``."""
        zeroed = {o.idx for o in self.sim_ops
                  if _matches_class(o, zero, name_substr)}
        baseline, _ = simulate_dag(self.sim_durs_us, self.sim_preds)
        after = _whatif_dag(self.sim_durs_us, self.sim_preds, zeroed)
        saving = max(0.0, baseline - after)
        return {
            "zero": zero if name_substr is None else f"name:{name_substr}",
            "n_zeroed": len(zeroed),
            "baseline_us": baseline,
            "whatif_us": after,
            "saving_us": saving,
            "saving_fraction": saving / baseline if baseline > 0 else 0.0,
        }

    # ---- serialization ----------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        """Flat-ish dict for perf_tool --json / perf_gate baselines."""
        return {
            "source": self.source,
            "mode": self.mode,
            "aligned": self.aligned,
            "n_ops": self.n_ops,
            "envelope_us": round(self.envelope_us, 3),
            "critical_path_us": round(self.critical_path.total_us, 3),
            "critical_path_coverage": round(self.critical_path.coverage,
                                            4),
            "critical_path_gap_us": round(self.critical_path.gap_us, 3),
            "bubbles": {
                m: {"bubble_fraction": round(b.bubble_fraction, 4),
                    "busy_us": round(b.busy_us, 3),
                    "n_ops": b.n_ops,
                    "stream_wait_us": round(b.stream_wait_us, 3),
                    **{f"{k}_fraction": round(v, 4)
                       for k, v in b.fractions().items()},
                    **({} if b.idle_us is None else {
                        "idle_us": {c: round(v, 3)
                                    for c, v in b.idle_us.items()},
                        "dispatch_by_span": {
                            n: round(v, 3)
                            for n, v in b.dispatch_by_span.items()},
                        "collective_exposed_us":
                            round(b.collective_exposed_us, 3),
                        "collective_hidden_us":
                            round(b.collective_hidden_us, 3)})}
                for m, b in sorted(self.bubbles.items())
            },
            "transfers": {
                "pool_busy_us": round(self.transfers.pool_busy_us, 3),
                "wire_us": round(self.transfers.wire_us, 3),
                "queue_wait_us": round(self.transfers.queue_wait_us, 3),
                "exposed_wait_us": round(self.transfers.exposed_wait_us,
                                         3),
                "hidden_us": round(self.transfers.hidden_us, 3),
                "overlap_fraction": round(self.transfers.overlap_fraction,
                                          4),
            },
            "stages": {
                name: {"mfu": round(s.mfu, 6),
                       "tflops_per_chip": round(s.tflops_per_chip, 6),
                       "flops_per_run": s.flops_per_run,
                       "n_runs": s.n_runs,
                       "run_time_us": round(s.run_time_us, 3),
                       "n_devices": s.n_devices,
                       "peak_tflops": s.peak_tflops}
                for name, s in sorted(self.stages.items())
            },
        }

    # ---- text report (perf_report.txt) ------------------------------

    def format_text(self, top: int = 10) -> str:
        clock = ("the device's clock" if self.source == "device" else
                 "the host's clock: a RUN's time is its enqueue's")
        lines = [
            f"step perf report ({self.source}: {clock}"
            f"{', mode=' + self.mode if self.mode else ''}"
            f"{', graph-joined' if self.aligned else ', track-order only'}"
            f"): {self.n_ops} ops over {self.envelope_us:.1f} us",
            "",
            self.critical_path.format_table(top),
            "",
            "per-mesh bubbles (fractions of the step envelope):",
            f"  {'mesh':<8} {'busy':>7} {'warmup':>7} {'steady':>7} "
            f"{'drain':>7} {'bubble':>7} {'ops':>5} {'sched w/d':>10}",
        ]
        for m, b in sorted(self.bubbles.items()):
            f = b.fractions()
            sched = (f"{b.sched_warmup_ticks}/{b.sched_drain_ticks}"
                     if b.sched_warmup_ticks is not None else "-")
            lines.append(
                f"  {m:<8} {f['busy']:7.3f} {f['warmup']:7.3f} "
                f"{f['steady_idle']:7.3f} {f['drain']:7.3f} "
                f"{b.bubble_fraction:7.3f} {b.n_ops:5d} {sched:>10}")
        if any(b.idle_us is not None for b in self.bubbles.values()):
            lines += [
                "",
                "idle by cause (fractions of the step envelope), and the "
                "op line in a collective:",
                f"  {'mesh':<8} " + " ".join(f"{c:>9}" for c in IDLE_CAUSES)
                + f" {'exposed':>9} {'hidden':>9}"]
            for m, b in sorted(self.bubbles.items()):
                if b.idle_us is None:
                    continue
                e = b.envelope_us or 1.0
                lines.append(
                    f"  {m:<8} " + " ".join(
                        f"{b.idle_us[c] / e:9.3f}" for c in IDLE_CAUSES) +
                    f" {b.collective_exposed_us / e:9.3f}"
                    f" {b.collective_hidden_us / e:9.3f}")
                for name, us in sorted(b.dispatch_by_span.items(),
                                       key=lambda kv: -kv[1])[:4]:
                    lines.append(f"  {'':<8} dispatch under {name}: "
                                 f"{us:.1f} us")
        t = self.transfers
        lines += [
            "",
            f"transfers: pool busy {t.pool_busy_us:.1f} us "
            f"(wire {t.wire_us:.1f}, queue-wait {t.queue_wait_us:.1f}), "
            f"exposed {t.exposed_wait_us:.1f} us, hidden "
            f"{t.hidden_us:.1f} us, overlap fraction "
            f"{t.overlap_fraction:.3f}",
        ]
        if self.stages:
            lines += ["", "stage MFU:",
                      f"  {'stage':<24} {'runs':>5} {'time_us':>10} "
                      f"{'TFLOPS/chip':>12} {'MFU':>8}"]
            for name, s in sorted(self.stages.items()):
                lines.append(
                    f"  {name:<24} {s.n_runs:5d} {s.run_time_us:10.1f} "
                    f"{s.tflops_per_chip:12.4f} {s.mfu:8.4f}")
        if self.donated:
            lines += ["", "donated pairs and given inputs, as the planner "
                      "left them:"] + [
                "  " + line for name, stats in self.donated.items()
                for line in format_plan_stats(name, stats)]
        if self.notes:
            lines += [""] + [f"note: {n}" for n in self.notes]
        return "\n".join(lines)


def _matches_class(op: TimedOp, zero: str,
                   name_substr: Optional[str]) -> bool:
    if name_substr is not None:
        return name_substr in op.name
    zero = zero.lower()
    if zero in ("reshard", "transfer"):
        return (op.kind in ("launch", "wait") or
                op.name.startswith("RESHARD"))
    if zero == "run":
        return op.name.startswith("RUN")
    if zero == "free":
        return op.name.startswith("FREE")
    raise ValueError(f"unknown what-if op class {zero!r} "
                     "(reshard|run|free, or pass name_substr)")


########################################
# report construction
########################################


def _mesh_bubbles(ops: Sequence[TimedOp], t0_us: float,
                  envelope_us: float,
                  schedule=None) -> Dict[str, MeshBubbles]:
    t1_env = t0_us + envelope_us
    by_track: Dict[str, List[TimedOp]] = collections.defaultdict(list)
    for o in ops:
        by_track[o.track].append(o)
    sched_first: Dict[int, int] = {}
    sched_last: Dict[int, int] = {}
    num_clock = None
    if schedule is not None:
        ticks = schedule.schedules
        num_clock = len(ticks)
        for t, tick in enumerate(ticks):
            for mesh_id, task in enumerate(tick):
                if task is not None:
                    sched_first.setdefault(mesh_id, t)
                    sched_last[mesh_id] = t
    out: Dict[str, MeshBubbles] = {}
    for track, group in by_track.items():
        group.sort(key=lambda o: o.t0_us)
        busy = sum(max(0.0, min(o.t1_us, t1_env) - max(o.t0_us, t0_us))
                   for o in group)
        first = max(t0_us, min(o.t0_us for o in group))
        last = min(t1_env, max(o.t1_us for o in group))
        warmup = max(0.0, first - t0_us)
        drain = max(0.0, t1_env - last)
        steady = max(0.0, envelope_us - busy - warmup - drain)
        wait_us = sum(o.dur_us for o in group if o.kind == "wait" or
                      o.name.startswith("WAIT"))
        mesh_id = None
        if track.startswith("mesh "):
            try:
                mesh_id = int(track.split()[1])
            except ValueError:
                pass
        out[track] = MeshBubbles(
            mesh=track, envelope_us=envelope_us, busy_us=busy,
            warmup_us=warmup, steady_idle_us=steady, drain_us=drain,
            n_ops=len(group), stream_wait_us=wait_us,
            sched_warmup_ticks=(sched_first.get(mesh_id)
                                if num_clock is not None and
                                mesh_id is not None else None),
            sched_drain_ticks=(num_clock - 1 - sched_last[mesh_id]
                               if num_clock is not None and
                               mesh_id in sched_last else None),
            sched_num_clock=num_clock)
    return out


def _upstream_runs(ops: Sequence[TimedOp], causal: Dict[int, set]
                   ) -> Dict[int, List[int]]:
    """For every RUN op, the RUN ops on OTHER meshes it cannot start
    before: its causal predecessors, followed back through the ops that
    run no program (the RESHARD that carries a value, its LAUNCH and WAIT,
    a FREE) and no further than the first RUN on each way."""
    out = {}
    for o in ops:
        if not _is_run(o):
            continue
        found, seen, stack = [], set(), list(causal.get(o.idx, ()))
        while stack:
            j = stack.pop()
            if j in seen:
                continue
            seen.add(j)
            if _is_run(ops[j]):
                if ops[j].track != o.track:
                    found.append(j)
            else:
                stack.extend(causal.get(j, ()))
        out[o.idx] = found
    return out


def _by_shortest_span(spans: Sequence[Tuple[str, float, float]],
                      lo: float, hi: float) -> Dict[str, float]:
    """{name: microseconds} of [lo, hi]: each instant under the shortest
    of ``spans`` (name, start, end) open then, of two as short the first in
    the alphabet (the rule of the benchmark's ``idle_gaps``); what no span
    covers under ``unattributed``."""
    open_ = [(e - s, n, s, e) for n, s, e in spans if s < hi and e > lo]
    cuts = sorted({lo, hi, *(t for _, _, s, e in open_ for t in (s, e)
                             if lo < t < hi)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        name = min(((d, n) for d, n, s, e in open_ if s <= a and e >= b),
                   default=(0.0, "unattributed"))[1]
        out[name] = out.get(name, 0.0) + b - a
    return out


def _covered_us(intervals, lo: float, hi: float) -> float:
    """Microseconds of [lo, hi] that some (start, end) of ``intervals``
    covers."""
    total, at = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total


def _collective_time(events: Sequence[tuple], lo: float, hi: float
                     ) -> Tuple[float, float]:
    """(exposed, hidden) microseconds of [lo, hi] on one chip, from its
    collective events (instruction, start, end) in order of start.
    Exposed: the op line has a collective open, so the core does nothing
    else (a synchronous ``all-gather``, a ``-done`` that waits).  Hidden:
    some asynchronous collective is between the end of its ``-start`` and
    the start of its ``-done``, where the op line runs other work.  A
    ``-done`` is paired with the oldest open ``-start`` of its name
    (``async-collective-start.3``, ``async-collective-done.3``), else of
    its kind."""
    pending: List[Tuple[str, str, float]] = []  # (kind, number, -start's end)
    flights = []
    for name, s, e in events:
        half = _device_time.ASYNC_COLLECTIVE.match(name)
        if half is None:
            continue
        kind, which, number = half.groups()
        if which == "start":
            pending.append((kind, number, e))
            continue
        found = next((p for p in pending if p[:2] == (kind, number)),
                     next((p for p in pending if p[0] == kind), None))
        if found is not None:
            pending.remove(found)
            flights.append((found[2], s))
    return (_covered_us([(s, e) for _, s, e in events], lo, hi),
            _covered_us(flights, lo, hi))


def _device_bubbles(joined: JoinedStep, causal: Dict[int, set],
                    schedule=None) -> Dict[str, MeshBubbles]:
    """Per-mesh account of a step joined on the device's clock: a mesh is
    busy while one of its RUN ops' programs runs, and every other instant
    of the envelope goes to one cause.

    The gap before RUN *i* on a mesh (from the end of the mesh's previous
    run, or the envelope's start, to the run's start) is cut at three
    instants, and each stretch goes to the first cause that holds:

    ``boundary``  before the step's ``pipeshard.place-inputs`` returned
                  (the inputs' ``device_put``, the zeroed accumulators,
                  what the host did between two steps);
    ``upstream``  a RUN on ANOTHER mesh that RUN *i* depends on
                  (:func:`_upstream_runs`) has not finished on the device:
                  the pipeline's own dependency (fill, drain, every
                  steady-state bubble);
    ``dispatch``  those have finished and the enqueue of RUN *i* has not
                  returned: the chip waits for the driver
                  (``dispatch_by_span`` says what the driver was in);
    ``edge``      what is left: predecessors done, RUN enqueued, program
                  not started (the cross-mesh move and the launch).

    After a mesh's last run: ``upstream`` until the step's last run on any
    mesh has ended, ``boundary`` from there to the envelope's end."""
    ops, host = joined.ops, joined.host_ops
    t0, t1 = joined.t0_us, joined.t0_us + joined.envelope_us
    upstream = _upstream_runs(ops, causal)
    placed = joined.inputs_placed_us
    last_end = max((o.t1_us for o in ops if _is_run(o)), default=t0)
    host_bubbles = _mesh_bubbles(host, t0, joined.envelope_us, schedule)
    out: Dict[str, MeshBubbles] = {}
    for track, hb in host_bubbles.items():
        runs = sorted((o for o in ops if o.track == track and _is_run(o)),
                      key=lambda o: o.t0_us)
        idle = dict.fromkeys(IDLE_CAUSES, 0.0)
        by_span: Dict[str, float] = {}
        busy, at = 0.0, t0      # ``at``: the envelope is accounted to here
        for o in runs:
            start, end = min(max(o.t0_us, t0), t1), min(o.t1_us, t1)
            if start > at:
                done = max([placed] + [ops[j].t1_us
                                       for j in upstream[o.idx]])
                cuts = [min(max(c, at), start) for c in
                        (placed, done, max(done, host[o.idx].t1_us))]
                idle["boundary"] += cuts[0] - at
                idle["upstream"] += cuts[1] - cuts[0]
                idle["dispatch"] += cuts[2] - cuts[1]
                idle["edge"] += start - cuts[2]
                if cuts[2] > cuts[1]:
                    for name, us in _by_shortest_span(
                            joined.host_spans, cuts[1], cuts[2]).items():
                        by_span[name] = by_span.get(name, 0.0) + us
                at = start
            if end > at:
                busy += end - at
                at = end
        first = min(max(runs[0].t0_us, t0), t1) if runs else t1
        drain_from = at
        if t1 > at:
            cut = min(max(last_end, at), t1)
            idle["upstream"] += cut - at
            idle["boundary"] += t1 - cut
        chips = joined.mesh_chips.get(track, [])
        moving = [_collective_time(joined.collectives.get(c, ()), t0, t1)
                  for c in chips]
        exposed, hidden = (sum(part) / len(chips) for part in zip(*moving)) \
            if moving else (0.0, 0.0)
        warmup, drain = first - t0, t1 - drain_from
        out[track] = dataclasses.replace(
            hb, busy_us=busy, warmup_us=warmup, drain_us=drain,
            steady_idle_us=max(0.0, t1 - t0 - busy - warmup - drain),
            idle_us=idle, dispatch_by_span=by_span, n_chips=len(chips),
            collective_exposed_us=exposed, collective_hidden_us=hidden)
    return out


def _transfer_breakdown(ops: Sequence[TimedOp],
                        pool_spans: Sequence[Dict[str, Any]],
                        run_stats: Optional[Dict[str, Any]] = None
                        ) -> TransferBreakdown:
    wire = sum(s["dur_us"] for s in pool_spans
               if s["name"] == "reshard.wire")
    queue = sum(s["dur_us"] for s in pool_spans
                if s["name"] == "reshard.wait")
    # parent submit→retire spans (the labeled LAUNCH payload spans);
    # reshard.* children and nested resharding-category spans excluded
    parent = sum(s["dur_us"] for s in pool_spans
                 if s["category"] == "transfer" and
                 not s["name"].startswith("reshard."))
    pool_busy = wire if wire > 0 else parent
    if pool_busy == 0 and run_stats:
        pool_busy = run_stats.get("transfer_busy_s", 0.0) * 1e6
    exposed = sum(o.dur_us for o in ops if o.kind == "wait" or
                  o.name.startswith("WAIT"))
    if exposed == 0 and run_stats:
        exposed = run_stats.get("wait_blocked_s", 0.0) * 1e6
    hidden = max(0.0, pool_busy - exposed)
    frac = max(0.0, min(1.0, 1.0 - exposed / pool_busy)) \
        if pool_busy > 0 else 1.0
    return TransferBreakdown(pool_busy_us=pool_busy, wire_us=wire,
                             queue_wait_us=queue,
                             exposed_wait_us=exposed, hidden_us=hidden,
                             overlap_fraction=frac)


def _n_devices(stage_exec) -> int:
    mesh = getattr(stage_exec, "_physical_mesh", None)
    n = getattr(mesh, "num_devices", None)
    if n:
        return int(n)
    jm = getattr(stage_exec, "jax_mesh", None)
    if jm is not None:
        try:
            return int(jm.devices.size)
        except Exception:  # pylint: disable=broad-except
            pass
    return 1


def _stage_mfu(ops: Sequence[TimedOp], stage_execs,
               peak_tflops: Optional[float] = None
               ) -> Dict[str, StageMfu]:
    if not stage_execs:
        return {}
    try:
        peak = peak_tflops if peak_tflops else device_peak_tflops()
    except ValueError:
        # not a TPU in TPU_GENERATION_SPECS and no device_peak_tflops
        # knob: there is no peak, so no MFU is reported
        return {}
    out: Dict[str, StageMfu] = {}
    for ex in stage_execs:
        name = getattr(ex, "name", None)
        if not name:
            continue
        spans = [o for o in ops if o.name == f"RUN {name}"]
        if not spans:
            continue
        t_us = sum(o.dur_us for o in spans)
        try:
            flops = stage_flops(ex.comp.closed_jaxpr())
        except Exception:  # pylint: disable=broad-except
            continue
        ndev = _n_devices(ex)
        tfpc = (flops * len(spans) / (t_us * 1e-6) / ndev / 1e12
                if t_us > 0 else 0.0)
        out[name] = StageMfu(stage=name, flops_per_run=flops,
                             n_runs=len(spans), run_time_us=t_us,
                             n_devices=ndev, peak_tflops=peak,
                             tflops_per_chip=tfpc,
                             mfu=tfpc / peak if peak > 0 else 0.0)
    return out


def build_step_report(joined: JoinedStep, program=None, schedule=None,
                      stage_execs=None, mode: Optional[str] = None,
                      run_stats: Optional[Dict[str, Any]] = None,
                      peak_tflops: Optional[float] = None
                      ) -> StepPerfReport:
    """Assemble the StepPerfReport from a joined step.

    ``program`` (when its hooks aligned) contributes the dataflow
    edges; without it the walk rides track order + issue order only.
    ``schedule`` keys the warmup/drain bubble expectation;
    ``stage_execs`` enable MFU attribution."""
    ops = joined.ops
    notes: List[str] = list(joined.notes)
    causal: Dict[int, set] = {}
    if joined.aligned and program is not None and \
            program.graph is not None:
        causal, sim_preds = _op_dependencies(program, len(ops))
    else:
        if program is not None and not joined.aligned:
            notes.append("spans did not align 1:1 with the lowered "
                         "program; dataflow edges unavailable "
                         "(track-order analysis)")
        sim_preds = [set() for _ in ops]
        last_on_track: Dict[str, int] = {}
        for i, o in enumerate(ops):
            p = last_on_track.get(o.track)
            if p is not None:
                sim_preds[i].add(p)
            last_on_track[o.track] = i
    cp = measured_critical_path(ops, causal,
                                envelope_us=joined.envelope_us)
    if joined.source == "device":
        bubbles = _device_bubbles(joined, causal, schedule)
    else:
        bubbles = _mesh_bubbles(ops, joined.t0_us, joined.envelope_us,
                                schedule)
    transfers = _transfer_breakdown(ops, joined.pool_spans, run_stats)
    stages = _stage_mfu(ops, stage_execs, peak_tflops)
    return StepPerfReport(
        source=joined.source, mode=mode, envelope_us=joined.envelope_us,
        n_ops=len(ops), aligned=joined.aligned, critical_path=cp,
        bubbles=bubbles, transfers=transfers, stages=stages,
        notes=notes,
        sim_durs_us=[o.dur_us for o in ops],
        sim_preds=[tuple(sorted(p)) for p in sim_preds],
        sim_ops=list(ops))


########################################
# the account a capture gives with no executable at hand
########################################


def pipeline_time(capture, pipelines: Sequence[Dict[str, Any]]
                  ) -> Dict[str, Dict[str, Any]]:
    """What :meth:`Capture.pipeline_time` returns: by mesh, seconds summed
    over the traced steps that joined on the device's clock.
    ``pipelines`` is what the capture kept of each pipeshard executable
    that had lowered its program when it stopped
    (``device_time.register_pipeline``): ``{"program", "mesh_chips",
    "run_programs"}`` as :func:`joined_from_capture` takes them."""
    out: Dict[str, Dict[str, Any]] = {}
    for kept in pipelines:
        program = kept["program"]
        for joined in joined_from_capture(capture, program,
                                          kept["mesh_chips"],
                                          kept["run_programs"]):
            if joined.source != "device" or program.graph is None:
                continue
            causal, _ = _op_dependencies(program, len(joined.ops))
            for track, b in _device_bubbles(joined, causal).items():
                acc = out.setdefault(track, {
                    "chips": b.n_chips, "envelope_s": 0.0, "busy_s": 0.0,
                    **{f"{c}_s": 0.0 for c in IDLE_CAUSES},
                    "collective_exposed_s": 0.0,
                    "collective_hidden_s": 0.0, "dispatch_by_span": {}})
                acc["envelope_s"] += b.envelope_us / 1e6
                acc["busy_s"] += b.busy_us / 1e6
                for c in IDLE_CAUSES:
                    acc[f"{c}_s"] += b.idle_us[c] / 1e6
                acc["collective_exposed_s"] += b.collective_exposed_us / 1e6
                acc["collective_hidden_s"] += b.collective_hidden_us / 1e6
                for name, us in b.dispatch_by_span.items():
                    acc["dispatch_by_span"][name] = \
                        acc["dispatch_by_span"].get(name, 0.0) + us / 1e6
    return out


########################################
# raw Chrome-trace entry point (scripts/perf_tool.py)
########################################


def spans_from_chrome(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Reconstruct completed spans (name/category/ts_us/dur_us/track)
    from Chrome-trace B/E pairs, joining the ``M`` thread_name records
    so per-track identity survives the round trip."""
    track_of: Dict[Tuple[int, int], str] = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            track_of[(e.get("pid", 0), e["tid"])] = e["args"]["name"]
    stacks: Dict[Tuple[int, int], List[Dict[str, Any]]] = \
        collections.defaultdict(list)
    spans: List[Dict[str, Any]] = []
    events = sorted(
        (e for e in trace.get("traceEvents", [])
         if e.get("ph") in ("B", "E")),
        key=lambda e: (e["ts"], 0 if e["ph"] == "E" else 1))
    for e in events:
        key = (e.get("pid", 0), e["tid"])
        if e["ph"] == "B":
            stacks[key].append(e)
        elif stacks[key]:
            b = stacks[key].pop()
            spans.append({
                "name": b["name"],
                "category": b.get("cat", ""),
                "ts_us": b["ts"],
                "dur_us": e["ts"] - b["ts"],
                "track": track_of.get(key, f"tid {key[1]}"),
                "args": b.get("args"),
            })
    spans.sort(key=lambda s: s["ts_us"])
    return spans


def format_alias_stats(program: str, stats: Dict[str, int]) -> str:
    """One line of ``solver.alias_stats`` for a report."""
    return (f"{program}: {stats['alias_pairs']} donated pairs, "
            f"{stats['alias_sharded']} sharded, "
            f"{stats['alias_reshard_bytes']} B a run to bring the outputs "
            "to their inputs' specs")


def format_plan_stats(program: str, stats: Dict[str, int]) -> List[str]:
    """A program's plan counters as lines of a report: what the planner
    did with its donated pairs (``solver.alias_stats``), with the inputs
    it was handed as given (``solver.given_stats``), and how many of its
    shardings unification then took from the plan, whichever it has."""
    lines = []
    if "alias_pairs" in stats:
        lines.append(format_alias_stats(program, stats))
    if "given_in" in stats:
        line = (f"{program}: {stats['given_in']} inputs given, "
                f"{stats['given_sharded']} sharded, "
                f"{stats['given_reshard_bytes']} B a run to re-lay them "
                "out for their readers")
        if "unify_overrides" in stats:
            line += (f"; {stats['unify_overrides']} shardings moved from "
                     "the plan by unification")
        lines.append(line)
    return lines


_PLAN_STAT_KEYS = ("alias_pairs", "alias_sharded", "alias_reshard_bytes",
                   "given_in", "given_sharded", "given_reshard_bytes")


def donated_from_spans(spans: Sequence[Dict[str, Any]]
                       ) -> Dict[str, Dict[str, int]]:
    """``StepPerfReport.donated`` from the planner's spans (``ilp-solve``
    or, for a plan replayed from the cache, ``ilp-cache-replay``) and the
    driver's ``unify-shardings``."""
    found: Dict[str, Dict[str, int]] = {}
    for s in spans:
        args = s.get("args") or {}
        if s["name"] in ("ilp-solve", "ilp-cache-replay") and \
                args.get("stage"):
            found[args["stage"]] = {k: int(args[k]) for k in _PLAN_STAT_KEYS
                                    if k in args}
    for s in spans:
        if s["name"] == "unify-shardings":
            for program, n in ((s.get("args") or {})
                               .get("unify_overrides") or {}).items():
                if program in found:
                    found[program]["unify_overrides"] = int(n)
    return found


def report_from_trace(trace: Dict[str, Any],
                      peak_tflops: Optional[float] = None
                      ) -> Optional[StepPerfReport]:
    """Analyze a saved Chrome trace (no program/graph available —
    track-order analysis of the last ``pipeshard.step`` envelope)."""
    spans = spans_from_chrome(trace)
    joined = _join_spans(spans, None)
    if joined is None:
        return None
    report = build_step_report(joined, peak_tflops=peak_tflops)
    report.donated = donated_from_spans(spans)
    return report


########################################
# registry gauges (ISSUE 9 metric families)
########################################

_PERF_REG = _tmetrics.get_registry()
_STAGE_MFU_GAUGE = _PERF_REG.gauge(
    "alpa_stage_mfu",
    "Last analyzed step's model-FLOPs utilization per pipeline stage",
    labelnames=("stage",))
_BUBBLE_GAUGE = _PERF_REG.gauge(
    "alpa_step_bubble_fraction",
    "Last analyzed step's per-mesh idle fraction of the step envelope",
    labelnames=("mesh",))
_IDLE_GAUGE = _PERF_REG.gauge(
    "alpa_step_idle_seconds",
    "Last analyzed step's idle seconds per mesh by cause (boundary, "
    "upstream, dispatch, edge), from a capture's device events",
    labelnames=("mesh", "cause"))
_CRITICAL_PATH_GAUGE = _PERF_REG.gauge(
    "alpa_critical_path_us",
    "Last analyzed step's measured critical-path op time")
_GATE_TOTAL = _PERF_REG.counter(
    "alpa_perf_gate_total",
    "Perf regression gate verdicts (benchmark/perf_gate.py)",
    labelnames=("result",))


def publish_report(report: StepPerfReport) -> None:
    """Fold one report into the central registry (GET /metrics)."""
    _CRITICAL_PATH_GAUGE.set(report.critical_path.total_us)
    for track, b in report.bubbles.items():
        label = track.split()[1] if track.startswith("mesh ") else track
        _BUBBLE_GAUGE.labels(label).set(b.bubble_fraction)
        for cause, us in (b.idle_us or {}).items():
            _IDLE_GAUGE.labels(label, cause).set(us / 1e6)
    for name, s in report.stages.items():
        _STAGE_MFU_GAUGE.labels(name).set(s.mfu)


def record_gate_verdict(passed: bool) -> None:
    _GATE_TOTAL.labels("pass" if passed else "fail").inc()
