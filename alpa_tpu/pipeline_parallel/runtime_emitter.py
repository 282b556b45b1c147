"""Pipeline instruction emitter.

Analog of ref ``alpa/pipeline_parallel/runtime_emitter.py`` (SURVEY.md
§2.4): walk the schedule tick by tick and compile it into a static
instruction list.  Single-controller simplifications vs the reference:

* ``SEND``/``RECV``/``BROADCAST`` collapse into one ``RESHARD`` instruction
  executed as ``jax.device_put`` (the jax runtime moves data between meshes
  over ICI/DCN; ref cross_mesh_resharding's NCCL P2P machinery becomes the
  runtime's transfer engine).
* There is one global instruction stream instead of per-host worker
  streams; cross-mesh overlap is explicit (ISSUE 4): the lowering builds
  an instruction-level dataflow graph over register slots and the
  ``overlap`` dispatch mode replays it with cross-mesh RESHARDs launched
  eagerly on a transfer pool the moment their producers retire, bounded
  by an in-flight window (jax's async dispatch remains the fallback
  overlap story for the interpreter modes).
* ``FREE`` is emitted from liveness analysis like the reference
  (``_compile_free``, ref runtime_emitter.py:1087) and drops env references
  so buffers are reclaimed promptly.

Value identity: (var, instance) where instance = microbatch index for
per-microbatch values and -1 for microbatch-invariant ones (params, grad
accumulators, apply-grad results).
"""
import collections
import dataclasses
import enum
import heapq
import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.extend.core import Var

from alpa_tpu import fault as _fault
from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import flight as _flight
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry import trace as _ttrace
from alpa_tpu.util import aval_bytes

logger = logging.getLogger(__name__)


class PipelineInstType(enum.IntEnum):
    """(ref runtime_emitter.py:31)"""
    RUN = 0
    RESHARD = 1
    FREE = 2


@dataclasses.dataclass
class PipelineInstruction:
    """(ref runtime_emitter.py:47)"""
    opcode: PipelineInstType
    # RUN
    stage_id: Optional[int] = None
    micro_batch: Optional[int] = None
    input_keys: Optional[List[Tuple[int, int]]] = None   # (var_id, inst)
    output_keys: Optional[List[Tuple[int, int]]] = None
    # RESHARD
    var_key: Optional[Tuple[int, int]] = None
    src_mesh: Optional[int] = None
    dst_mesh: Optional[int] = None
    dst_sharding: Any = None
    # the source-side sharding the plan was built against — kept so a
    # profile-guided replan (ISSUE 12) can re-price and re-plan the edge
    # without re-deriving the emitter's sharding environment
    src_sharding: Any = None
    # tile-level transfer plan (cross_mesh_resharding.ReshardingTaskSpec)
    plan: Any = None
    # cached executor for planned execution mode
    task: Any = None
    # FREE
    free_keys: Optional[List[Tuple[int, int, int]]] = None  # (var,inst,mesh)
    info: str = ""

    def __repr__(self):
        if self.opcode == PipelineInstType.RUN:
            return (f"RUN(stage={self.stage_id}, mb={self.micro_batch})")
        if self.opcode == PipelineInstType.RESHARD:
            return (f"RESHARD({self.var_key}, {self.src_mesh}->"
                    f"{self.dst_mesh})")
        return f"FREE({len(self.free_keys)})"


@dataclasses.dataclass
class PlacementSpecEntry:
    """Where a global input lives: list of (mesh_id, sharding)."""
    mesh_ids: List[int]
    shardings: List[Any]
    is_batch: bool = False


@dataclasses.dataclass
class PipeshardConfig:
    """The full compiled artifact (ref runtime_emitter.py:228)."""
    instructions: List[PipelineInstruction]
    # global invar index -> placement
    input_placements: List[PlacementSpecEntry]
    # accumulator allocations: (var_id, mesh_id, aval, sharding)
    acc_allocs: List[Tuple[int, int, Any, Any]]
    # flat output -> (var_id, inst, mesh_id)
    output_specs: List[Tuple[int, int, int]]
    num_micro_batches: int
    num_meshes: int
    var_ids: Dict[Var, int]
    # (var_id, inst) -> producing mesh (for debugging)
    schedule_text: str = ""


@dataclasses.dataclass
class InstructionStreams:
    """Per-mesh instruction streams with cross-stream dependencies — the
    single-controller analog of the reference's pre-pushed per-worker
    instruction lists (ref runtime_emitter.py:258 PipelineInstEmitter ->
    per-worker lists; pipeshard_executable.py:489 execute_on_worker).

    ``streams[m]`` is the ordered list of global instruction indices mesh
    ``m``'s worker executes; ``deps[i]`` is the set of global indices in
    OTHER streams instruction ``i`` must wait for.  Dependencies cover
    read-after-write (a consumer waits for its producer), plus
    write/kill-after-read anti-dependencies (donating or freeing a buffer
    waits for every earlier reader) — all edges point to earlier global
    indices, so stream workers that execute in-stream in order can never
    deadlock.
    """
    streams: List[List[int]]
    deps: Dict[int, set]
    stream_of: Dict[int, int]
    # per-(src, dst)-mesh FIFO channel metadata: edge -> the cross-mesh
    # RESHARD indices that travel it, in emission (= send) order.  The
    # ISSUE-13 model checker binds its SEND/RECV micro-ops to these
    # channels (carried on PlanModel.channels).
    channels: Dict[Tuple[int, int], List[int]] = dataclasses.field(
        default_factory=dict)


def instructions_independent(a, b) -> bool:
    """True when two instructions commute: no value key is touched by
    both with at least one side writing/killing it.  The model checker
    (and any reordering optimization) may swap independent ops without
    changing program meaning."""
    acc_a = instruction_accesses(a)
    acc_b = instruction_accesses(b)
    keys_b: Dict[Tuple[int, int, int], str] = {}
    for key, kind in acc_b:
        if keys_b.get(key) != "write" and keys_b.get(key) != "kill":
            keys_b[key] = kind
    for key, kind in acc_a:
        other = keys_b.get(key)
        if other is None:
            continue
        if kind != "read" or other != "read":
            return False
    return True


def instruction_accesses(inst) -> List[Tuple[Tuple[int, int, int], str]]:
    """The (value key, access kind) pairs one instruction touches —
    kind "read" | "write" | "kill" (donation or FREE).  Shared by the
    stream partitioner (dependency edges) and the dispatch race checker
    (runtime conflict detection)."""
    acc = []
    if inst.opcode == PipelineInstType.RUN:
        ex = getattr(inst, "executable", None)
        donated = set(getattr(ex, "donate_idx", ()) or ())
        for pos, k in enumerate(inst.input_keys):
            kind = "kill" if pos in donated else "read"
            acc.append(((k[0], k[1], inst.dst_mesh), kind))
        for k in inst.output_keys:
            acc.append(((k[0], k[1], inst.dst_mesh), "write"))
    elif inst.opcode == PipelineInstType.RESHARD:
        acc.append(
            ((inst.var_key[0], inst.var_key[1], inst.src_mesh), "read"))
        acc.append(
            ((inst.var_key[0], inst.var_key[1], inst.dst_mesh), "write"))
    else:  # FREE
        for key in inst.free_keys:
            acc.append((tuple(key), "kill"))
    return acc


def partition_streams(instructions: List[PipelineInstruction],
                      num_meshes: int) -> InstructionStreams:
    """Split the global instruction list into per-mesh streams.

    Assignment: RUN executes on its ``dst_mesh``; RESHARD on its
    ``dst_mesh`` (the destination initiates the pull, matching the jax
    transfer model); FREE follows the stream of the preceding
    instruction — its last user, since emit_free_instructions places
    each FREE immediately after the last use (stream 0 if the list
    starts with a FREE).
    """
    streams: List[List[int]] = [[] for _ in range(num_meshes)]
    stream_of: Dict[int, int] = {}
    deps: Dict[int, set] = {}
    channels: Dict[Tuple[int, int], List[int]] = {}
    # key -> ordered access history: (global_idx, stream, kind)
    history: Dict[Tuple[int, int, int], List[Tuple[int, int, str]]] = {}

    prev_stream = 0
    for i, inst in enumerate(instructions):
        if inst.opcode == PipelineInstType.RUN:
            m = inst.dst_mesh
        elif inst.opcode == PipelineInstType.RESHARD:
            m = inst.dst_mesh
            if inst.src_mesh != inst.dst_mesh:
                channels.setdefault(
                    (inst.src_mesh, inst.dst_mesh), []).append(i)
        else:
            m = prev_stream
        m = m if 0 <= m < num_meshes else 0
        streams[m].append(i)
        stream_of[i] = m
        prev_stream = m

        d = set()
        for key, kind in instruction_accesses(inst):
            hist = history.setdefault(key, [])
            if kind == "read":
                # wait for the latest write from another stream
                for j, sm, k in reversed(hist):
                    if k in ("write", "kill"):
                        if sm != m:
                            d.add(j)
                        break
            else:  # write or kill: wait for every earlier access
                for j, sm, k in hist:
                    if sm != m:
                        d.add(j)
            hist.append((i, m, kind))
        if d:
            deps[i] = d
    return InstructionStreams(streams=streams, deps=deps,
                              stream_of=stream_of, channels=channels)


class DispatchRaceChecker:
    """Runtime race detector for threaded per-mesh dispatch (SURVEY §5
    race detection — a capability the reference does not have).

    With ``global_config.debug_dispatch_races`` on, every worker reports
    its instruction's value accesses before executing and withdraws them
    after.  Two accesses CONFLICT when they touch the same (var,
    microbatch, mesh) key from different streams and at least one is a
    write or kill (donation/FREE).  A conflict observed live means the
    partitioner's dependency edges failed to serialize the pair — the
    exact bug class that would otherwise surface as silent numeric
    corruption or a use-after-donate crash far from its cause.
    """

    def __init__(self, instructions, stream_of):
        import threading
        self._stream_of = stream_of
        # instructions and streams are fixed for the executable's
        # lifetime: extract every access list once, not per step
        self._accs = [instruction_accesses(i) for i in instructions]
        self._lock = threading.Lock()
        # key -> {idx: kind} of instructions currently executing
        self._active: Dict[Tuple, Dict[int, str]] = {}
        self.violations: List[str] = []

    @staticmethod
    def _conflict(a: str, b: str) -> bool:
        return a != "read" or b != "read"

    def begin(self, idx: int):
        accs = self._accs[idx]
        me = self._stream_of[idx]
        with self._lock:
            for key, kind in accs:
                holders = self._active.setdefault(key, {})
                for other, okind in holders.items():
                    if self._stream_of[other] != me and \
                            self._conflict(kind, okind):
                        self.violations.append(
                            f"inst {idx} ({kind} {key}) raced inst "
                            f"{other} ({okind}) across streams "
                            f"{me}/{self._stream_of[other]}")
                holders[idx] = kind
        return accs

    def end(self, idx: int, accs):
        with self._lock:
            for key, _ in accs:
                holders = self._active.get(key)
                if holders is not None:
                    holders.pop(idx, None)
                    if not holders:
                        self._active.pop(key, None)

    def reset(self):
        """Clear violations AND in-flight accesses (an aborted launch can
        leave registrations behind); call at the start of every launch."""
        with self._lock:
            self._active = {}
            self.violations = []

    def check(self):
        if self.violations:
            raise RuntimeError(
                "threaded dispatch raced (stream dependency edges failed "
                "to serialize conflicting accesses):\n  " +
                "\n  ".join(self.violations[:10]))


########################################
# instruction dataflow graph (ISSUE 4 tentpole)
########################################


@dataclasses.dataclass
class DataflowNode:
    """One lowered instruction's register-slot footprint."""
    idx: int                            # flat (emitted) instruction index
    kind: str                           # "RUN" | "RESHARD" | "FREE"
    reads: Tuple[int, ...] = ()
    writes: Tuple[int, ...] = ()
    kills: Tuple[int, ...] = ()         # donation / FREE targets
    edge: Optional[Tuple[int, int]] = None  # RESHARD (src_mesh, dst_mesh)
    cross_mesh: bool = False
    info: str = ""


@dataclasses.dataclass
class InstructionDataflowGraph:
    """Explicit producer/consumer edges over register slots, built at
    lowering time for every instruction (ISSUE 4).

    Edge kinds:

    * RAW — a reader depends on the last writer of every slot it reads.
    * WAW/WAR and kill — a writer or killer depends on the previous
      writer AND on every reader since.  Donation (``donate_argnums`` on
      accumulator inputs) and FREE are kills: they invalidate the buffer,
      so an eagerly launched transfer reading the slot must retire before
      the donating RUN or the FREE executes.

    Every edge points to an earlier flat index, so any replay that
    respects ``preds`` is deadlock-free by construction.  The ``overlap``
    dispatch mode replays this graph instead of the flat list; the fuzz
    test in tests/runtime/test_overlap_dispatch.py drives randomized
    topologies through :func:`schedule_overlap` and checks the replay
    invariants directly.
    """
    nodes: List[DataflowNode]
    preds: List[Tuple[int, ...]]
    succs: List[Tuple[int, ...]]

    @classmethod
    def build(cls, nodes: Sequence[DataflowNode]
              ) -> "InstructionDataflowGraph":
        last_writer: Dict[int, int] = {}
        readers_since: Dict[int, List[int]] = {}
        preds: List[set] = [set() for _ in nodes]
        for node in nodes:
            i = node.idx
            for s in node.reads:
                w = last_writer.get(s)
                if w is not None and w != i:
                    preds[i].add(w)
                readers_since.setdefault(s, []).append(i)
            for s in tuple(node.writes) + tuple(node.kills):
                w = last_writer.get(s)
                if w is not None and w != i:
                    preds[i].add(w)
                for r in readers_since.get(s, ()):
                    if r != i:
                        preds[i].add(r)
                readers_since[s] = []
                last_writer[s] = i
        succs: List[set] = [set() for _ in nodes]
        for i, ps in enumerate(preds):
            for p in ps:
                succs[p].add(i)
        return cls(list(nodes),
                   [tuple(sorted(p)) for p in preds],
                   [tuple(sorted(s)) for s in succs])

    @property
    def n_cross_mesh(self) -> int:
        return sum(1 for n_ in self.nodes if n_.cross_mesh)

    def check(self) -> None:
        """Static lowering-time hazard pass (ISSUE 6): independently
        re-derive every slot hazard with a forward walk and assert the
        graph's ``preds`` cover it.  Runs on EVERY compile (called by
        :func:`lower_to_register_file`), not just under debug — it is
        O(edges) over an in-memory list, so the cost is lowering noise.

        Catches the bug class where an edit to :meth:`build`, the
        lowering, or a hand-constructed graph drops a dependency edge:
        a reader without an edge to its slot's last writer (RAW), a
        writer/killer without edges to the previous writer (WAW) or to
        readers since (write-after-read on a live slot), a FREE/kill of
        a cross-mesh transfer destination with no edge to the transfer
        (the in-flight-FREE hazard the overlap replay relies on), a
        forward-pointing edge (deadlock risk), or a node list whose
        positions disagree with node indices.

        Also validates RESHARD node structure (ISSUE 8): every RESHARD
        node must carry its mesh edge, read exactly one slot and write
        exactly one slot (grouped/coalesced transfers batch at the OP
        level — each dataflow node keeps its single-edge footprint, so
        the plan verifier can reconstruct group footprints as member
        unions), and its ``cross_mesh`` flag must agree with the edge.
        """
        nodes = self.nodes
        problems: List[str] = []
        for i, node in enumerate(nodes):
            if node.idx != i:
                problems.append(
                    f"node at position {i} carries idx {node.idx}")
            if node.kind == "RESHARD":
                if node.edge is None:
                    problems.append(
                        f"RESHARD node {i} carries no mesh edge")
                elif node.cross_mesh != (node.edge[0] != node.edge[1]):
                    problems.append(
                        f"RESHARD node {i} cross_mesh={node.cross_mesh}"
                        f" disagrees with edge {node.edge}")
                if len(node.reads) != 1 or len(node.writes) != 1:
                    problems.append(
                        f"RESHARD node {i} must read/write exactly one "
                        f"slot each, has reads={node.reads} "
                        f"writes={node.writes}")
        last_writer: Dict[int, int] = {}
        readers_since: Dict[int, List[int]] = {}
        for node in nodes:
            if len(problems) > 20:
                break
            i = node.idx
            preds = set(self.preds[i]) if i < len(self.preds) else set()
            for p in preds:
                if p >= i:
                    problems.append(
                        f"node {i} ({node.kind}) has a non-backward "
                        f"edge to node {p}")
            for s in node.reads:
                w = last_writer.get(s)
                if w is not None and w != i and w not in preds:
                    problems.append(
                        f"RAW hazard: node {i} ({node.kind}) reads slot "
                        f"{s} with no edge to its writer, node {w}")
                readers_since.setdefault(s, []).append(i)
            for s in tuple(node.writes) + tuple(node.kills):
                kill = s in node.kills
                verb = "kills" if kill else "writes"
                w = last_writer.get(s)
                if w is not None and w != i and w not in preds:
                    if kill and nodes[w].cross_mesh:
                        problems.append(
                            f"FREE of an in-flight transfer destination:"
                            f" node {i} ({node.kind}) kills slot {s} "
                            f"with no edge to cross-mesh transfer node "
                            f"{w}")
                    else:
                        problems.append(
                            f"WAW hazard: node {i} ({node.kind}) {verb} "
                            f"slot {s} with no edge to its previous "
                            f"writer, node {w}")
                for r in readers_since.get(s, ()):
                    if r != i and r not in preds:
                        problems.append(
                            f"write-after-read on a live slot: node {i} "
                            f"({node.kind}) {verb} slot {s} with no "
                            f"edge to its reader, node {r}")
                readers_since[s] = []
                last_writer[s] = i
        if problems:
            raise RuntimeError(
                "instruction dataflow graph failed the static hazard "
                "check (a dependency edge is missing or malformed):\n  "
                + "\n  ".join(problems[:20]))


def schedule_overlap(graph: InstructionDataflowGraph, window: int
                     ) -> Tuple[List[Tuple[str, int]], int]:
    """Greedy overlap schedule: replay the dataflow graph with cross-mesh
    RESHARDs hoisted and launched eagerly the moment their producers
    retire, bounded by an in-flight-transfer ``window`` (caps host/staging
    memory: at most ``window`` launched-but-unwaited transfers exist).

    Returns ``(plan, n_hoisted)`` where ``plan`` is a list of
    ``("exec" | "launch" | "wait", node_idx)`` issue steps and
    ``n_hoisted`` counts transfers launched before their flat position.

    Invariants (held by construction, asserted by the fuzz test):

    * every node appears exactly once as exec or launch, and every
      launch has exactly one later wait;
    * a node issues only after ALL its graph predecessors retired
      (exec'd, or waited for transfers) — no op reads a slot before its
      producer transfer lands, and no donation/FREE fires while a
      transfer still uses the slot;
    * non-transfer ops keep their flat relative order, so the schedule
      is the flat order with transfers slid earlier (launch) and their
      completion points slid as late as the first dependent allows;
    * at most ``window`` transfers are in flight at any step.
    """
    nodes = graph.nodes
    n = len(nodes)
    window = max(1, int(window))
    unmet = [len(graph.preds[i]) for i in range(n)]
    issued = [False] * n
    retired = [False] * n
    inflight: List[int] = []            # launch order (FIFO)
    ready: List[int] = []               # min-heap of launchable transfers
    plan: List[Tuple[str, int]] = []
    n_hoisted = 0

    def retire(i):
        retired[i] = True
        for s in graph.succs[i]:
            unmet[s] -= 1
            if unmet[s] == 0 and nodes[s].cross_mesh and not issued[s]:
                heapq.heappush(ready, s)

    def wait(i):
        plan.append(("wait", i))
        inflight.remove(i)
        retire(i)

    def launch(i, cur):
        nonlocal n_hoisted
        plan.append(("launch", i))
        issued[i] = True
        inflight.append(i)
        if i > cur:
            n_hoisted += 1

    def pump(cur):
        while ready and len(inflight) < window:
            i = heapq.heappop(ready)
            if not issued[i]:
                launch(i, cur)

    for i in range(n):
        if unmet[i] == 0 and nodes[i].cross_mesh:
            heapq.heappush(ready, i)
    pump(-1)

    for cur in range(n):
        node = nodes[cur]
        if node.cross_mesh:
            if not issued[cur]:
                # make room, then settle any in-flight transfer this one
                # chains on (e.g. multi-hop reshard of the same value)
                while len(inflight) >= window:
                    wait(inflight[0])
                for p in graph.preds[cur]:
                    if not retired[p]:
                        wait(p)
                launch(cur, cur)
            pump(cur)
            continue
        # non-transfer op: settle exactly the transfers it depends on
        for p in graph.preds[cur]:
            if not retired[p]:
                wait(p)
        plan.append(("exec", cur))
        issued[cur] = True
        retire(cur)
        pump(cur)
    while inflight:
        wait(inflight[0])
    return plan, n_hoisted


########################################
# register-file lowering (replay fast path)
########################################


def _equiv_shardings(s1, s2, ndim) -> bool:
    if s1 is None or s2 is None:
        return True
    try:
        return s1.is_equivalent_to(s2, ndim)
    except Exception:  # pylint: disable=broad-except
        return s1 == s2


########################################
# per-node hook points (ISSUE 6 tentpole)
########################################


@dataclasses.dataclass(frozen=True)
class OpHook:
    """One op's hook point, compiled into the replay plan at lowering
    time (ISSUE 6).  The hook is pure metadata: which dataflow node the
    op replays, its slot footprint, and which fault site the
    interpreter would have fired for it.  At execute time, when any
    instrumentation is active, :meth:`RegisterFileProgram.execute`
    compiles a wrapped op list from these — tracing spans, flight
    recorder events, slot-hazard assertions, fault-site checks — and
    replays that; with everything off the raw closures run with zero
    added branches.

    A batched group op carries the union slot footprint and one fault
    info dict per member, so FaultSpec hit counts match the
    interpreter's per-instruction fires exactly.
    """
    kind: str                             # "exec" | "launch" | "wait"
    name: str                             # span/event label
    node: int                             # dataflow node idx (group: first)
    mesh: int
    reads: Tuple[int, ...] = ()
    writes: Tuple[int, ...] = ()
    kills: Tuple[int, ...] = ()
    slots: Tuple[int, ...] = ()           # union footprint (flight events)
    fault_site: Optional[str] = None      # fault.py site name
    fault_infos: Tuple[Any, ...] = ()     # one info dict per member
    idempotent: bool = True               # retry semantics (donation)
    # RUN eqn-classification facts (ISSUE 14 numerics certification):
    # matmul/reduce/cast counts + narrowest accumulation dtype
    precision: Optional[Any] = None
    # RUN stage-decomposition facts (ISSUE 15 translation validation):
    # {"stage": sig, "mb": int, "donate": [pos...], "acc": {out: in}}
    equiv: Optional[Any] = None
    # flat instruction indices this op replays: (idx,) for singletons,
    # every folded member for batched groups — the plan verifier
    # (ISSUE 8) checks the footprint above equals the union of the
    # members' dataflow-node footprints
    members: Tuple[int, ...] = ()


class SlotHazardChecker:
    """Graph-node flavor of the dispatch race checker (ISSUE 6): the
    threaded interpreter's :class:`DispatchRaceChecker` validates
    cross-stream value accesses; this validates the register/overlap
    replay's slot accesses against in-flight transfers at replay time.

    The overlap schedule promises that between a transfer's launch and
    its wait, nothing reads the destination slot, writes either
    endpoint slot, or donates/frees them.  With
    ``global_config.debug_dispatch_races`` on, every op's hook reports
    its footprint here; a violation means the dataflow graph or the
    scheduler failed to serialize the pair — the bug class that would
    otherwise surface as a torn read of a ``_PendingTransfer`` or a
    use-after-free far from its cause.  Driver-thread only (hooks run
    on the dispatch thread), so no lock is needed.
    """

    def __init__(self):
        self._inflight_src: Dict[int, int] = {}   # slot -> launch node
        self._inflight_dst: Dict[int, int] = {}
        self.violations: List[str] = []

    def begin_step(self):
        self._inflight_src.clear()
        self._inflight_dst.clear()
        self.violations = []

    def on_launch(self, hook: OpHook):
        for s in hook.reads:
            self._inflight_src[s] = hook.node
        for s in hook.writes:
            self._inflight_dst[s] = hook.node

    def on_wait(self, hook: OpHook):
        for s in hook.reads:
            self._inflight_src.pop(s, None)
        for s in hook.writes:
            self._inflight_dst.pop(s, None)

    def on_exec(self, hook: OpHook):
        for s in hook.reads:
            n = self._inflight_dst.get(s)
            if n is not None:
                self.violations.append(
                    f"{hook.name} (node {hook.node}) reads slot {s} "
                    f"still owned by in-flight transfer node {n}")
        for s in hook.writes:
            for owners, role in ((self._inflight_src, "source"),
                                 (self._inflight_dst, "destination")):
                n = owners.get(s)
                if n is not None:
                    self.violations.append(
                        f"{hook.name} (node {hook.node}) writes slot "
                        f"{s}, the {role} of in-flight transfer node "
                        f"{n}")
        for s in hook.kills:
            for owners, role in ((self._inflight_src, "source"),
                                 (self._inflight_dst, "destination")):
                n = owners.get(s)
                if n is not None:
                    self.violations.append(
                        f"{hook.name} (node {hook.node}) frees/donates "
                        f"slot {s}, the {role} of in-flight transfer "
                        f"node {n}")

    def check(self):
        if self.violations:
            raise RuntimeError(
                "register/overlap replay raced an in-flight transfer "
                "(graph schedule failed to serialize slot accesses):"
                "\n  " + "\n  ".join(self.violations[:10]))


def _wrap_fault(op, hook: OpHook):
    """Fault-site hook: fire every member's site before the op, retry
    under the site policy — same semantics (and same FaultSpec hit
    counts) as the interpreter's per-instruction wrapping."""
    def wrapped(regs, _op=op, _site=hook.fault_site,
                _infos=hook.fault_infos, _idem=hook.idempotent):
        def attempt():
            for info in _infos:
                _fault.fire(_site, **info)
            _op(regs)
        _fault.call_with_retry(attempt, site=_site, idempotent=_idem)
    return wrapped


def _wrap_hazard(op, hook: OpHook, checker: SlotHazardChecker):
    if hook.kind == "launch":
        def wrapped(regs, _op=op, _h=hook, _c=checker):
            _c.on_launch(_h)
            _op(regs)
    elif hook.kind == "wait":
        def wrapped(regs, _op=op, _h=hook, _c=checker):
            _op(regs)
            _c.on_wait(_h)
    else:
        def wrapped(regs, _op=op, _h=hook, _c=checker):
            _c.on_exec(_h)
            _op(regs)
    return wrapped


def _wrap_flight(op, hook: OpHook, rec):
    """Flight-recorder hook: one ring event per op, outcome included —
    the op's exception (if any) is re-raised after recording."""
    def wrapped(regs, _op=op, _rec=rec.record, _now=_flight.now_us,
                _k=hook.kind, _n=hook.name, _m=hook.mesh,
                _nd=hook.node, _s=hook.slots):
        t0 = _now()
        try:
            _op(regs)
        except BaseException as e:  # noqa: B036 — record, then re-raise
            _rec(_k, _n, _m, _nd, _s, t0, _now(),
                 f"error:{type(e).__name__}")
            raise
        _rec(_k, _n, _m, _nd, _s, t0, _now(), "ok")
    return wrapped


def _wrap_trace(op, name, cat, track, rec):
    def wrapped(regs, _op=op, _span=rec.span, _n=name, _c=cat, _t=track):
        with _span(_n, _c, None, _t):
            _op(regs)
    return wrapped


@dataclasses.dataclass
class RegisterFileProgram:
    """The instruction list lowered to a flat register file (ISSUE 2).

    Replay becomes ``for op in ops: op(regs)`` over ``regs = [None] *
    num_slots``: every ``(var, microbatch, mesh)`` key was resolved to an
    integer slot at build time, RUN inputs/outputs are precomputed index
    tuples closed over each op, FREE is slot clears, and RESHARD carries a
    pre-built :class:`~alpa_tpu.pipeline_parallel.cross_mesh_resharding.
    DirectTransfer` executor (adjacent same-edge transfers coalesced into
    one batched call) — no dict hashing, no sharding resolution, no
    per-call planning on the hot path.
    """
    num_slots: int
    ops: List[Any]                      # each: fn(regs) -> None
    n_instructions: int                 # original instruction count
    by_opcode: Dict[str, int]           # original counts per opcode
    slot_of: Dict[Tuple[Var, int, int], int]
    n_coalesced_groups: int
    n_fixups: int
    text: str                           # one line per op, for fingerprints
    # --- ISSUE 4: dataflow graph + overlap mode ---
    mode: str = "registers"
    graph: Optional[InstructionDataflowGraph] = None
    n_cross_mesh: int = 0               # cross-mesh RESHARDs in the list
    n_hoisted: int = 0                  # transfers launched before flat pos
    n_launches: int = 0                 # async launch ops (groups count 1)
    n_free_hops: int = 0                # FREEs hopped by extended coalescing
    overlap_window: int = 0             # in-flight window (overlap mode)
    run_stats: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"transfer_busy_s": 0.0,
                                 "wait_blocked_s": 0.0})
    # per mesh, one output of each RUN dispatched and not yet known to
    # have finished (``_settle_run_ahead``); the RUN ops hold the queues
    run_ahead: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # telemetry (ISSUE 5): per-op (span name, category, track) built at
    # lowering time; only consulted when tracing is on — the hot replay
    # checks the enabled flag ONCE per step, not per op.
    op_meta: Optional[List[Tuple[str, str, str]]] = None
    # hook points (ISSUE 6): per-op OpHook metadata built at lowering
    # time.  None (synthetic/legacy programs) keeps the pre-hook
    # execute() path byte for byte.
    hooks: Optional[List[OpHook]] = None
    # which hook families ran last step (stats/debugging)
    last_hooks: Tuple[str, ...] = ()
    # static verification verdict (ISSUE 8): attached by
    # lower_to_register_file when global_config.verify_plans != "off";
    # surfaced via dump_debug_info's plan_verdict.txt and
    # PipeshardDriverExecutable.get_plan_verdict()
    verdict: Any = None
    # compiled wrapped-op cache, keyed by the active-hook signature
    _hook_sig: Any = dataclasses.field(default=None, init=False,
                                       repr=False, compare=False)
    _hooked_ops: Optional[List[Any]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    _hazard: Optional[SlotHazardChecker] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def execute(self, regs: List[Any]):
        rs = self.run_stats
        rs["transfer_busy_s"] = 0.0
        rs["wait_blocked_s"] = 0.0
        for queue in self.run_ahead.values():
            queue.clear()       # the last step's: its state may be gone
        if self.hooks is not None:
            sig = self._active_hook_signature()
            if sig is not None:
                self._execute_hooked(regs, sig)
                return
            self.last_hooks = ()
        if _ttrace.enabled():
            self._execute_traced(regs)
            return
        for op in self.ops:
            op(regs)

    def _execute_traced(self, regs: List[Any]):
        meta = self.op_meta
        if meta is None or len(meta) != len(self.ops):
            for op in self.ops:
                op(regs)
            return
        rec = _ttrace.get_recorder()
        for op, (name, cat, track) in zip(self.ops, meta):
            with rec.span(name, cat, None, track):
                op(regs)

    # ---- hook compilation (ISSUE 6) ---------------------------------

    def _active_hook_signature(self):
        """One cheap per-step check deciding whether (and how) the op
        list must be replayed hooked.  None = nothing active: the raw
        closures run with zero added branches, preserving the <2%
        disabled-overhead guard.  Recorder identities are part of the
        signature because tests (and trace_tool record) swap recorders
        mid-process via set_recorder."""
        trace_on = _ttrace.enabled() or global_config.collect_trace
        fault_on = _fault.instrumented()
        race_on = global_config.debug_dispatch_races
        flight_on = _flight.enabled()
        if not (trace_on or fault_on or race_on or flight_on):
            return None
        return (trace_on,
                id(_ttrace.get_recorder()) if trace_on else 0,
                fault_on, race_on, flight_on,
                id(_flight.get_recorder()) if flight_on else 0)

    def _execute_hooked(self, regs: List[Any], sig):
        if sig != self._hook_sig:
            self._hooked_ops, self._hazard = self._compile_hooks(sig)
            self._hook_sig = sig
        self.last_hooks = tuple(
            name for on, name in zip(
                (sig[0], sig[2], sig[3], sig[4]),
                ("trace", "fault", "race", "flight")) if on)
        hz = self._hazard
        if hz is not None:
            hz.begin_step()
        for op in self._hooked_ops:
            op(regs)
        if hz is not None:
            hz.check()

    def _compile_hooks(self, sig):
        """Build the wrapped-op list for the active instrumentation.
        Wrapper nesting, outermost first: trace span > flight event >
        hazard check > fault site — so a fault retry re-fires inside
        one span, and the flight event's outcome reflects the final
        (post-retry) result."""
        trace_on, _tid, fault_on, race_on, flight_on, _fid = sig
        hooks = self.hooks
        if hooks is None or len(hooks) != len(self.ops):
            return list(self.ops), None
        trec = _ttrace.get_recorder() if trace_on else None
        frec = _flight.get_recorder() if flight_on else None
        hazard = SlotHazardChecker() if race_on else None
        meta = self.op_meta
        if meta is None or len(meta) != len(self.ops):
            trace_on, meta = False, None
        wrapped: List[Any] = []
        for i, (op, hook) in enumerate(zip(self.ops, hooks)):
            w = op
            if fault_on and hook.fault_site is not None:
                w = _wrap_fault(w, hook)
            if hazard is not None:
                w = _wrap_hazard(w, hook, hazard)
            if flight_on:
                w = _wrap_flight(w, hook, frec)
            if trace_on:
                name, cat, track = meta[i]
                w = _wrap_trace(w, name, cat, track, trec)
            wrapped.append(w)
        return wrapped, hazard

    def fingerprint(self) -> str:
        import hashlib
        return hashlib.sha256(self.text.encode()).hexdigest()


# RUNs a mesh may have dispatched and not finished when the driver
# dispatches its next one.  Nothing else holds the driver back once no
# cross-mesh edge goes through the host: a program's outputs are
# allocated when it is dispatched, so a driver that runs a whole step
# ahead of the chips holds every micro-batch's buffers at once (the
# four-chip cell's second mesh peaked at 15.3 GB for 8.3, and the driver
# then stood in the allocator with the chips idle; PERF.md §6, PR 46).
# Two keeps one program queued behind the one that runs, which is what
# hides the dispatch.
_RUN_AHEAD = 2


def _settle_run_ahead(queue, mesh=None):
    """Blocks until the oldest of a mesh's dispatched RUNs has finished,
    once ``_RUN_AHEAD`` of them are out.  ``queue`` holds one output of
    each (its smallest); one that a later RUN was given to donate is
    gone, and so is the need to wait for it.  While tracing is on, a wait
    that does block is the span ``pipeshard.run-ahead`` of ``mesh``: the
    driver held by that mesh's queue, whatever else waits for it."""
    if len(queue) >= _RUN_AHEAD:
        token = queue.popleft()
        if not token.is_deleted():
            if _ttrace.enabled() and not token.is_ready():
                with _ttrace.span("pipeshard.run-ahead", "runtime",
                                  {"mesh": mesh}):
                    token.block_until_ready()
            else:
                token.block_until_ready()


def _make_run_op(compiled, in_slots, out_slots, fixups, ahead, token,
                 mesh=None):
    """RUN as a closure: gather args by slot index, call the compiled
    fast path, scatter outputs.  ``fixups`` carries the (rare) arg
    positions whose statically-tracked layout differs from the stage's
    expected sharding — the register-file analog of the interpreter's
    per-arg safety net, resolved at lowering instead of per call.
    ``ahead`` is the mesh's queue of dispatched RUNs
    (:func:`_settle_run_ahead`) and ``token`` the position of the output
    that stands for this one in it (None: the program has no output);
    ``mesh`` names the queue in the span of a wait for it."""
    if fixups:

        def op(regs, _c=compiled, _i=in_slots, _o=out_slots, _f=fixups,
               _q=ahead, _t=token, _m=mesh):
            import jax
            args = [regs[s] for s in _i]
            for pos, sh, ndim in _f:
                a = args[pos]
                if not a.sharding.is_equivalent_to(sh, ndim):
                    args[pos] = jax.device_put(a, sh)
            _settle_run_ahead(_q, _m)
            outs = _c(*args)
            for s, o in zip(_o, outs):
                regs[s] = o
            if _t is not None:
                _q.append(outs[_t])
    else:

        def op(regs, _c=compiled, _i=in_slots, _o=out_slots, _q=ahead,
               _t=token, _m=mesh):
            _settle_run_ahead(_q, _m)
            outs = _c(*[regs[s] for s in _i])
            for s, o in zip(_o, outs):
                regs[s] = o
            if _t is not None:
                _q.append(outs[_t])

    return op


def _make_reshard_op(transfer, src_slot, dst_slot):
    def op(regs, _t=transfer, _s=src_slot, _d=dst_slot):
        regs[_d] = _t(regs[_s])

    return op


def _make_reshard_group_op(group, src_slots, dst_slots):
    def op(regs, _g=group, _s=src_slots, _d=dst_slots):
        outs = _g([regs[s] for s in _s])
        for d, o in zip(_d, outs):
            regs[d] = o

    return op


def _make_free_op(slots):
    def op(regs, _s=slots):
        for i in _s:
            regs[i] = None

    return op


########################################
# overlap mode: async transfer launch/wait ops (ISSUE 4)
########################################

_TRANSFER_POOL = None
_TRANSFER_POOL_LOCK = threading.Lock()


def _transfer_pool():
    """Process-wide transfer thread pool shared by every overlap-mode
    program (the scheduler's in-flight window — not the pool size — is
    what bounds concurrent transfers and staging memory)."""
    global _TRANSFER_POOL
    if _TRANSFER_POOL is None:
        with _TRANSFER_POOL_LOCK:
            if _TRANSFER_POOL is None:
                from concurrent.futures import ThreadPoolExecutor
                _TRANSFER_POOL = ThreadPoolExecutor(
                    max_workers=16, thread_name_prefix="alpa-overlap")
    return _TRANSFER_POOL


class _PendingTransfer:
    """A launched-but-unwaited cross-mesh transfer, parked in its dst
    slot until the matching wait op resolves it.  The dataflow graph
    guarantees nothing reads the slot in between."""
    __slots__ = ("future",)

    def __init__(self, future):
        self.future = future


# launched-but-unretired transfers, exported to the trace as the
# "transfers_in_flight" counter track (only touched when tracing is on)
_INFLIGHT_LOCK = threading.Lock()
_INFLIGHT = 0


def _inflight_delta(d: int):
    global _INFLIGHT
    with _INFLIGHT_LOCK:
        _INFLIGHT += d
        v = _INFLIGHT
    _ttrace.counter("transfers_in_flight", v)


def _record_pool_spans(label, t_sub_us, t_run_us, t_end_us):
    """Pool-side span family for one transfer: the labeled parent covers
    submit→retire, with ``reshard.wait`` (queue time between the driver
    submit and the worker picking it up — scheduler backpressure, not
    network) and ``reshard.wire`` (actual transfer execution) children.
    All timestamps come from ``trace.now_us`` so the driver-side submit
    stamp and the worker-side stamps share one epoch; insertion order
    parent-first keeps B-tie nesting correct in the Chrome export."""
    rec = _ttrace.get_recorder()
    rec.complete(label, "transfer", t_sub_us, t_end_us - t_sub_us)
    rec.complete("reshard.wait", "transfer", t_sub_us,
                 max(0.0, t_run_us - t_sub_us))
    rec.complete("reshard.wire", "transfer", t_run_us,
                 max(0.0, t_end_us - t_run_us))


def _make_launch_op(transfer, src_slot, dst_slot, label="transfer"):
    # regs[src] is captured on the driver thread at launch time, so a
    # later donation/FREE of the src slot (which the schedule orders
    # after this launch's wait anyway) can never race the worker.
    # A transfer that launches a program over a whole mesh
    # (``on_driver``: CollectiveTransfer's relayout) runs here, on the
    # thread that launches the mesh's stage programs, so that programs
    # with collectives reach a mesh's chips in one order; it only
    # enqueues, and its wait finds the future resolved.
    on_driver = getattr(transfer, "on_driver", False)

    def op(regs, _t=transfer, _s=src_slot, _d=dst_slot, _l=label):
        v = regs[_s]
        traced = _ttrace.enabled()
        t_sub = _ttrace.now_us() if traced else 0.0

        def work(_v=v, _tt=_t, _ll=_l, _traced=traced, _sub=t_sub):
            t_run = _ttrace.now_us() if _traced else 0.0
            t0 = time.perf_counter()
            out = _tt(_v)
            busy = time.perf_counter() - t0
            if _traced:
                _record_pool_spans(_ll, _sub, t_run, _ttrace.now_us())
            return out, busy

        if traced:
            _inflight_delta(1)
        if on_driver:
            fut = Future()
            try:
                fut.set_result(work())
            except Exception as e:  # pylint: disable=broad-except
                fut.set_exception(e)    # raised by the wait, as a
                #                         worker's would be
        else:
            fut = _transfer_pool().submit(work)
        regs[_d] = _PendingTransfer(fut)

    return op


def _make_wait_op(dst_slot, stats):
    def op(regs, _d=dst_slot, _st=stats):
        p = regs[_d]
        if type(p) is _PendingTransfer:
            t0 = time.perf_counter()
            out, busy = p.future.result()
            _st["wait_blocked_s"] += time.perf_counter() - t0
            _st["transfer_busy_s"] += busy
            regs[_d] = out
            if _ttrace.enabled():
                _inflight_delta(-1)

    return op


def _make_launch_group_op(group, src_slots, dst_slots,
                          label="transfer-group"):
    # The whole batched group travels as one future, parked at the first
    # member's dst slot; the group wait scatters every output.
    def op(regs, _g=group, _s=src_slots, _d=dst_slots, _l=label):
        vals = [regs[s] for s in _s]
        traced = _ttrace.enabled()
        t_sub = _ttrace.now_us() if traced else 0.0

        def work(_v=vals, _gg=_g, _ll=_l, _traced=traced, _sub=t_sub):
            t_run = _ttrace.now_us() if _traced else 0.0
            t0 = time.perf_counter()
            outs = _gg(_v)
            busy = time.perf_counter() - t0
            if _traced:
                _record_pool_spans(_ll, _sub, t_run, _ttrace.now_us())
            return outs, busy

        if traced:
            _inflight_delta(1)
        regs[_d[0]] = _PendingTransfer(_transfer_pool().submit(work))

    return op


def _make_wait_group_op(dst_slots, stats):
    def op(regs, _d=dst_slots, _st=stats):
        p = regs[_d[0]]
        if type(p) is _PendingTransfer:
            t0 = time.perf_counter()
            outs, busy = p.future.result()
            _st["wait_blocked_s"] += time.perf_counter() - t0
            _st["transfer_busy_s"] += busy
            for d, o in zip(_d, outs):
                regs[d] = o
            if _ttrace.enabled():
                _inflight_delta(-1)

    return op


# process-wide overlap runtime counters, kept in the central metrics
# registry (ISSUE 5) and surfaced via monitoring.get_overlap_stats —
# the same series GET /metrics exports as alpa_overlap_*.
_OVERLAP_REG = _tmetrics.get_registry()
_OVERLAP_STEPS = _OVERLAP_REG.counter(
    "alpa_overlap_steps_total", "Overlap-mode pipeshard steps executed")
_OVERLAP_BUSY = _OVERLAP_REG.counter(
    "alpa_overlap_transfer_busy_seconds_total",
    "Accumulated pool-side transfer execution time")
_OVERLAP_BLOCKED = _OVERLAP_REG.counter(
    "alpa_overlap_wait_blocked_seconds_total",
    "Accumulated driver time blocked in transfer waits")
_OVERLAP_HOISTED = _OVERLAP_REG.counter(
    "alpa_overlap_hoisted_total",
    "Cross-mesh transfers launched ahead of flat instruction order")
_OVERLAP_LAUNCHES = _OVERLAP_REG.counter(
    "alpa_overlap_launches_total",
    "Async transfer launches issued (a batched group counts once)")
_OVERLAP_LAST_FRACTION = _OVERLAP_REG.gauge(
    "alpa_overlap_last_overlap_fraction",
    "Last step's 1 - wait_blocked/transfer_busy overlap fraction")
_OVERLAP_LAST_WINDOW = _OVERLAP_REG.gauge(
    "alpa_overlap_last_window",
    "Last step's in-flight transfer window")

# train state that a step's launch had to carry to another device set
# than the previous step left it on (pipeshard_executable
# ``_launch_registers``): 0 for a plan that keeps every leaf of the state
# beside the stage that reads it
LAUNCH_MOVED_BYTES = _OVERLAP_REG.counter(
    "alpa_pipeshard_launch_moved_bytes_total",
    "Bytes of non-batch input arrays put onto another device set at a "
    "pipeshard step's launch, from the second step on")
LAUNCH_MOVED_ARRAYS = _OVERLAP_REG.counter(
    "alpa_pipeshard_launch_moved_arrays_total",
    "Non-batch input arrays put onto another device set at a pipeshard "
    "step's launch, from the second step on (one per target mesh)")
# ... and what it found on the right devices in another sharding than
# the reading stage planned: jax re-lays such an array out through the
# host (``shard_sharded_device_array_slow_path``), with every chip idle
LAUNCH_RELAID_BYTES = _OVERLAP_REG.counter(
    "alpa_pipeshard_launch_relayout_bytes_total",
    "Bytes of non-batch input arrays a pipeshard step's launch found on "
    "their mesh in another sharding than wanted, from the second step on")
LAUNCH_RELAID_ARRAYS = _OVERLAP_REG.counter(
    "alpa_pipeshard_launch_relayout_arrays_total",
    "Non-batch input arrays a pipeshard step's launch found on their "
    "mesh in another sharding than wanted, from the second step on")


def record_overlap_step(stats: Dict[str, Any]) -> None:
    """Fold one overlap-mode step's dispatch stats into the registry
    (called by pipeshard_executable after each launch)."""
    _OVERLAP_STEPS.inc()
    _OVERLAP_BUSY.inc(stats.get("transfer_busy_s", 0.0))
    _OVERLAP_BLOCKED.inc(stats.get("wait_blocked_s", 0.0))
    _OVERLAP_HOISTED.inc(stats.get("n_hoisted", 0))
    _OVERLAP_LAUNCHES.inc(stats.get("n_launches", 0))
    _OVERLAP_LAST_FRACTION.set(stats.get("overlap_fraction", 0.0))
    _OVERLAP_LAST_WINDOW.set(stats.get("overlap_window", 0))


def get_overlap_runtime_stats() -> Dict[str, Any]:
    """Thin view over the registry; dict shape is unchanged from the
    pre-telemetry module-private counters."""
    return {
        "steps": int(_OVERLAP_STEPS.value),
        "transfer_busy_s": _OVERLAP_BUSY.value,
        "wait_blocked_s": _OVERLAP_BLOCKED.value,
        "n_hoisted": int(_OVERLAP_HOISTED.value),
        "n_launches": int(_OVERLAP_LAUNCHES.value),
        "last_overlap_fraction": _OVERLAP_LAST_FRACTION.value,
        "last_window": int(_OVERLAP_LAST_WINDOW.value),
    }


def reset_overlap_runtime_stats() -> None:
    for fam in (_OVERLAP_STEPS, _OVERLAP_BUSY, _OVERLAP_BLOCKED,
                _OVERLAP_HOISTED, _OVERLAP_LAUNCHES,
                _OVERLAP_LAST_FRACTION, _OVERLAP_LAST_WINDOW):
        fam.reset()


def lower_to_register_file(
        instructions: List[PipelineInstruction],
        preplaced_shardings: Dict[Tuple[Var, int, int], Any],
        mode: str = "registers",
        overlap_window: int = 4,
        protected_keys=frozenset(),
        opt_state_keys=frozenset(),
        provenance_keys=None,
        equiv_reference=None,
) -> RegisterFileProgram:
    """Lower the emitted instruction list into a :class:`RegisterFileProgram`.

    ``preplaced_shardings`` seeds the static sharding model with the
    launch-placed values (global inputs, consts, zero accumulators):
    key ``(var, microbatch-instance, mesh)`` -> sharding.  The lowering
    walks the instructions in global order tracking the layout each slot
    holds, so RESHARD executors know their source sharding statically and
    RUN args that would need the interpreter's per-call relayout safety
    net become precomputed fixups.

    Two phases (ISSUE 4).  Phase 1 is mode-independent: slot allocation,
    static sharding propagation, and the per-instruction dataflow graph
    are identical for every ``mode``, so programs lowered from the same
    instruction list share ``slot_of`` and the launch-time slot tables
    can be reused across modes.  Phase 2 emits ops per mode:

    * ``registers`` — flat instruction order, with same-edge RESHARD
      coalescing extended past intervening FREEs (PR 2's pass required
      global adjacency, but FREEs emitted right after a value's last use
      split otherwise-contiguous same-edge runs).  Hopping a FREE is safe
      because FREE always follows its slots' last use — the batched group
      runs first and the FREE is re-emitted right after it; a same-edge
      RESHARD touching a hopped slot ends the group instead of joining.
    * ``overlap`` — replay :func:`schedule_overlap`'s plan: cross-mesh
      RESHARDs become launch/wait pairs over a shared transfer thread
      pool with a bounded in-flight window, and consecutive same-edge
      launches merge into one batched group launch.  Same-mesh relayouts
      and everything else execute synchronously in flat relative order.
    """
    from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
        DirectTransfer, DirectTransferGroup, make_transfer)

    if mode not in ("registers", "overlap"):
        raise ValueError(f"unknown lowering mode: {mode!r}")

    slot_of: Dict[Tuple[Var, int, int], int] = {}

    def slot(key):
        s = slot_of.get(key)
        if s is None:
            s = slot_of[key] = len(slot_of)
        return s

    # per mesh, the RUNs dispatched and not yet known to have finished
    run_ahead: Dict[int, Any] = collections.defaultdict(collections.deque)

    cur_sharding: Dict[int, Any] = {}
    for key, sh in preplaced_shardings.items():
        cur_sharding[slot(key)] = sh

    # ---- phase 1: slot allocation + sharding propagation (mode-free) ----
    recs: List[Dict[str, Any]] = []
    by_opcode = {"RUN": 0, "RESHARD": 0, "FREE": 0}
    n_fixups = 0

    # numerics certification (ISSUE 14): classify each stage's
    # matmul/reduce/cast population once per executable, only when the
    # verifier will actually consume it (both knobs on)
    want_numerics = (
        getattr(global_config, "verify_plans", "warn") != "off" and
        getattr(global_config, "verify_plans_numerics", "warn") != "off")
    _prec_cache: Dict[int, Any] = {}

    def _precision_of(ex):
        if not want_numerics:
            return None
        key = id(ex)
        if key not in _prec_cache:
            from alpa_tpu.shard_parallel.eqn_classify import (
                classify_stage_precision)
            _prec_cache[key] = classify_stage_precision(ex)
        return _prec_cache[key]

    # translation validation (ISSUE 15): the per-RUN stage signature /
    # donation / accumulation facts the symbolic executor applies —
    # derived by the same shared helper the driver's reference
    # decomposition uses, so a correct lowering matches by construction
    want_equiv = (
        getattr(global_config, "verify_plans", "warn") != "off" and
        getattr(global_config, "verify_plans_equiv", "warn") != "off"
        and equiv_reference is not None)

    def _equiv_of(inst, ex):
        if not want_equiv:
            return None
        from alpa_tpu.analysis.equivalence import stage_equiv_info
        info = stage_equiv_info(ex)
        mb = getattr(inst, "micro_batch", None)
        return {"stage": info["stage"],
                "mb": int(mb) if mb is not None else -1,
                "donate": list(info["donate"]),
                "acc": dict(info["acc"])}

    # quantized gradient collectives (ISSUE 19): when the knob is on,
    # every RUN record carries the codec facts; the numerics analysis
    # composes the bound only where the stage actually donates
    # gradient-provenance accumulators, so the tag alone never taints a
    # forward stage.  None at grad_quantize=off — records (and
    # therefore plan fingerprints) are byte-identical to main.
    _gq_mode = getattr(global_config, "grad_quantize", "off")
    _grad_tag = None
    if _gq_mode != "off":
        _mbs = {int(getattr(inst, "micro_batch", 0) or 0)
                for inst in instructions
                if inst.opcode == PipelineInstType.RUN and
                getattr(inst, "micro_batch", None) is not None}
        _grad_tag = {
            "mode": _gq_mode,
            "ef": bool(getattr(global_config, "grad_error_feedback",
                               True)),
            "hops": max(1, len(_mbs)),
            "rs": False,
        }

    for inst in instructions:
        if inst.opcode == PipelineInstType.RUN:
            by_opcode["RUN"] += 1
            ex = inst.executable
            in_slots, fixups = [], []
            for pos, k in enumerate(inst.input_keys):
                s = slot((k[0], k[1], inst.dst_mesh))
                in_slots.append(s)
                need = ex.in_shardings[pos]
                ndim = len(getattr(ex.invars[pos].aval, "shape", ()))
                if not _equiv_shardings(cur_sharding.get(s), need, ndim):
                    fixups.append((pos, need, ndim))
            out_slots = []
            for pos, k in enumerate(inst.output_keys):
                s = slot((k[0], k[1], inst.dst_mesh))
                out_slots.append(s)
                cur_sharding[s] = ex.out_shardings[pos]
            n_fixups += len(fixups)
            donated = set(getattr(ex, "donate_idx", ()) or ())
            kills = tuple(sorted({in_slots[p] for p in donated
                                  if p < len(in_slots)}))
            sizes = [aval_bytes(v.aval)
                     for v in getattr(ex, "outvars", ())]
            recs.append({
                "kind": "RUN",
                "op": _make_run_op(
                    ex.compiled, tuple(in_slots), tuple(out_slots),
                    tuple(fixups), run_ahead[inst.dst_mesh],
                    sizes.index(min(sizes)) if sizes else None,
                    mesh=inst.dst_mesh),
                "reads": tuple(in_slots),
                "writes": tuple(out_slots),
                "kills": kills,
                "name": f"RUN {inst.info}",
                "mesh": inst.dst_mesh,
                # fault hook point: same site/info/retry semantics the
                # interpreter uses for this instruction (ISSUE 6)
                "site": "stage_launch",
                "finfo": {"stage": inst.info, "mesh_id": inst.dst_mesh},
                "precision": _precision_of(ex),
                "equiv": _equiv_of(inst, ex),
                "grad_quant": _grad_tag,
                "idem": not donated,
                "line": (f"RUN {inst.info} mb={inst.micro_batch} "
                         f"in={in_slots} out={out_slots} "
                         f"fix={[(p, str(s)) for p, s, _ in fixups]}"),
            })
        elif inst.opcode == PipelineInstType.RESHARD:
            by_opcode["RESHARD"] += 1
            v = inst.var_key[0]
            ss = slot((v, inst.var_key[1], inst.src_mesh))
            ds = slot((v, inst.var_key[1], inst.dst_mesh))
            # collective lowering (ISSUE 7): the factory replays the
            # planner's per-edge strategy (DirectTransfer,
            # CollectiveTransfer, or the opt-in quantized codec); weight
            # edges (microbatch-invariant, var_key[1] < 0) stay lossless
            t = make_transfer(v.aval, cur_sharding.get(ss),
                              inst.dst_sharding,
                              cross=inst.src_mesh != inst.dst_mesh,
                              plan=inst.plan,
                              weight=inst.var_key[1] < 0)
            strategy = getattr(t, "strategy", None) or \
                ("quantized" if not isinstance(t, DirectTransfer)
                 else "direct_p2p")
            tag = "" if strategy == "direct_p2p" else f" [{strategy}]"
            cur_sharding[ds] = inst.dst_sharding
            recs.append({
                "kind": "RESHARD",
                "op": _make_reshard_op(t, ss, ds),
                "transfer": t,
                # only DirectTransfers coalesce into batched groups
                "groupable": isinstance(t, DirectTransfer),
                "ss": ss,
                "ds": ds,
                "edge": (inst.src_mesh, inst.dst_mesh),
                "cross": inst.src_mesh != inst.dst_mesh,
                "reads": (ss,),
                "writes": (ds,),
                "kills": (),
                "name": f"RESHARD {inst.src_mesh}->{inst.dst_mesh}{tag}",
                "mesh": inst.dst_mesh,
                "site": "cross_mesh_send",
                "finfo": {"var": str(v), "src_mesh": inst.src_mesh,
                          "dst_mesh": inst.dst_mesh,
                          "strategy": strategy,
                          "codec": getattr(t, "mode", None)
                          if strategy == "quantized" else None},
                "codec": getattr(t, "mode", None)
                if strategy == "quantized" else None,
                "idem": True,
                "line": (f"RESHARD {inst.var_key} {inst.src_mesh}->"
                         f"{inst.dst_mesh} slot {ss}->{ds} fast={t.fast}" +
                         ("" if strategy == "direct_p2p"
                          else f" strategy={strategy}")),
            })
        else:  # FREE
            by_opcode["FREE"] += 1
            slots = tuple(slot((k[0], k[1], k[2])) for k in inst.free_keys)
            recs.append({
                "kind": "FREE",
                "op": _make_free_op(slots),
                "slots": slots,
                "reads": (),
                "writes": (),
                "kills": slots,
                "name": "FREE",
                "mesh": inst.free_keys[0][2] if inst.free_keys else 0,
                "line": f"FREE {list(slots)}",
            })

    nodes = [
        DataflowNode(idx=i, kind=r["kind"], reads=r["reads"],
                     writes=r["writes"], kills=r["kills"],
                     edge=r.get("edge"), cross_mesh=r.get("cross", False),
                     info=r["line"])
        for i, r in enumerate(recs)
    ]
    graph = InstructionDataflowGraph.build(nodes)
    # static hazard pass on every compile (ISSUE 6): a missing
    # dependency edge is a lowering bug — fail here, not as silent
    # numeric corruption three replays later
    graph.check()
    n_cross = graph.n_cross_mesh
    n = len(recs)

    def _hook_for(r, idx, kind="exec"):
        reads, writes, kills = r["reads"], r["writes"], r["kills"]
        site = r.get("site")
        return OpHook(kind=kind, name=r["name"], node=idx,
                      mesh=r["mesh"], reads=reads, writes=writes,
                      kills=kills,
                      slots=tuple(sorted({*reads, *writes, *kills})),
                      fault_site=site,
                      fault_infos=(r["finfo"],) if site else (),
                      idempotent=r.get("idem", True),
                      precision=r.get("precision"),
                      equiv=r.get("equiv"),
                      members=(idx,))

    def _group_hook(mem_idx, kind="exec", label=None):
        # one hook for a batched same-edge group: union footprint, one
        # fault info per member (hit counts match the interpreter)
        mem = [recs[m] for m in mem_idx]
        first = mem[0]
        reads = tuple(m["ss"] for m in mem)
        writes = tuple(m["ds"] for m in mem)
        name = label or (f"RESHARD-GROUP x{len(mem)} "
                         f"{first['edge'][0]}->{first['edge'][1]}")
        return OpHook(kind=kind, name=name, node=mem_idx[0],
                      mesh=first["mesh"], reads=reads, writes=writes,
                      slots=tuple(sorted({*reads, *writes})),
                      fault_site="cross_mesh_send",
                      fault_infos=tuple(m["finfo"] for m in mem),
                      idempotent=True,
                      members=tuple(mem_idx))

    ops: List[Any] = []
    lines: List[str] = []
    meta: List[Tuple[str, str, str]] = []   # (span name, category, track)
    hooks: List[OpHook] = []                # per-op hook points (ISSUE 6)
    n_groups = 0
    n_free_hops = 0
    n_hoisted = 0
    n_launches = 0
    run_stats = {"transfer_busy_s": 0.0, "wait_blocked_s": 0.0}

    if mode == "registers":
        # ---- phase 2a: flat replay with extended same-edge coalescing ----
        # group-membership legality lives in ONE oracle shared with the
        # superopt fusion family (analysis/superopt.py, ISSUE 17);
        # superopt_max_group > 0 is the fission knob.  Lazy import:
        # analysis/ sits above the lowering layer.
        from alpa_tpu.analysis.superopt import reshard_group_extent
        i = 0
        while i < n:
            r = recs[i]
            if r["kind"] != "RESHARD":
                ops.append(r["op"])
                lines.append(r["line"])
                meta.append((r["name"], "instruction",
                             f"mesh {r['mesh']}"))
                hooks.append(_hook_for(r, i))
                i += 1
                continue
            edge = r["edge"]
            members, hopped, hops, j = reshard_group_extent(
                recs, i,
                max_members=global_config.superopt_max_group)
            n_free_hops += hops
            # trailing FREEs (after the last member) keep their original
            # relative position by being re-emitted after the group
            if len(members) == 1:
                m = recs[members[0]]
                ops.append(m["op"])
                lines.append(m["line"] + " edgegroup=1")
                meta.append((m["name"], "instruction",
                             f"mesh {m['mesh']}"))
                hooks.append(_hook_for(m, members[0]))
            else:
                n_groups += 1
                mem = [recs[m_] for m_ in members]
                ops.append(_make_reshard_group_op(
                    DirectTransferGroup([m["transfer"] for m in mem]),
                    tuple(m["ss"] for m in mem),
                    tuple(m["ds"] for m in mem)))
                for m in mem:
                    lines.append(m["line"] + f" edgegroup={len(mem)}")
                meta.append((
                    f"RESHARD-GROUP x{len(mem)} "
                    f"{edge[0]}->{edge[1]}", "instruction",
                    f"mesh {mem[0]['mesh']}"))
                hooks.append(_group_hook(members))
            for qi in hopped:
                q = recs[qi]
                ops.append(q["op"])
                lines.append(q["line"])
                meta.append((q["name"], "instruction",
                             f"mesh {q['mesh']}"))
                hooks.append(_hook_for(q, qi))
            i = j
    else:
        # ---- phase 2b: overlap replay of the dataflow graph ----
        window = max(1, min(int(overlap_window), max(1, n_cross)))
        plan, n_hoisted = schedule_overlap(graph, window)
        # merge consecutive same-edge launches into one batched group
        group_of: Dict[int, int] = {}
        group_members: Dict[int, List[int]] = {}
        k = 0
        while k < len(plan):
            kind, idx = plan[k]
            if kind != "launch" or not recs[idx].get("groupable", True):
                k += 1
                continue
            edge = recs[idx]["edge"]
            mem = [idx]
            k2 = k + 1
            while (k2 < len(plan) and plan[k2][0] == "launch" and
                   recs[plan[k2][1]].get("groupable", True) and
                   recs[plan[k2][1]]["edge"] == edge):
                mem.append(plan[k2][1])
                k2 += 1
            if len(mem) > 1:
                gid = len(group_members)
                group_members[gid] = mem
                for m_ in mem:
                    group_of[m_] = gid
            k = k2
        waited_groups: set = set()
        for kind, idx in plan:
            r = recs[idx]
            if kind == "exec":
                ops.append(r["op"])
                lines.append(r["line"])
                meta.append((r["name"], "instruction",
                             f"mesh {r['mesh']}"))
                hooks.append(_hook_for(r, idx))
            elif kind == "launch":
                gid = group_of.get(idx)
                if gid is None:
                    n_launches += 1
                    ops.append(_make_launch_op(
                        r["transfer"], r["ss"], r["ds"],
                        label=r["name"]))
                    lines.append(f"LAUNCH #{idx} " + r["line"])
                    meta.append((f"LAUNCH {r['name']}", "transfer",
                                 f"mesh {r['mesh']}"))
                    hooks.append(_hook_for(r, idx, kind="launch"))
                elif group_members[gid][0] == idx:
                    n_launches += 1
                    n_groups += 1
                    mem = group_members[gid]
                    ops.append(_make_launch_group_op(
                        DirectTransferGroup(
                            [recs[m]["transfer"] for m in mem]),
                        tuple(recs[m]["ss"] for m in mem),
                        tuple(recs[m]["ds"] for m in mem),
                        label=(f"{r['name']} x{len(mem)}")))
                    lines.append(
                        f"LAUNCH-GROUP #{mem} edge={r['edge']}")
                    meta.append((
                        f"LAUNCH-GROUP x{len(mem)} "
                        f"{r['edge'][0]}->{r['edge'][1]}", "transfer",
                        f"mesh {r['mesh']}"))
                    hooks.append(_group_hook(
                        mem, kind="launch",
                        label=(f"LAUNCH-GROUP x{len(mem)} "
                               f"{r['edge'][0]}->{r['edge'][1]}")))
                # non-leading group members were folded into the group op
            else:  # wait
                gid = group_of.get(idx)
                if gid is None:
                    ops.append(_make_wait_op(r["ds"], run_stats))
                    lines.append(f"WAIT #{idx} slot {r['ds']}")
                    meta.append((f"WAIT {r['name']}", "transfer",
                                 f"mesh {r['mesh']}"))
                    hooks.append(dataclasses.replace(
                        _hook_for(r, idx, kind="wait"),
                        name=f"WAIT {r['name']}",
                        fault_site=None, fault_infos=()))
                elif gid not in waited_groups:
                    waited_groups.add(gid)
                    mem = group_members[gid]
                    ops.append(_make_wait_group_op(
                        tuple(recs[m]["ds"] for m in mem), run_stats))
                    lines.append(f"WAIT-GROUP #{mem}")
                    meta.append((f"WAIT-GROUP x{len(mem)}", "transfer",
                                 f"mesh {r['mesh']}"))
                    hooks.append(dataclasses.replace(
                        _group_hook(mem, kind="wait",
                                    label=f"WAIT-GROUP x{len(mem)}"),
                        fault_site=None, fault_infos=()))
                # later member waits are satisfied by the group wait
        lines.append(f"MODE overlap window={window} hoisted={n_hoisted} "
                     f"launches={n_launches}")

    assert len(hooks) == len(ops) == len(meta), (
        "lowering emitted misaligned op/meta/hook lists")
    prog = RegisterFileProgram(num_slots=len(slot_of),
                               ops=ops,
                               n_instructions=n,
                               by_opcode=by_opcode,
                               slot_of=slot_of,
                               n_coalesced_groups=n_groups,
                               n_fixups=n_fixups,
                               text="\n".join(lines),
                               mode=mode,
                               graph=graph,
                               n_cross_mesh=n_cross,
                               n_hoisted=n_hoisted,
                               n_launches=n_launches,
                               n_free_hops=n_free_hops,
                               overlap_window=(window if mode == "overlap"
                                               else 0),
                               run_stats=run_stats,
                               run_ahead=run_ahead,
                               op_meta=meta,
                               hooks=hooks)
    # static plan verification (ISSUE 8): typed abstract interpretation
    # + deadlock/liveness/structure analyses over the program just
    # built.  Runs once per compile (cached by plan fingerprint for
    # warm restarts), costs nothing at dispatch replay.  verify_plans:
    # "error" blocks compilation on findings, "warn" (default) logs,
    # "off" skips entirely.
    if getattr(global_config, "verify_plans", "warn") != "off":
        from alpa_tpu.analysis import plan_verifier
        prog.verdict = plan_verifier.verify_program(
            instructions, prog, preplaced_shardings, recs,
            protected_keys=protected_keys,
            opt_state_keys=opt_state_keys,
            provenance_keys=provenance_keys,
            reference=equiv_reference)
    return prog


def emit_free_instructions(instructions: List[PipelineInstruction],
                           protected_keys) -> List[PipelineInstruction]:
    """Insert FREE after the last use of each (var, inst, mesh) value
    (ref _compile_free, runtime_emitter.py:1087)."""
    last_use: Dict[Tuple[int, int, int], int] = {}
    defined: Dict[Tuple[int, int, int], int] = {}
    for i, inst in enumerate(instructions):
        if inst.opcode == PipelineInstType.RUN:
            mesh = inst.dst_mesh
            for k in inst.input_keys:
                last_use[(k[0], k[1], mesh)] = i
            for k in inst.output_keys:
                defined[(k[0], k[1], mesh)] = i
        elif inst.opcode == PipelineInstType.RESHARD:
            last_use[(inst.var_key[0], inst.var_key[1], inst.src_mesh)] = i
            defined[(inst.var_key[0], inst.var_key[1], inst.dst_mesh)] = i
    out: List[PipelineInstruction] = []
    frees_at: Dict[int, List[Tuple[int, int, int]]] = {}
    for key, i in last_use.items():
        if key in protected_keys:
            continue
        if key not in defined:
            continue  # inputs placed at launch are managed by the driver
        frees_at.setdefault(i, []).append(key)
    for i, inst in enumerate(instructions):
        out.append(inst)
        if i in frees_at:
            out.append(
                PipelineInstruction(PipelineInstType.FREE,
                                    free_keys=frees_at[i]))
    return out
