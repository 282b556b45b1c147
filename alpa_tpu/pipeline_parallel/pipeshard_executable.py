"""Pipeshard driver executable: compile stages onto submeshes and interpret
the static instruction stream.

Analog of ref ``alpa/pipeline_parallel/pipeshard_executable.py`` (SURVEY.md
§2.4): the reference pushes per-worker instruction lists to Ray actors and
instantiates NCCL groups; here a single controller dispatches async jax
executions onto per-stage meshes, and cross-mesh resharding is
``jax.device_put`` (ICI/DCN transfers by the jax runtime).  Dispatch is
asynchronous, so consecutive RUNs on different meshes overlap on device —
the single Python loop plays the role of the reference's per-host
interpreter loops (``execute_on_worker``, ref pipeshard_executable.py:489).
"""
import contextlib
import dataclasses
import itertools
import logging
import threading
import time
import types
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.core import jaxpr_as_fun
from jax.extend.core import Literal, Var

from alpa_tpu import fault
from alpa_tpu.global_env import global_config
from alpa_tpu.mesh_executable import alloc_zero_buffers
from alpa_tpu.pipeline_parallel.runtime_emitter import (
    LAUNCH_MOVED_ARRAYS, LAUNCH_MOVED_BYTES, LAUNCH_RELAID_ARRAYS,
    LAUNCH_RELAID_BYTES, PipelineInstType, PipelineInstruction,
    PipeshardConfig, PlacementSpecEntry, emit_free_instructions,
    partition_streams)
from alpa_tpu.pipeline_parallel.schedules import create_pipeline_schedule
from alpa_tpu.shard_parallel import kernel_choice
from alpa_tpu.shard_parallel.auto_sharding import (MESH_AXIS_NAMES,
                                                  resolved_zero_stage)
from alpa_tpu.telemetry import device_time as _device_time
from alpa_tpu.telemetry import flight as _flight
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry import trace as _ttrace
from alpa_tpu.util import OrderedSet

logger = logging.getLogger(__name__)

# driver-side dispatch latency of one pipeshard step (the whole
# instruction replay, not device wall clock) — replaces the deprecated
# timers("pipeshard-dispatch") bridge
_DISPATCH_SECONDS = _tmetrics.get_registry().histogram(
    "alpa_pipeshard_dispatch_seconds",
    "launch_on_driver dispatch latency per pipeshard step")


@dataclasses.dataclass
class _InputLoad:
    """Where one flat argument goes at a register launch: resolved once,
    replayed every step."""
    arg_idx: int
    is_batch: bool
    # (slot, sharding, micro-batch or -1) of every residency
    entries: List[Tuple[int, Any, int]]
    # the sharding the argument last came with, how many of the
    # ``entries`` lie on another device set than that, and how many on the
    # same devices in another layout (a state leaf comes back with the
    # same sharding object every step, so the comparison is made once)
    src_sharding: Any = None
    n_moved: int = 0
    n_relaid: int = 0


class StageExecutable:
    """One compiled stage bound to one mesh.

    Two-phase: ``plan()`` runs the intra-op planner, handed what the
    programs planned before it on the mesh decided (``fixed_in``: a value
    one of them produces arrives as produced, one it reads as it reads
    it), and keeps the plan: ``planned_in``, how each input arrives, and
    ``planned_out``, how each output leaves (None where the plan does not
    say).  The driver then reads the plans together
    (``_unify_same_mesh_shardings``) and ``compile()`` locks in what they
    agree on, so that no value is re-laid out between two programs of one
    mesh, at run time or by GSPMD at a program's end.
    """

    def __init__(self, name, comp, mesh_id, physical_mesh, as_option,
                 logical_shape, donate_idx, as_overrides=None,
                 in_paths=None, fixed_in=None, state_pairs=()):
        self.name = name
        self.comp = comp
        self.mesh_id = mesh_id
        self.invars = list(comp.invars)
        self.outvars = list(comp.outvars)
        self.donate_idx = tuple(donate_idx)
        self._physical_mesh = physical_mesh
        self._as_option = as_option
        self._logical_shape = logical_shape
        self._as_overrides = as_overrides
        # pytree paths of stage invars that are global inputs ("" for
        # stage-internal values) — lets the per-stage planner classify
        # optimizer-state / param leaves for weight-update sharding
        self._in_paths = list(in_paths) if in_paths is not None else None
        # invar position -> the sharding the value has on this mesh
        # whatever this program plans (another program's choice, which
        # unification hands it): the planner takes it as given
        self._fixed_in = dict(fixed_in or {})
        # (invar position, outvar position) of an apply program's donated
        # state leaves and their new values, which unification pins to the
        # leaf's input sharding
        self._state_pairs = list(state_pairs)
        self._fun = None
        self.compiled = None
        self.plan()

    def plan(self):
        closed = self.comp.closed_jaxpr()
        physical_mesh = self._physical_mesh
        if physical_mesh.num_devices > 1:
            # a kernel the planner cannot partition stays on a one-device
            # mesh only
            closed = kernel_choice.bind_defaults(closed)
        fun = jaxpr_as_fun(closed)
        avals = [v.aval for v in self.comp.invars]
        as_option = self._as_option
        # what the planner did with the donated pairs and the given
        # inputs (``solver.alias_stats``, ``solver.given_stats``; empty
        # where no planner ran)
        self.plan_stats: Dict[str, int] = {}
        planned_out: List[Any] = []

        if physical_mesh.num_devices > 1 and as_option.enable_auto_sharding:
            from alpa_tpu.shard_parallel.solver import plan_auto_sharding
            opt = as_option.copy()
            if self._logical_shape is not None:
                opt.logical_mesh_shape = tuple(self._logical_shape)
            # per-stage AutoShardingOption overrides
            # (ref submesh_autosharding_option_dicts)
            for k, v in (self._as_overrides or {}).items():
                if not hasattr(opt, k):
                    raise ValueError(
                        f"unknown AutoShardingOption field {k!r} in "
                        "submesh_autosharding_option_dicts")
                setattr(opt, k, v)
            in_paths = (self._in_paths if self._in_paths is not None
                        else [""] * len(avals))
            # an output written into a donated input's buffer leaves with
            # that input's sharding (``donated_out_shardings``, and for a
            # state leaf the pin of its new value): the planner chooses
            # the sharding with the lock in view
            jax_mesh, in_shardings, cfn, _shape = plan_auto_sharding(
                fun, avals, in_paths, [], physical_mesh, opt,
                alias_pairs=self.donated_pairs() + self._state_pairs,
                fixed_in=self._fixed_in, stage=self.name,
                stats=self.plan_stats, out_shardings=planned_out)
            if cfn is not None:
                fun = cfn  # realize the ILP plan inside the stage too
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            lm = physical_mesh.get_logical_mesh(
                (physical_mesh.num_devices, 1))
            jax_mesh = lm.get_jax_mesh(MESH_AXIS_NAMES)
            from alpa_tpu.shard_parallel.auto_sharding import (
                plan_rule_based)
            if (physical_mesh.num_devices > 1 and
                    self._in_paths is not None and
                    resolved_zero_stage(as_option) in (2, 3)):
                # manual (rule-based) stages still honor forced
                # weight-update sharding over the stage's dp group
                in_shardings = plan_rule_based(
                    jax_mesh, avals, self._in_paths, [], as_option)
            else:
                in_shardings = [
                    NamedSharding(jax_mesh, PartitionSpec()) for _ in avals
                ]
        self._fun = fun
        self._avals = avals
        self.jax_mesh = jax_mesh
        self.in_shardings = list(in_shardings)
        # the plan as it was made, which unification reads and
        # ``in_shardings`` / ``pinned_out`` may leave
        self.planned_in = list(in_shardings)
        self.planned_out = planned_out or [None] * len(self.outvars)
        # output shardings as the programs of the mesh agree on them, and
        # how many of this program's inputs and outputs that agreement
        # took from what it planned (both filled by unification)
        self.pinned_out: Dict[Var, Any] = {}
        self.unify_overrides = 0

    def donated_pairs(self) -> List[Tuple[int, int]]:
        """(invar position, outvar position) of every summed gradient
        accumulator that is written into its (donated) acc invar's
        buffer."""
        donate_var = {self.comp.invars[i]: i for i in self.donate_idx}
        acc_out_for = getattr(self.comp, "_acc_out_map", {})
        return [
            (donate_var[acc_out_for[ov]], k)
            for k, ov in enumerate(self.comp.outvars)
            if ov in acc_out_for and acc_out_for[ov] in donate_var
        ]

    def donated_out_shardings(self) -> Dict[Var, Any]:
        """Outvars whose sharding is locked by donation: summed gradient
        accumulators alias their (donated) acc invar's buffer, so their
        output sharding must equal that input sharding.  Single source of
        truth for both unification seeding and compile()."""
        return {
            self.comp.outvars[k]: self.in_shardings[i]
            for i, k in self.donated_pairs()
        }

    def compile(self):
        comp = self.comp
        # donated (accumulator) outputs must keep the input sharding
        locked = self.donated_out_shardings()
        out_shardings = []
        for ov in comp.outvars:
            if ov in locked:
                out_shardings.append(locked[ov])
            elif ov in self.pinned_out:
                out_shardings.append(self.pinned_out[ov])
            else:
                out_shardings.append(None)
        in_shardings = self.in_shardings

        def program(*args):
            return self._fun(*args)

        # the compiled program is called what the stage is called
        # (``jit_stage_0_fwd``): the profiler labels its runs so, and a
        # capture finds its HLO text by that label
        program.__name__ = program.__qualname__ = self.name
        jitted = jax.jit(program,
                         in_shardings=tuple(in_shardings),
                         out_shardings=out_shardings,
                         donate_argnums=self.donate_idx)
        with _ttrace.span("xla-compile", "compile",
                          {"stage": self.name} if _ttrace.enabled()
                          else None):
            lowered = jitted.lower(*self._avals)
            self.compiled = lowered.compile()
        # what the profiler calls this program's runs
        self.program_name = _device_time.compiled_name(self.compiled)
        _device_time.register_program(
            self.program_name, self,
            lambda stage: stage.compiled.as_text())
        self.out_shardings = list(self.compiled.output_shardings)

    def sharding_for(self, var) -> Any:
        return self.in_shardings[self.invars.index(var)]

    def __call__(self, args):
        return self.compiled(*args)


def _same_sharding(a, b, ndim: int) -> bool:
    return a is b or a.is_equivalent_to(b, ndim)


def _decide_shardings(ex: "StageExecutable", chosen: Dict, canon):
    """Add to ``chosen`` ((mesh, value) -> sharding) what program ``ex``
    is the first on its mesh to decide: a value it reads, in the sharding
    it planned to read it in; a value it produces, in the sharding its
    plan has it leave in (an accumulator's sum in its donation's lock; an
    output the plan says nothing of is left to its first reader)."""
    for v, planned in zip(ex.invars, ex.planned_in):
        chosen.setdefault((ex.mesh_id, canon(v)), planned)
    locked = ex.donated_out_shardings()
    for v, planned in zip(ex.outvars, ex.planned_out):
        s = locked.get(v, planned)
        if s is not None:
            chosen.setdefault((ex.mesh_id, canon(v)), s)


def _unify_same_mesh_shardings(execs: List["StageExecutable"],
                               var_alias: Optional[Dict[Var, Var]] = None):
    """Read the plans of the programs of each mesh together, in the order
    they were planned (``execs``'s), and lock in one sharding a value:

    * the first program to decide a value decides it for the mesh
      (``_decide_shardings``),
    * every later reader's ``in_shardings`` entry and the producer's
      ``pinned_out`` are that sharding.

    A program planned with the earlier decisions given (``fixed_in``) has
    already paid in its own objective for whatever else it wanted, and
    this changes nothing of it.  One planned without them (an update
    program under a forced or disabled ``zero_stage``) is handed them
    here, after its plan, and ``unify_overrides`` counts how many of its
    inputs and outputs that moved from what it planned.

    Call once, after every program's plan() and before any compile().
    """
    # ``var_alias`` canonicalizes distinct Vars naming the same runtime
    # value (gradient-marker `post` vars alias the accumulator's summed
    # outvar; a donated state leaf's new value is named as the old), so
    # apply stages adopt the accumulator shardings and write each leaf as
    # the next step reads it.
    var_alias = var_alias or {}

    def canon(v):
        return var_alias.get(v, v)

    chosen: Dict[Tuple[int, Var], Any] = {}
    for ex in execs:
        _decide_shardings(ex, chosen, canon)
    read = {(ex.mesh_id, canon(v)) for ex in execs for v in ex.invars}
    for ex in execs:
        ex.unify_overrides = 0
        for pos, v in enumerate(ex.invars):
            s = chosen[(ex.mesh_id, canon(v))]
            ex.in_shardings[pos] = s
            ex.unify_overrides += not _same_sharding(
                s, ex.planned_in[pos], len(v.aval.shape))
        locked = ex.donated_out_shardings()
        for v, planned in zip(ex.outvars, ex.planned_out):
            key = (ex.mesh_id, canon(v))
            # an output that no program of the mesh reads is the
            # compiler's to lay out
            if key in read and v not in locked:
                ex.pinned_out[v] = chosen[key]
                ex.unify_overrides += planned is not None and \
                    not _same_sharding(chosen[key], planned,
                                       len(v.aval.shape))


class PipeshardDriverExecutable:
    """(ref pipeshard_executable.py:41)"""

    def __init__(self, *, virtual_mesh, fwd_stages, bwd_stages, apply_comps,
                 submeshes, logical_shapes, as_dicts, as_option,
                 schedule_name, num_micro_batches, global_invars,
                 global_outvars, batch_invars, donated_invars, grad_pairs,
                 acc_info, in_avals, micro_avals, consts_map,
                 apply_var_mesh, invar_paths=None):
        self.num_micro_batches = num_micro_batches
        self.global_invars = global_invars
        self.global_outvars = global_outvars
        self.batch_invars = batch_invars
        self.donated_invars = donated_invars
        self.in_avals = in_avals
        self.out_tree = None  # set by caller
        self.schedule_name = schedule_name
        self.grad_pairs = grad_pairs
        self.acc_info = acc_info
        self.consts_map = consts_map
        # global invar Var -> caller pytree path (keystr); lets per-stage
        # planners and the plan verifier classify optimizer-state leaves
        self.invar_paths: Dict[Var, str] = dict(invar_paths or {})

        num_stages = len(fwd_stages)
        self.num_meshes = num_stages
        self.mesh_group = virtual_mesh.get_physical_mesh_group(submeshes)

        # ---- per-stage gradient-accumulation metadata ----
        # acc invar -> (sum outvar); attach map for sharding pinning
        self.acc_pairs: Dict[Var, Var] = {}
        sum_to_acc = {}
        for pre, (acc, summed, ci) in acc_info.items():
            self.acc_pairs[acc] = summed
            sum_to_acc[summed] = acc
        all_comps = list(fwd_stages) + list(bwd_stages)
        for comp in all_comps:
            comp._acc_out_map = {
                ov: sum_to_acc[ov] for ov in comp.outvars if ov in sum_to_acc
            }

        # ---- compile stages ----
        def stage_paths(comp):
            """Caller pytree path per stage invar ("" for stage-internal
            values) — feeds weight-update sharding classification."""
            if not self.invar_paths:
                return None
            return [self.invar_paths.get(v, "") for v in comp.invars]

        post_to_sum = {
            post: acc_info[pre][1]
            for pre, post in grad_pairs if pre in acc_info
        }
        # A donated state leaf comes back as the output at its own flat
        # position (state in, new state out).  Naming the new value as
        # the old one makes the program that writes it pin its output to
        # the sharding the next step reads the leaf with, so the next
        # launch finds it laid out as wanted instead of re-laying it out
        # through the host, and the write can reuse the donated buffer.
        new_to_old = {
            o: i for i, o, d in zip(global_invars, global_outvars,
                                    donated_invars)
            if d and isinstance(o, Var) and o is not i and
            o.aval.shape == i.aval.shape and o.aval.dtype == i.aval.dtype
        }
        same_value = {**new_to_old, **post_to_sum}

        # On a mesh, a program planned after another takes every value the
        # earlier ones decided as given (a value one of them produces
        # arrives as its plan has it leave, one it reads as it reads it),
        # and its own ILP pays for whatever else its products want.  The
        # forward stages come first and find nothing decided; a backward
        # stage finds its forward stage's residuals, parameters and pieces
        # of the batch.
        self.stage_execs: List[StageExecutable] = []
        self._stage_of_comp = {}
        tic = time.time()

        self.apply_execs: List[Optional[StageExecutable]] = []
        # (mesh, value) -> sharding, as decided by the programs planned so
        # far (``_decide_shardings``)
        decided: Dict[Tuple[int, Var], Any] = {}

        def same(v):
            return same_value.get(v, v)

        def planned(comp, mesh_id, donate, given=True, **kwargs):
            """The next program of a mesh, planned with what is decided
            there given (``fixed_in``) unless told not to; what it is the
            first to decide is decided from here on."""
            fixed_in = {i: decided[(mesh_id, same(v))]
                        for i, v in enumerate(comp.invars)
                        if given and (mesh_id, same(v)) in decided}
            ex = StageExecutable(comp.name, comp, mesh_id,
                                 self.mesh_group[mesh_id], as_option,
                                 logical_shapes[mesh_id], donate,
                                 in_paths=stage_paths(comp),
                                 fixed_in=fixed_in, **kwargs)
            _decide_shardings(ex, decided, same)
            return ex

        for s, comp in itertools.chain(enumerate(fwd_stages),
                                       enumerate(bwd_stages)):
            donate = [
                i for i, v in enumerate(comp.invars) if v in self.acc_pairs
            ]
            self.stage_execs.append(planned(
                comp, s, donate,
                as_overrides=as_dicts[s] if as_dicts else None))
        self.num_fwd_stages = len(fwd_stages)
        self.has_bwd = len(bwd_stages) > 0
        # Donate state inputs (params/opt state) to the apply executables
        # that consume them exactly once — realizes the caller's
        # donate_argnums contract so old and new state never coexist.
        donated_global = {
            v for v, d in zip(global_invars, donated_invars) if d
        }
        use_count: Dict[Var, int] = {}
        for comp in apply_comps:
            for v in comp.invars:
                use_count[v] = use_count.get(v, 0) + 1
        # An apply program is handed the same under ``zero_stage="auto"``:
        # the parameters as their first reader wants them, the summed
        # gradients as their accumulators' donation locks them.  Its own
        # choice is the rest (the optimizer's state), made with those in
        # view and with each donated leaf's new value held to the leaf's
        # sharding.
        # That is weight-update sharding chosen by cost, which is what
        # ``zero_stage="auto"`` asks for: "0" turns it off and "2"/"3" lay
        # the state out by their own rule, and under those the apply
        # programs are planned as they were, and handed the rest after.
        by_cost = resolved_zero_stage(as_option) == -1
        for m, comp in enumerate(apply_comps):
            if comp.eqns or comp.outvars:
                donate = [
                    i for i, v in enumerate(comp.invars)
                    if v in donated_global and use_count.get(v) == 1
                ]
                position = {v: i for i, v in enumerate(comp.invars)}
                state_pairs = [
                    (position[new_to_old[o]], k)
                    for k, o in enumerate(comp.outvars)
                    if by_cost and position.get(new_to_old.get(o)) in donate
                ]
                self.apply_execs.append(planned(
                    comp, m, donate, given=by_cost,
                    state_pairs=state_pairs))
            else:
                self.apply_execs.append(None)
        # read the plans together, then compile everything with the
        # layouts they agree on
        all_execs = self.stage_execs + [
            e for e in self.apply_execs if e is not None
        ]
        with _ttrace.span("unify-shardings", "compile",
                          {} if _ttrace.enabled() else None) as unify_span:
            _unify_same_mesh_shardings(all_execs, same_value)
            if getattr(unify_span, "args", None) is not None:
                unify_span.args["unify_overrides"] = {
                    e.name: e.unify_overrides for e in all_execs}
        for e in all_execs:
            e.compile()
        if global_config.print_compilation_time:
            logger.warning("stage compilation took %.2f s",
                           time.time() - tic)

        # ---- build the schedule + instruction stream ----
        self.schedule = create_pipeline_schedule(
            schedule_name,
            num_stages=2 * num_stages if self.has_bwd else num_stages,
            num_meshes=num_stages,
            num_batch=num_micro_batches)
        self._emit()

    # ------------------------------------------------------------------
    # emission
    # ------------------------------------------------------------------
    def _stage_exec_for(self, stage_idx: int) -> StageExecutable:
        S = self.num_fwd_stages
        if stage_idx < S:
            return self.stage_execs[stage_idx]
        # backward stage: bwd of mesh (2S-1-stage_idx)
        mesh = 2 * S - 1 - stage_idx
        return self.stage_execs[S + mesh]

    def _apply_topo_order(self) -> List[int]:
        """Topological order of apply computations by cross-comp data deps.
        Cycles (mutual dependence, e.g. bidirectional norm clipping) raise —
        the compile driver re-partitions onto a single mesh in that case."""
        n = len(self.apply_execs)
        outs_of = {}
        for m, e in enumerate(self.apply_execs):
            if e is not None:
                for v in e.outvars:
                    outs_of[v] = m
        deps = {m: set() for m in range(n)}
        for m, e in enumerate(self.apply_execs):
            if e is None:
                continue
            for v in e.invars:
                src = outs_of.get(v)
                if src is not None and src != m:
                    deps[m].add(src)
        order, done = [], set()

        def visit(m, stack):
            if m in done:
                return
            if m in stack:
                raise ValueError(
                    "Cyclic cross-mesh dependency in apply_grad partition")
            stack.add(m)
            for d in deps[m]:
                visit(d, stack)
            stack.discard(m)
            done.add(m)
            order.append(m)

        for m in range(n):
            visit(m, set())
        return order

    def _emit(self):
        self._resharding_bytes = 0.0
        self._executed_resharding_bytes = 0.0
        self._executed_intra_mesh_bytes = 0.0
        # max per-link (per-device egress/ingress) bytes over all planned
        # cross-mesh transfers — the ISSUE 4 planner objective
        self._max_link_bytes = 0.0
        ginvar_idx = {v: i for i, v in enumerate(self.global_invars)}
        batch_var = {
            v for v, b in zip(self.global_invars, self.batch_invars) if b
        }
        instructions: List[PipelineInstruction] = []
        # key -> set of meshes currently holding the value
        location: Dict[Tuple[Var, int], OrderedSet] = {}
        # (var, inst, mesh) -> sharding the value currently has there
        sharding_at: Dict[Tuple[Var, int, int], Any] = {}

        def _compatible(s1, s2, ndim):
            if s1 is None or s2 is None:
                return True
            try:
                return s1.is_equivalent_to(s2, ndim)
            except Exception:  # pylint: disable=broad-except
                return s1 == s2

        # global invar placement (filled on demand)
        self.input_place: Dict[Var, List[Tuple[int, Any]]] = {}
        self.const_place: Dict[Var, List[Tuple[int, Any]]] = {}
        self.acc_allocs: List[Tuple[Var, int, Any, Any]] = []

        post_alias = {}
        for pre, post in self.grad_pairs:
            if pre in self.acc_info:
                _, summed, _ = self.acc_info[pre]
                post_alias[post] = summed

        def key_of(v, mb, exec_, first_mb):
            """Resolve the env key an invar reads from."""
            if v in self.acc_pairs:  # accumulator input
                if mb == first_mb:
                    return (v, -1)
                return (self.acc_pairs[v], -1)
            if v in post_alias:
                return (post_alias[v], -1)
            if v in ginvar_idx:
                return (v, mb) if v in batch_var else (v, -1)
            if v in self.consts_map:
                return (v, -1)
            return (v, mb)

        def ensure_on_mesh(key, mesh_id, dst_sharding, exec_name):
            v = key[0]
            ndim = len(getattr(v.aval, "shape", ()))
            if key not in location:
                # input / const / accumulator placed at launch
                if v in self.acc_pairs:
                    location[key] = OrderedSet([mesh_id])
                    sharding_at[(v, key[1], mesh_id)] = dst_sharding
                    return
                place_list = (self.input_place if v in ginvar_idx else
                              self.const_place).setdefault(v, [])
                if mesh_id not in [m for m, _ in place_list]:
                    place_list.append((mesh_id, dst_sharding))
                    sharding_at[(v, key[1], mesh_id)] = dst_sharding
                location[key] = OrderedSet([m for m, _ in place_list])
            if mesh_id not in location[key]:
                # ReplicatedDistributedArray analog (ref device_mesh.py:1697):
                # a non-batch global input or const consumed by stages on
                # several meshes (e.g. a tied embedding table used by both
                # the first and last stage) is placed on EACH mesh directly
                # from the host at launch — one logical tensor, multiple
                # residencies — instead of a serialized cross-mesh hop.
                replicable = (v in self.consts_map or
                              (v in ginvar_idx and v not in batch_var))
                if replicable and v not in self.acc_pairs:
                    place_list = (self.input_place if v in ginvar_idx else
                                  self.const_place).setdefault(v, [])
                    if mesh_id not in [m for m, _ in place_list]:
                        place_list.append((mesh_id, dst_sharding))
                    location[key].add(mesh_id)
                    sharding_at[(v, key[1], mesh_id)] = dst_sharding
                    return
                src = next(iter(location[key]))
                inst = PipelineInstruction(PipelineInstType.RESHARD,
                                           var_key=key, src_mesh=src,
                                           dst_mesh=mesh_id,
                                           dst_sharding=dst_sharding,
                                           info=exec_name)
                # plan the cross-mesh transfer (tile coverage + local
                # allgather rewrite) for accounting/reporting
                src_sh = sharding_at.get((v, key[1], src))
                if src_sh is not None and hasattr(v.aval, "shape"):
                    try:
                        from alpa_tpu.pipeline_parallel. \
                            cross_mesh_resharding import (ReshardingTask,
                                                          plan_resharding)
                        inst.src_sharding = src_sh
                        inst.plan = plan_resharding(
                            tuple(v.aval.shape), v.aval.dtype.itemsize,
                            src_sh, dst_sharding)
                        self._resharding_bytes += inst.plan.transfer_bytes
                        self._max_link_bytes = max(
                            self._max_link_bytes, inst.plan.max_link_bytes,
                            inst.plan.max_link_bytes_broadcast)
                        # pre-built, reusable executor: planned execution
                        # modes replay this task every step instead of
                        # re-resolving it on the hot path
                        inst.task = ReshardingTask(inst.plan, dst_sharding)
                    except Exception as e:  # pylint: disable=broad-except
                        # the planned execution mode silently degrades to
                        # device_put for this transfer — keep it visible
                        logger.warning(
                            "resharding plan for %s (%s -> mesh %d) "
                            "failed: %s", v, exec_name, mesh_id, e)
                        inst.plan = None
                instructions.append(inst)
                location[key].add(mesh_id)
                sharding_at[(v, key[1], mesh_id)] = dst_sharding
                return
            # present on this mesh: reconcile layout if needed
            cur = sharding_at.get((v, key[1], mesh_id))
            if not _compatible(cur, dst_sharding, ndim):
                instructions.append(
                    PipelineInstruction(PipelineInstType.RESHARD,
                                        var_key=key, src_mesh=mesh_id,
                                        dst_mesh=mesh_id,
                                        dst_sharding=dst_sharding,
                                        info=f"relayout:{exec_name}"))
                sharding_at[(v, key[1], mesh_id)] = dst_sharding

        first_mb_of_stage = {}

        def emit_run(exec_: StageExecutable, mb: int, mesh_id: int):
            first_mb = first_mb_of_stage.setdefault(id(exec_), mb)
            in_keys = []
            for pos, v in enumerate(exec_.invars):
                k = key_of(v, mb, exec_, first_mb)
                if v in self.acc_pairs and k == (v, -1):
                    # zero-allocated accumulator
                    if not any(a[0] is v for a in self.acc_allocs):
                        self.acc_allocs.append(
                            (v, mesh_id, v.aval, exec_.in_shardings[pos]))
                    location[(v, -1)] = OrderedSet([mesh_id])
                    sharding_at[(v, -1, mesh_id)] = exec_.in_shardings[pos]
                ensure_on_mesh(k, mesh_id, exec_.in_shardings[pos],
                               exec_.name)
                in_keys.append(k)
            out_keys = []
            for pos, ov in enumerate(exec_.outvars):
                k = (ov, -1) if ov in getattr(exec_.comp, "_acc_out_map",
                                              {}) else (ov, mb)
                out_keys.append(k)
                location[k] = OrderedSet([mesh_id])
                sharding_at[(k[0], k[1], mesh_id)] = exec_.out_shardings[pos]
            instructions.append(
                PipelineInstruction(PipelineInstType.RUN,
                                    stage_id=self.stage_execs.index(exec_)
                                    if exec_ in self.stage_execs else -1,
                                    micro_batch=mb,
                                    input_keys=in_keys,
                                    output_keys=out_keys,
                                    dst_mesh=mesh_id,
                                    info=exec_.name))
            instructions[-1].executable = exec_

        for tick in self.schedule.schedules:
            for mesh_id, task in enumerate(tick):
                if task is None:
                    continue
                mb, stage_idx = task
                exec_ = self._stage_exec_for(stage_idx)
                if not exec_.invars and not exec_.outvars:
                    continue
                emit_run(exec_, mb, mesh_id)

        # apply-grad runs, in dependency order (one apply comp may consume
        # another's exported values, e.g. a global grad-norm scalar)
        for m in self._apply_topo_order():
            exec_ = self.apply_execs[m]
            if exec_ is None:
                continue
            emit_run(exec_, -1, m)

        # ---- output specs ----
        self.output_specs = []
        sub_outvars = list(self.global_outvars)
        for v in sub_outvars:
            if isinstance(v, Literal):
                self.output_specs.append(("literal", v.val))
                continue
            k = (post_alias.get(v, v), -1)
            if k in location:
                self.output_specs.append(
                    ("env", (k, next(iter(location[k])))))
            elif (v, 0) in location:
                # per-microbatch output (inference)
                meshes = [(mb, next(iter(location[(v, mb)])))
                          for mb in range(self.num_micro_batches)]
                self.output_specs.append(("concat", (v, meshes)))
            elif v in ginvar_idx:
                self.output_specs.append(("input", ginvar_idx[v]))
            else:
                raise ValueError(
                    f"Cannot trace global output {v} to a stage output")

        protected = set()
        for spec_kind, payload in self.output_specs:
            if spec_kind == "env":
                (k, m) = payload
                protected.add((k[0], k[1], m))
            elif spec_kind == "concat":
                v, meshes = payload
                for mb, m in meshes:
                    protected.add((v, mb, m))
        self.instructions = emit_free_instructions(instructions, protected)
        # pre-partitioned per-mesh worker streams (the reference's
        # per-host instruction lists, computed once at emit time)
        self._instruction_streams = partition_streams(
            self.instructions, self.num_meshes)
        self._acct_lock = threading.Lock()
        self._const_cache = None
        self._zero_exec_cache = None
        # register-file replay fast path (built lazily on first eligible
        # launch; see _ensure_lowered).  _register_programs maps lowering
        # mode ("registers" | "overlap") -> RegisterFileProgram; the two
        # modes share identical slot numbering (phase-1 lowering is
        # mode-independent) so the launch-time slot tables are built once.
        self._register_programs = {}
        self._register_program = None   # the "registers" program (tests)
        self._has_cross_mesh = any(
            i.opcode == PipelineInstType.RESHARD and
            i.src_mesh != i.dst_mesh for i in self.instructions)
        self._reg_input_loads = None
        self._reg_const_loads = None
        self._reg_acc_slots = None
        self._reg_output_specs = None
        # certified superoptimization (ISSUE 17): the one-shot rewrite
        # decision (analysis/superopt.py SuperoptOutcome) and, when a
        # rewrite was accepted in auto mode, the rewritten instruction
        # list every lowering mode shares (identical slot_of).
        self._superopt_outcome = None
        self._superopt_instructions = None
        self._warned_register_fallback = False
        # quiesce gate: fault.RecoveryManager pauses new launches and
        # waits out in-flight ones before snapshotting driver state
        self._launch_gate = threading.Event()
        self._launch_gate.set()
        self._inflight_launches = 0
        self._n_launches = 0    # the ``step`` of each pipeshard.step span
        self._quiesce_cv = threading.Condition()

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def launch_on_driver(self, *flat_args):
        # blocks while quiesced (recovery in progress): a launch racing
        # a mesh failure would dispatch onto dead devices
        self._launch_gate.wait()
        with self._quiesce_cv:
            self._inflight_launches += 1
        t0 = time.perf_counter()
        step_span = _ttrace.begin(
            "pipeshard.step", "runtime",
            {"step": self._n_launches} if _ttrace.enabled() else None)
        self._n_launches += 1
        try:
            return self._launch(*flat_args)
        except BaseException:
            # post-mortem timeline of the instructions leading up to the
            # failure (no-op when the ring is empty or already dumped)
            _flight.auto_dump("pipeshard step raised")
            raise
        finally:
            _ttrace.end(step_span)
            _DISPATCH_SECONDS.observe(time.perf_counter() - t0)
            with self._quiesce_cv:
                self._inflight_launches -= 1
                self._quiesce_cv.notify_all()

    def quiesce(self, timeout: Optional[float] = None) -> bool:
        """Pause new launches and wait until in-flight pipeshard work
        drains (the recovery state machine's pre-snapshot step).
        Returns True when the driver reached a quiescent point within
        ``timeout``; launches stay blocked until :meth:`resume`."""
        self._launch_gate.clear()
        with self._quiesce_cv:
            drained = self._quiesce_cv.wait_for(
                lambda: self._inflight_launches == 0, timeout)
        if drained:
            try:
                self.sync()  # drain on-device queues too
            except Exception:  # pylint: disable=broad-except
                # a dead mesh cannot sync — quiescing must still succeed
                # driver-side so recovery can proceed
                logger.exception("quiesce: device sync failed")
        return bool(drained)

    def resume(self):
        """Re-open the launch gate after recovery."""
        self._launch_gate.set()

    def _launch(self, *flat_args):
        env: Dict[Tuple[Var, int], Dict[int, Any]] = {}
        n_mb = self.num_micro_batches
        # executed-resharding accounting is per step, comparable to the
        # per-step planned bytes in get_resharding_report
        self._executed_resharding_bytes = 0.0
        self._executed_intra_mesh_bytes = 0.0
        exec_mode = global_config.resharding_execution
        if exec_mode not in ("device_put", "planned"):
            raise ValueError(
                "global_config.resharding_execution must be 'device_put' "
                f"or 'planned', got {exec_mode!r}")
        multiprocess = jax.process_count() > 1
        # Register-file replay fast path (ISSUE 2): the lowered program
        # does no dict hashing / sharding resolution per call.  Fault
        # sites, trace collection, and race checking are NOT exclusions
        # (ISSUE 6): they compile in as per-node hooks on the graph
        # executor, so instrumented launches run the same fast path.
        # Only planned resharding and the multi-process collective-order
        # contract still take the interpreter below.
        dmode = getattr(global_config, "pipeline_dispatch_mode", "auto")
        reg_ok = not multiprocess and exec_mode == "device_put"
        if dmode in ("registers", "overlap") and not reg_ok and \
                not self._warned_register_fallback:
            self._warned_register_fallback = True
            logger.warning(
                "pipeline_dispatch_mode=%r requested but the "
                "launch is not eligible (multiprocess or planned "
                "resharding); falling back to the "
                "instruction interpreter", dmode)
        # overlap mode (ISSUE 4): replay the dataflow graph with eager
        # async cross-mesh transfers.  Eligible when the register path is
        # eligible AND there is actual cross-mesh traffic to overlap.
        overlap_ok = (reg_ok and self.num_meshes > 1 and
                      self._has_cross_mesh and
                      getattr(global_config, "overlap_resharding", True))
        if dmode == "overlap" and reg_ok and not overlap_ok and \
                not self._warned_register_fallback:
            self._warned_register_fallback = True
            logger.warning(
                "pipeline_dispatch_mode='overlap' requested but there is "
                "nothing to overlap (single mesh, no cross-mesh RESHARDs, "
                "or overlap_resharding disabled); using register dispatch")
        if reg_ok and dmode in ("auto", "registers", "overlap"):
            use_overlap = overlap_ok and dmode in ("auto", "overlap")
            return self._launch_registers(
                flat_args, mode="overlap" if use_overlap else "registers")
        # multiprocess + "planned": cross-process RESHARD instructions
        # drive the tile plan via ReshardingTask.run_multiprocess (packed
        # tiles cross the boundary, not a full-array gather); everything
        # else stays host-mediated put_global
        mp_planned = multiprocess and exec_mode == "planned"
        if multiprocess:
            from alpa_tpu.distributed import host_gather, put_global
            _put = put_global
            exec_mode = "device_put"
        else:
            _put = jax.device_put

        # place global inputs
        for v, places in self.input_place.items():
            i = self.global_invars.index(v)
            arg = flat_args[i]
            if self.batch_invars[i]:
                if n_mb == 1:
                    mbs = [arg]
                elif isinstance(arg, jax.Array):
                    from alpa_tpu.distributed import is_process_local
                    if multiprocess and not is_process_local(arg):
                        # global array: collective gather (path choice uses
                        # only global metadata, so processes stay aligned)
                        mbs = np.split(host_gather(arg), n_mb, axis=0)
                    elif multiprocess:
                        mbs = np.split(np.asarray(arg), n_mb, axis=0)
                    else:
                        # split on device: avoids a blocking D2H round trip
                        mbs = jnp.split(arg, n_mb, axis=0)
                else:
                    mbs = np.split(np.asarray(arg), n_mb, axis=0)
                for mb in range(n_mb):
                    slot = env.setdefault((v, mb), {})
                    for mesh_id, sharding in places:
                        slot[mesh_id] = _put(mbs[mb], sharding)
            else:
                slot = env.setdefault((v, -1), {})
                for mesh_id, sharding in places:
                    slot[mesh_id] = _put(arg, sharding)

        # place consts (cached across calls)
        if self._const_cache is None:
            self._const_cache = {}
            for v, places in self.const_place.items():
                val = self.consts_map[v]
                slot = {}
                for mesh_id, sharding in places:
                    slot[mesh_id] = _put(val, sharding)
                self._const_cache[v] = slot
        for v, slot in self._const_cache.items():
            env[(v, -1)] = dict(slot)

        # zero accumulators (compiled once, reused every step)
        self._ensure_zero_execs()
        for mesh_id, vs, compiled in self._zero_exec_cache:
            bufs = compiled()
            for v, buf in zip(vs, bufs):
                env.setdefault((v, -1), {})[mesh_id] = buf

        # interpret.  Two dispatch modes (global_config.
        # pipeline_dispatch_mode):
        #
        # * "sequential": one Python loop over the global stream — the
        #   only collective-safe mode multi-process, where every process
        #   must issue collectives in the same order.
        # * "threaded": the emitter's pre-partitioned PER-MESH instruction
        #   streams (runtime_emitter.partition_streams — the
        #   single-controller analog of the reference's pre-pushed
        #   per-worker instruction lists) each run on their own worker
        #   thread, synchronized by cross-stream dependency events, so a
        #   slow enqueue on one mesh never stalls dispatch onto another.
        #
        # "auto" picks threaded for single-process multi-mesh, sequential
        # otherwise.  Per-opcode wall time is recorded either way so the
        # driver-side dispatch overhead (SURVEY §7 hard part 5) is
        # measurable: on an async backend RUN returns as soon as the work
        # is enqueued, so ``last_dispatch_stats`` bounds the
        # per-instruction driver cost.
        collect = global_config.collect_trace
        stats = {"RUN": [0, 0.0], "RESHARD": [0, 0.0], "FREE": [0, 0.0]}
        ctx = (env, _put, exec_mode, mp_planned, collect, stats)
        dmode = getattr(global_config, "pipeline_dispatch_mode", "auto")
        use_threads = (dmode == "threaded" or
                       (dmode == "auto" and self.num_meshes > 1)) \
            and not multiprocess
        loop_tic = time.perf_counter()
        if use_threads:
            self._run_streams_threaded(ctx)
        else:
            for inst_idx, inst in enumerate(self.instructions):
                inst_tic = time.perf_counter()
                self._exec_inst(inst, ctx, inst_idx)
                s = stats[inst.opcode.name]
                s[0] += 1
                s[1] += time.perf_counter() - inst_tic
        loop_s = time.perf_counter() - loop_tic
        n_inst = max(1, len(self.instructions))
        self.last_dispatch_stats = {
            "n_instructions": len(self.instructions),
            "loop_s": loop_s,
            "per_inst_us": loop_s / n_inst * 1e6,
            "mode": "threaded" if use_threads else "sequential",
            "by_opcode": {k: {"n": n, "s": t}
                          for k, (n, t) in stats.items()},
        }

        # collect outputs
        outs = []
        for kind, payload in self.output_specs:
            if kind == "literal":
                outs.append(payload)
            elif kind == "env":
                k, m = payload
                outs.append(env[k][m])
            elif kind == "input":
                outs.append(flat_args[payload])
            else:  # concat over microbatches (inference outputs)
                v, meshes = payload
                vals = [env[(v, mb)][m] for mb, m in meshes]
                if n_mb == 1:
                    outs.append(vals[0])
                elif vals[0].ndim >= 1:
                    # axis 0 must be the (microbatched) batch dim
                    outs.append(jnp.concatenate(
                        [jax.device_put(
                            x, self.mesh_group[meshes[0][1]]
                            .flat_devices[0]) for x in vals], axis=0))
                else:
                    raise ValueError(
                        "A scalar output of a pipelined forward-only "
                        "function is ambiguous with num_micro_batches > 1 "
                        "(per-microbatch reduction cannot be recombined); "
                        "return per-example values or use "
                        "num_micro_batches=1.")
        return outs

    def _ensure_zero_execs(self):
        """Compile (once) the per-mesh zero-accumulator allocators."""
        if self._zero_exec_cache is not None:
            return
        self._zero_exec_cache = []
        by_mesh: Dict[int, List] = {}
        for v, mesh_id, aval, sharding in self.acc_allocs:
            by_mesh.setdefault(mesh_id, []).append((v, aval, sharding))
        for mesh_id, items in by_mesh.items():
            avals = [a for _, a, _ in items]
            shardings = [s for _, _, s in items]
            compiled = (jax.jit(
                lambda avs=tuple(avals): [
                    jnp.zeros(a.shape, a.dtype) for a in avs
                ],
                out_shardings=shardings).lower().compile())
            self._zero_exec_cache.append(
                (mesh_id, [v for v, _, _ in items], compiled))

    # ------------------------------------------------------------------
    # register-file replay fast path (ISSUE 2)
    # ------------------------------------------------------------------
    def _overlap_window(self) -> int:
        """The in-flight transfer window for overlap lowering: the
        explicit knob when set, otherwise the schedule's hint."""
        w = int(getattr(global_config, "overlap_inflight_window", 0) or 0)
        if w > 0:
            return w
        hint = getattr(self.schedule, "overlap_window_hint", None)
        return int(hint()) if callable(hint) else max(2, self.num_meshes)

    def _make_lowerer(self, mode: str = "registers"):
        """Build the lowering closure for one mode: derives the static
        sharding seed, opt-state/provenance/protected key sets, and the
        equivalence reference from THIS executable, and returns
        ``lower(instructions) -> RegisterFileProgram``.  Shared by
        ``_ensure_lowered`` and the superopt engine (ISSUE 17), which
        lowers candidate instruction lists through the same context —
        so a rewritten program carries coherent OpHook/dataflow/
        PlanModel metadata and is verified against the same reference.
        """
        from alpa_tpu.pipeline_parallel.runtime_emitter import (
            lower_to_register_file)
        n_mb = self.num_micro_batches
        ginvar_idx = {v: i for i, v in enumerate(self.global_invars)}

        # static sharding seed: everything placed at launch
        preplaced: Dict[Tuple[Var, int, int], Any] = {}
        for v, places in self.input_place.items():
            if self.batch_invars[ginvar_idx[v]]:
                for mesh_id, sh in places:
                    for mb in range(n_mb):
                        preplaced[(v, mb, mesh_id)] = sh
            else:
                for mesh_id, sh in places:
                    preplaced[(v, -1, mesh_id)] = sh
        for v, places in self.const_place.items():
            for mesh_id, sh in places:
                preplaced[(v, -1, mesh_id)] = sh
        for v, mesh_id, _aval, sh in self.acc_allocs:
            preplaced[(v, -1, mesh_id)] = sh

        # optimizer-state inputs, classified by pytree path, so the
        # verifier's liveness pass can attribute resident bytes to
        # alpa_opt_state_bytes{mesh} and statically prove the ZeRO
        # saving (ISSUE 10)
        opt_state_keys = set()
        if self.invar_paths:
            from alpa_tpu.shard_parallel.auto_sharding import (
                is_opt_state_path)
            for v, places in self.input_place.items():
                if not is_opt_state_path(self.invar_paths.get(v, "")):
                    continue
                for mesh_id, _sh in places:
                    opt_state_keys.add((v, -1, mesh_id))

        # provenance seed for the numerics certification (ISSUE 14):
        # classify every launch-placed value by its pytree path so the
        # precision-flow analysis can prove params / opt state never
        # cross a lossy hop anywhere along their flow
        provenance_keys: Dict[Tuple[Var, int, int], str] = {}
        if self.invar_paths:
            from alpa_tpu.shard_parallel.auto_sharding import (
                is_opt_state_path, is_param_path)
            for v, places in self.input_place.items():
                path = self.invar_paths.get(v, "")
                if is_opt_state_path(path):
                    prov = "opt_state"
                elif is_param_path(path):
                    prov = "param"
                else:
                    prov = "activation"
                if self.batch_invars[ginvar_idx[v]]:
                    for mesh_id, _sh in places:
                        for mb in range(n_mb):
                            provenance_keys[(v, mb, mesh_id)] = prov
                else:
                    for mesh_id, _sh in places:
                        provenance_keys[(v, -1, mesh_id)] = prov
        for v, mesh_id, _aval, _sh in self.acc_allocs:
            provenance_keys[(v, -1, mesh_id)] = "gradient"

        # program outputs are never FREEd by design — the plan
        # verifier's leak analysis must not flag them (ISSUE 8)
        protected = set()
        for spec_kind, payload in self.output_specs:
            if spec_kind == "env":
                (k, m) = payload
                protected.add((k[0], k[1], m))
            elif spec_kind == "concat":
                v, meshes = payload
                for mb, m in meshes:
                    protected.add((v, mb, m))
        # reference decomposition for the translation validation
        # (ISSUE 15): the driver's pre-lowering RUN stream as serial
        # stage applications over (var, microbatch) value keys —
        # deliberately derived here, before lowering, so the certifier
        # proves the register program against an independent artifact
        superopt_active = getattr(
            global_config, "superopt_mode", "off") in ("suggest", "auto")
        equiv_reference = None
        if (getattr(global_config, "verify_plans", "warn") != "off" or
                superopt_active) and \
                getattr(global_config, "verify_plans_equiv",
                        "warn") != "off":
            from alpa_tpu.analysis import equivalence as _equiv
            equiv_reference = _equiv.build_reference(
                self.instructions, n_mb)

        def _lower(insts):
            # the equivalence reference stays derived from the ORIGINAL
            # driver stream above, so translation validation proves any
            # superopt rewrite still computes the source jaxpr.  With
            # superopt active the verdict gate needs verified programs,
            # so verify_plans=off is upgraded to warn for the lowering.
            old_verify = global_config.verify_plans
            try:
                if superopt_active and old_verify == "off":
                    global_config.verify_plans = "warn"
                return lower_to_register_file(
                    insts, preplaced, mode=mode,
                    overlap_window=self._overlap_window(),
                    protected_keys=frozenset(protected),
                    opt_state_keys=frozenset(opt_state_keys),
                    provenance_keys=provenance_keys,
                    equiv_reference=equiv_reference)
            finally:
                global_config.verify_plans = old_verify

        return _lower

    def _ensure_lowered(self, mode: str = "registers"):
        """Lower the instruction list into a RegisterFileProgram (once
        per mode) and precompute the launch-time slot tables: input
        loads, const loads, accumulator slots, and output slots — so the
        replay loop touches only integer-indexed lists.  Phase-1 lowering
        is mode-independent, so every mode's program has identical
        ``slot_of`` and the slot tables are shared."""
        prog = self._register_programs.get(mode)
        if prog is not None:
            return prog
        _lower = self._make_lowerer(mode)
        superopt_active = getattr(
            global_config, "superopt_mode", "off") in ("suggest", "auto")
        prog = None
        if superopt_active and self._superopt_outcome is None:
            prog = self._run_superopt(_lower)
        if prog is None:
            prog = _lower(self._superopt_instructions
                          if self._superopt_instructions is not None
                          else self.instructions)
        self._register_programs[mode] = prog
        if mode == "registers":
            self._register_program = prog
        # for a capture's reader that holds no executable
        # (``Capture.pipeline_time``): nothing is computed here
        _device_time.register_pipeline(
            self, PipeshardDriverExecutable._describe_pipeline)
        slot_of = prog.slot_of
        if self._reg_input_loads is not None:
            return prog
        n_mb = self.num_micro_batches
        ginvar_idx = {v: i for i, v in enumerate(self.global_invars)}

        # input placement: (flat arg index, is_batch, [(slot, sharding,
        # microbatch)]) — resolved once, replayed every launch
        self._reg_input_loads = []
        for v, places in self.input_place.items():
            i = ginvar_idx[v]
            entries = []
            if self.batch_invars[i]:
                for mesh_id, sh in places:
                    for mb in range(n_mb):
                        entries.append((slot_of[(v, mb, mesh_id)], sh, mb))
            else:
                for mesh_id, sh in places:
                    entries.append((slot_of[(v, -1, mesh_id)], sh, -1))
            self._reg_input_loads.append(
                _InputLoad(i, self.batch_invars[i], entries))

        # outputs: mirror output_specs with slots
        out_specs = []
        for kind, payload in self.output_specs:
            if kind == "literal":
                out_specs.append(("literal", payload))
            elif kind == "env":
                k, m = payload
                out_specs.append(("slot", slot_of[(k[0], k[1], m)]))
            elif kind == "input":
                out_specs.append(("input", payload))
            else:  # concat
                v, meshes = payload
                out_specs.append(
                    ("concat", ([slot_of[(v, mb, m)] for mb, m in meshes],
                                meshes)))
        self._reg_output_specs = out_specs
        return prog

    def _run_superopt(self, lower):
        """One-shot certified-superoptimization decision (ISSUE 17;
        analysis/superopt.py).  Lowers the baseline, runs the cached/
        searched rewrite engine with the seven-analysis verdict gate,
        and — in auto mode with an accepted rewrite — stores the
        rewritten instruction list so every later lowering mode shares
        it (identical ``slot_of``).  Returns the program to use for the
        calling mode, or None to fall through to a plain lowering."""
        from alpa_tpu.analysis import superopt as _superopt
        from alpa_tpu.analysis.plan_verifier import PlanVerdict
        smode = getattr(global_config, "superopt_mode", "off")
        baseline = lower(self.instructions)

        def _verify(p, _insts):
            v = getattr(p, "verdict", None)
            return v if v is not None else PlanVerdict()

        try:
            outcome = _superopt.run_superopt(
                list(self.instructions), self.num_meshes, baseline,
                lower, _verify, mode=smode)
        except Exception:  # pylint: disable=broad-except
            logger.exception(
                "superopt: engine failed; keeping the baseline plan")
            self._superopt_outcome = _superopt.SuperoptOutcome(
                mode=smode, searched=True, cache_hit=False,
                accepted=False,
                layout=_superopt.identity_layout(
                    len(self.instructions)),
                baseline_score=_superopt.PlanScore(0.0, ()),
                best_score=_superopt.PlanScore(0.0, ()),
                baseline_fingerprint=baseline.fingerprint(),
                fingerprint=None, rejected=[("superopt", "engine-error")],
                log=[])
            return baseline
        self._superopt_outcome = outcome
        if smode == "auto" and outcome.accepted and \
                outcome.instructions is not None:
            self._superopt_instructions = list(outcome.instructions)
            return outcome.program
        return baseline

    def get_superopt_text(self) -> str:
        """Human-readable superopt decision report (``superopt.txt``
        in monitoring.dump_debug_info; scripts/perf_tool.py superopt)."""
        from alpa_tpu.analysis import superopt as _superopt
        return _superopt.format_superopt_report(self._superopt_outcome)

    def _launch_registers(self, flat_args, mode: str = "registers"):
        """Replay the lowered register-file program: flat list reads and
        writes only — the per-instruction driver cost is the compiled
        executables' C++ dispatch plus the pre-resolved transfers.  In
        ``overlap`` mode the program is the dataflow-graph replay with
        eager async cross-mesh transfers (ISSUE 4)."""
        prog = self._ensure_lowered(mode)
        regs: List[Any] = [None] * prog.num_slots
        n_mb = self.num_micro_batches

        # inputs, constants and zeroed accumulators into their slots (this
        # and the replay: the step's two phases inside ``pipeshard.step``)
        with _ttrace.span("pipeshard.place-inputs", "runtime"):
            # place global inputs in one batched device_put
            put_vals, put_shs, put_slots = [], [], []
            # state that arrives on another device set than it is wanted
            # on, or there in another layout, counted from the second step
            # on: the first places what the caller hands over, every later
            # one what the plan left on the wrong mesh or in the wrong
            # sharding
            count_moved = self._n_launches > 1
            moved_arrays = moved_bytes = relaid_arrays = relaid_bytes = 0
            for load in self._reg_input_loads:
                arg = flat_args[load.arg_idx]
                entries = load.entries
                if load.is_batch:
                    if n_mb == 1:
                        mbs = [arg]
                    elif isinstance(arg, jax.Array):
                        mbs = jnp.split(arg, n_mb, axis=0)
                    else:
                        mbs = np.split(np.asarray(arg), n_mb, axis=0)
                    for s, sh, mb in entries:
                        put_vals.append(mbs[mb])
                        put_shs.append(sh)
                        put_slots.append(s)
                else:
                    for s, sh, _mb in entries:
                        put_vals.append(arg)
                        put_shs.append(sh)
                        put_slots.append(s)
                    if count_moved and isinstance(arg, jax.Array):
                        src = arg.sharding
                        if src is not load.src_sharding:
                            load.src_sharding = src
                            load.n_moved = load.n_relaid = 0
                            for _s, sh, _mb in entries:
                                if sh.device_set != src.device_set:
                                    load.n_moved += 1
                                elif not src.is_equivalent_to(sh, arg.ndim):
                                    load.n_relaid += 1
                        if load.n_moved:
                            moved_arrays += load.n_moved
                            moved_bytes += load.n_moved * arg.nbytes
                        if load.n_relaid:
                            relaid_arrays += load.n_relaid
                            relaid_bytes += load.n_relaid * arg.nbytes
            if moved_arrays:
                LAUNCH_MOVED_ARRAYS.inc(moved_arrays)
                LAUNCH_MOVED_BYTES.inc(moved_bytes)
            if relaid_arrays:
                LAUNCH_RELAID_ARRAYS.inc(relaid_arrays)
                LAUNCH_RELAID_BYTES.inc(relaid_bytes)
            if put_vals:
                # one call for every leaf: those already laid out as
                # wanted take jax's fast path and a replicated one crosses
                # to another mesh device to device, but one that is wanted
                # in another sharding goes through the host
                # (``shard_sharded_device_array_slow_path``), and this
                # call waits for it
                with _ttrace.span("pipeshard.place-inputs.put", "runtime"):
                    placed = jax.device_put(put_vals, put_shs)
                for s, o in zip(put_slots, placed):
                    regs[s] = o

            # consts (placed once, re-slotted per launch)
            if self._reg_const_loads is None:
                slot_of = prog.slot_of
                loads = []
                for v, places in self.const_place.items():
                    val = self.consts_map[v]
                    for mesh_id, sh in places:
                        loads.append((slot_of[(v, -1, mesh_id)],
                                      jax.device_put(val, sh)))
                self._reg_const_loads = loads
            for s, a in self._reg_const_loads:
                regs[s] = a

            # zero accumulators (compiled once; slots resolved once)
            self._ensure_zero_execs()
            if self._reg_acc_slots is None:
                slot_of = prog.slot_of
                self._reg_acc_slots = [
                    (compiled, [slot_of[(v, -1, mesh_id)] for v in vs])
                    for mesh_id, vs, compiled in self._zero_exec_cache
                ]
            with _ttrace.span("pipeshard.place-inputs.zero", "runtime"):
                for compiled, slots in self._reg_acc_slots:
                    for s, buf in zip(slots, compiled()):
                        regs[s] = buf

        # replay
        loop_tic = time.perf_counter()
        with _ttrace.span("pipeshard.replay", "runtime"):
            prog.execute(regs)
        loop_s = time.perf_counter() - loop_tic
        n_inst = max(1, prog.n_instructions)
        self.last_dispatch_stats = {
            "n_instructions": prog.n_instructions,
            "n_ops": len(prog.ops),
            "loop_s": loop_s,
            "per_inst_us": loop_s / n_inst * 1e6,
            "mode": prog.mode,
            # hook families compiled into this replay ("trace"/"fault"/
            # "race"/"flight"; empty = raw closures, zero added branches)
            "hooks": prog.last_hooks,
            "by_opcode": {k: {"n": v, "s": 0.0}
                          for k, v in prog.by_opcode.items()},
        }
        if prog.mode == "overlap":
            busy = prog.run_stats["transfer_busy_s"]
            blocked = prog.run_stats["wait_blocked_s"]
            frac = max(0.0, min(1.0, 1.0 - blocked / busy)) if busy > 0 \
                else 1.0
            self.last_dispatch_stats.update(
                n_cross_mesh=prog.n_cross_mesh,
                n_hoisted=prog.n_hoisted,
                n_launches=prog.n_launches,
                overlap_window=prog.overlap_window,
                transfer_busy_s=busy,
                wait_blocked_s=blocked,
                overlap_fraction=frac,
            )
            from alpa_tpu.pipeline_parallel.runtime_emitter import (
                record_overlap_step)
            record_overlap_step(self.last_dispatch_stats)

        # collect outputs
        outs = []
        for kind, payload in self._reg_output_specs:
            if kind == "literal":
                outs.append(payload)
            elif kind == "slot":
                outs.append(regs[payload])
            elif kind == "input":
                outs.append(flat_args[payload])
            else:  # concat over microbatches (inference outputs)
                slots, meshes = payload
                vals = [regs[s] for s in slots]
                if n_mb == 1:
                    outs.append(vals[0])
                elif vals[0].ndim >= 1:
                    outs.append(jnp.concatenate(
                        [jax.device_put(
                            x, self.mesh_group[meshes[0][1]]
                            .flat_devices[0]) for x in vals], axis=0))
                else:
                    raise ValueError(
                        "A scalar output of a pipelined forward-only "
                        "function is ambiguous with num_micro_batches > 1 "
                        "(per-microbatch reduction cannot be recombined); "
                        "return per-example values or use "
                        "num_micro_batches=1.")
        return outs

    def _exec_inst(self, inst, ctx, idx: int = -1):
        """Execute one pipeline instruction (shared by the sequential loop
        and the per-stream worker threads).  ``idx`` is the global
        instruction index, recorded in flight-recorder events."""
        collect = ctx[4]
        # per-instruction span on the destination mesh's track (the
        # interpreter analog of the register replay's op_meta spans).
        # collect_trace records through the recorder even when the
        # telemetry master switch is off — same contract as the graph
        # executor's trace hook — feeding dump_stage_execution_trace.
        trace_on = _ttrace.enabled() or collect
        flight_on = _flight.enabled()
        if not (trace_on or flight_on):
            self._exec_inst_inner(inst, ctx)
            return
        opname = inst.opcode.name
        mesh = (inst.free_keys[0][2]
                if opname == "FREE" and inst.free_keys
                else inst.dst_mesh)
        name = f"{opname} {inst.info}" if inst.info else opname
        span = (_ttrace.get_recorder().span(
                    name, "instruction", None, f"mesh {mesh}")
                if trace_on else contextlib.nullcontext())
        if not flight_on:
            with span:
                self._exec_inst_inner(inst, ctx)
            return
        rec = _flight.get_recorder()
        t0 = _flight.now_us()
        try:
            with span:
                self._exec_inst_inner(inst, ctx)
        except BaseException as e:
            rec.record("exec", name, mesh, idx, (), t0, _flight.now_us(),
                       f"error:{type(e).__name__}")
            raise
        rec.record("exec", name, mesh, idx, (), t0, _flight.now_us(),
                   "ok")

    def _exec_inst_inner(self, inst, ctx):
        env, _put, exec_mode, mp_planned, _collect, _stats = ctx
        if inst.opcode == PipelineInstType.RUN:
            exec_ = inst.executable
            args = [env[k][inst.dst_mesh] for k in inst.input_keys]
            # Safety net: the emitter models shardings statically; any
            # divergence (logged) is reconciled here with a device_put.
            for i, (a, s) in enumerate(zip(args, exec_.in_shardings)):
                if (isinstance(a, jax.Array) and
                        not a.sharding.is_equivalent_to(s, a.ndim)):
                    # Happens when one RUN needs the same value in two
                    # layouts (env holds one layout per mesh).
                    logger.debug(
                        "emit-model sharding miss: %s arg[%d] %s -> %s",
                        inst.info, i, a.sharding.spec, s.spec)
                    args[i] = _put(a, s)
            if fault.instrumented():
                # Donated-buffer stages are NOT idempotent (a re-run
                # would read freed inputs): only injected faults — which
                # fire before the real execution — are retried there.
                outs = fault.call_with_retry(
                    lambda: (fault.fire("stage_launch", stage=inst.info,
                                        mesh_id=inst.dst_mesh),
                             exec_.compiled(*args))[1],
                    site="stage_launch",
                    idempotent=not exec_.donate_idx)
            else:
                outs = exec_.compiled(*args)
            for k, o in zip(inst.output_keys, outs):
                env.setdefault(k, {})[inst.dst_mesh] = o
        elif inst.opcode == PipelineInstType.RESHARD:
            val = env[inst.var_key][inst.src_mesh]

            def transfer():
                fault.fire("cross_mesh_send", var=str(inst.var_key[0]),
                           src_mesh=inst.src_mesh, dst_mesh=inst.dst_mesh)
                if (mp_planned and inst.src_mesh != inst.dst_mesh and
                        inst.plan is not None):
                    if inst.task is None:
                        from alpa_tpu.pipeline_parallel. \
                            cross_mesh_resharding import ReshardingTask
                        inst.task = ReshardingTask(inst.plan,
                                                   inst.dst_sharding)
                    env[inst.var_key][inst.dst_mesh] = \
                        inst.task.run_multiprocess(val)
                elif (exec_mode == "planned" and
                      inst.src_mesh != inst.dst_mesh and
                      inst.plan is not None):
                    # Drive the tile plan literally (per-tile routed
                    # transfers; send_recv or broadcast leg choice from
                    # global_config.resharding_mode, ref :418/:935).
                    if inst.task is None:
                        from alpa_tpu.pipeline_parallel. \
                            cross_mesh_resharding import ReshardingTask
                        inst.task = ReshardingTask(inst.plan,
                                                   inst.dst_sharding)
                    mode = ("broadcast" if global_config.resharding_mode ==
                            "broadcast" else "tiled")
                    env[inst.var_key][inst.dst_mesh] = inst.task.run(
                        val, mode)
                else:
                    env[inst.var_key][inst.dst_mesh] = _put(
                        val, inst.dst_sharding)
                    return
                rep = inst.task.last_report
                with self._acct_lock:
                    self._executed_resharding_bytes += rep.cross_mesh_bytes
                    self._executed_intra_mesh_bytes += rep.intra_mesh_bytes

            if fault.instrumented():
                # a transfer reads the source value functionally:
                # re-running after a failure is safe single-process; the
                # multiprocess collective path must stay lock-step, so
                # it only gets detection (no blind re-runs)
                fault.call_with_retry(transfer, site="cross_mesh_send",
                                      idempotent=not mp_planned)
            else:
                transfer()
        else:  # FREE
            for (v, i, m) in inst.free_keys:
                d = env.get((v, i))
                if d is not None:
                    d.pop(m, None)

    def _run_streams_threaded(self, ctx):
        """Per-mesh worker threads over the emitter's pre-partitioned
        instruction streams.

        Each worker executes its stream in order; cross-stream data and
        anti-dependencies (see runtime_emitter.partition_streams) are
        waited on via per-instruction events.  All dependency edges point
        to earlier global indices, so workers cannot deadlock; an abort
        flag stops every stream promptly if one instruction raises.
        Single-process only: issuing collectives from reordered streams
        would violate the cross-process same-order contract.
        """
        streams = self._instruction_streams
        n = len(self.instructions)
        events = [threading.Event() for _ in range(n)]
        abort = threading.Event()
        errors: List[BaseException] = []
        stats = ctx[5]
        checker = None
        if global_config.debug_dispatch_races:
            # cached across steps (access extraction is per-executable
            # static work); violations reset per launch
            checker = getattr(self, "_race_checker", None)
            if checker is None:
                from alpa_tpu.pipeline_parallel.runtime_emitter import (
                    DispatchRaceChecker)
                checker = DispatchRaceChecker(self.instructions,
                                              streams.stream_of)
                self._race_checker = checker
            checker.reset()

        def worker(stream):
            local = {"RUN": [0, 0.0], "RESHARD": [0, 0.0], "FREE": [0, 0.0]}
            try:
                for idx in stream:
                    for dep in sorted(streams.deps.get(idx, ())):
                        while not events[dep].wait(0.05):
                            if abort.is_set():
                                return
                    if abort.is_set():
                        return
                    inst = self.instructions[idx]
                    accs = checker.begin(idx) if checker else None
                    tic = time.perf_counter()
                    try:
                        self._exec_inst(inst, ctx, idx)
                    finally:
                        if checker:
                            checker.end(idx, accs)
                    s = local[inst.opcode.name]
                    s[0] += 1
                    s[1] += time.perf_counter() - tic
                    events[idx].set()
            except BaseException as e:  # pylint: disable=broad-except
                errors.append(e)
                abort.set()
            finally:
                with self._acct_lock:
                    for k, (cnt, sec) in local.items():
                        stats[k][0] += cnt
                        stats[k][1] += sec

        threads = [
            threading.Thread(target=worker, args=(s,), daemon=True)
            for s in streams.streams if s
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        if checker is not None:
            checker.check()

    def __call__(self, *args):
        return self.launch_on_driver(*args)

    # ---- introspection ----
    def get_hlo_text(self) -> str:
        return "\n\n".join(
            f"=== {s.name} (mesh {s.mesh_id}) ===\n" +
            s.compiled.as_text() for s in self.stage_execs)

    def get_schedule_text(self) -> str:
        return self.schedule.pprint_schedule()

    def get_instruction_text(self) -> str:
        return "\n".join(repr(i) for i in self.instructions)

    def get_plan_verdict(self, mode: str = "registers"):
        """The static plan verifier's :class:`PlanVerdict` for the
        lowered program (ISSUE 8), lowering on demand when no launch
        has run yet.  None when ``verify_plans`` is off or lowering is
        impossible (e.g. multi-process)."""
        prog = self._register_programs.get(mode)
        if prog is None:
            from alpa_tpu.analysis.plan_verifier import (
                PlanVerificationError)
            try:
                prog = self._ensure_lowered(mode)
            except PlanVerificationError as e:
                # verify_plans="error" blocks the compile, but the
                # caller asked for a report, not a launch gate
                return e.verdict
            except Exception:  # pylint: disable=broad-except
                logger.exception("get_plan_verdict: lowering failed")
                return None
        return getattr(prog, "verdict", None)

    def get_plan_verdict_text(self) -> str:
        """``plan_verdict.txt`` content for dump_debug_info."""
        verdict = None
        try:
            verdict = self.get_plan_verdict()
        except Exception:  # pylint: disable=broad-except
            logger.exception("get_plan_verdict_text failed")
        if verdict is None:
            return ("plan verdict: (not available — verify_plans=off, "
                    "lowering failed, or launch not register-eligible)")
        return verdict.format_table()

    def get_model_check_text(self) -> str:
        """``model_check.txt`` content for dump_debug_info (ISSUE 13):
        the model checker's stats + findings for the lowered plan."""
        verdict = None
        try:
            verdict = self.get_plan_verdict()
        except Exception:  # pylint: disable=broad-except
            logger.exception("get_model_check_text failed")
        if verdict is None:
            return ("model check: (not available — verify_plans=off, "
                    "lowering failed, or launch not register-eligible)")
        mc_stats = verdict.stats.get("model_check")
        if not mc_stats:
            return ("model check: (not run — "
                    "verify_plans_model_check=off or plan exceeds "
                    "fixture-mode size gate)")
        from alpa_tpu.analysis import model_check as _mc
        mc_findings = [f for f in verdict.findings()
                       if f.analysis == "model_check"]
        return _mc.format_stats(mc_stats, mc_findings)

    def get_numerics_text(self) -> str:
        """``numerics.txt`` content for dump_debug_info (ISSUE 14): the
        numerics certification's per-output bound table + findings for
        the lowered plan."""
        verdict = None
        try:
            verdict = self.get_plan_verdict()
        except Exception:  # pylint: disable=broad-except
            logger.exception("get_numerics_text failed")
        if verdict is None:
            return ("numerics: (not available — verify_plans=off, "
                    "lowering failed, or launch not register-eligible)")
        num_stats = verdict.stats.get("numerics")
        if not num_stats:
            return "numerics: (not run — verify_plans_numerics=off)"
        from alpa_tpu.analysis import numerics as _num
        num_findings = [f for f in verdict.findings()
                        if f.analysis == "numerics"]
        return _num.format_numerics(num_stats, num_findings)

    def get_equiv_text(self) -> str:
        """``equiv.txt`` content for dump_debug_info (ISSUE 15): the
        translation validation's per-output proof table + findings for
        the lowered plan."""
        verdict = None
        try:
            verdict = self.get_plan_verdict()
        except Exception:  # pylint: disable=broad-except
            logger.exception("get_equiv_text failed")
        if verdict is None:
            return ("equiv: (not available — verify_plans=off, "
                    "lowering failed, or launch not register-eligible)")
        eq_stats = verdict.stats.get("equiv")
        if not eq_stats:
            return "equiv: (not run — verify_plans_equiv=off)"
        from alpa_tpu.analysis import equivalence as _eq
        eq_findings = [f for f in verdict.findings()
                       if f.analysis == "equiv"]
        return _eq.format_equiv(eq_stats, eq_findings)

    def _last_program(self):
        """The register program of the last launch's dispatch mode."""
        stats = getattr(self, "last_dispatch_stats", None) or {}
        return self._register_programs.get(stats.get("mode"))

    def _describe_pipeline(self):
        """What ``telemetry/perf.py`` ``joined_from_capture`` needs of
        this executable, without the compiled programs: the lowered
        program's metadata, each mesh's chips, each RUN op's program."""
        prog = self._last_program()
        if prog is None:
            return None
        execs = self.stage_execs + [e for e in self.apply_execs
                                    if e is not None]
        return {
            "program": types.SimpleNamespace(
                hooks=prog.hooks, op_meta=prog.op_meta, graph=prog.graph),
            "mesh_chips": {m: [d.id for d in mesh.flat_devices]
                           for m, mesh in enumerate(self.mesh_group)},
            "run_programs": {f"RUN {e.name}": e.program_name
                             for e in execs
                             if getattr(e, "program_name", None)}}

    def get_perf_report(self, capture=None):
        """Post-step :class:`~alpa_tpu.telemetry.perf.StepPerfReport`
        (ISSUE 9): critical path, per-mesh bubbles, transfer overlap,
        stage MFU — joined from the last launch's trace spans (or the
        flight ring when full tracing is off) against the lowered
        program's dataflow graph.  Publishes the ``alpa_stage_mfu``/
        ``alpa_step_bubble_fraction``/``alpa_critical_path_us`` gauges.
        None when no step has been recorded.

        Those spans are the driver's, and a RUN is an asynchronous
        enqueue.  Given a ``capture`` (``telemetry.trace.stop_capture``),
        or with ``trace.last_capture()`` holding the recorder's last step,
        the report is of the capture's last traced step on the device's
        clock (``source == "device"``: each RUN the interval its program
        ran on its mesh's chips, idle time by cause, collectives exposed
        and hidden, ``alpa_step_idle_seconds``), or says in its ``notes``
        why it could not be.  On a TPU only such a report's RUN costs go
        to the calibration store."""
        from alpa_tpu.telemetry import perf as _perf
        stats = getattr(self, "last_dispatch_stats", None) or {}
        mode = stats.get("mode")
        prog = self._last_program()
        joined = None
        held = capture if capture is not None else _ttrace.last_capture()
        if held is not None and prog is not None and (
                capture is not None or
                _perf.last_step_start(held.spans) == _perf.last_step_start(
                    _ttrace.get_recorder().spans()) is not None):
            described = self._describe_pipeline()
            steps = _perf.joined_from_capture(
                held, prog, described["mesh_chips"],
                described["run_programs"])
            joined = steps[-1] if steps else None
        if joined is None:
            joined = _perf.joined_from_recorder(_ttrace.get_recorder(),
                                                prog)
        if joined is None and _flight.enabled():
            joined = _perf.joined_from_flight(
                _flight.get_recorder().snapshot(), prog)
        if joined is None:
            return None
        report = _perf.build_step_report(
            joined, program=prog, schedule=self.schedule,
            stage_execs=(self.stage_execs +
                         [e for e in self.apply_execs if e is not None]),
            mode=mode, run_stats=stats)
        report.donated = self._plan_stats_by_program()
        _perf.publish_report(report)
        try:
            # fold the measured step into the calibration store (ISSUE
            # 12): per-stage RUN costs and per-edge wire costs become
            # the drift gauges' samples and, under replan_mode, the
            # planners' measured overrides.  On a TPU only from a step
            # read on the device's clock: a host span around a RUN is
            # the enqueue's time there, not the stage's
            from alpa_tpu.telemetry import calibration as _calibration
            on_tpu = self.mesh_group[0].flat_devices[0].platform == "tpu"
            if joined.source == "device" or not on_tpu:
                _calibration.ingest_joined(joined)
        except Exception:  # pylint: disable=broad-except
            logger.exception("calibration ingest failed")
        return report

    def get_perf_report_text(self) -> str:
        """``perf_report.txt`` content for dump_debug_info."""
        report = None
        try:
            report = self.get_perf_report()
        except Exception:  # pylint: disable=broad-except
            logger.exception("get_perf_report_text failed")
        if report is None:
            return ("perf report: (not available — no step recorded; "
                    "enable tracing via ALPA_TPU_TRACE=1 or the flight "
                    "ring via ALPA_TPU_FLIGHT=1 and run a step)")
        return report.format_text()

    def get_calibration_text(self) -> str:
        """``calibration.txt`` content for dump_debug_info: the measured
        -cost store's entries ranked by drift from the analytic model."""
        from alpa_tpu.telemetry.calibration import format_calibration_report
        return format_calibration_report()

    def consider_replan(self, report=None):
        """Profile-guided replanning (ISSUE 12): compare the measured
        step against the calibration store's view and — per
        ``global_config.replan_mode`` — recommend or apply a replan.

        * ``off``: returns None; nothing consulted, plans untouched.
        * ``suggest``: re-prices every cross-mesh edge under the
          calibrated cost model, logs the predicted critical-path delta
          from the ISSUE 9 ``simulate_dag`` what-if engine, and returns
          the verdict without applying anything.
        * ``auto``: additionally re-plans the flipped edges (through the
          calibration-fingerprinted compile-cache path, so a warm
          restart replays the same replan with zero solves) and
          hot-swaps the lowered programs — the static plan verifier
          re-runs on the swapped plan in ``_ensure_lowered``.

        Returns a verdict dict (baseline/predicted critical path µs,
        per-edge strategy flips, plan fingerprints) or None when replan
        is off / no measured step is available."""
        from alpa_tpu.analysis.critical_path import simulate_dag
        from alpa_tpu.pipeline_parallel import (cross_mesh_resharding as
                                                _cmr)
        from alpa_tpu.telemetry import calibration as _calibration
        mode = getattr(global_config, "replan_mode", "off")
        if mode == "off":
            return None
        if report is None:
            report = self.get_perf_report()  # ingests into the store
        if report is None or not report.sim_durs_us:
            return None
        store = _calibration.get_calibration_store()
        baseline_us, _ = simulate_dag(report.sim_durs_us,
                                      report.sim_preds)

        # Re-price every cross-mesh edge under the calibrated chooser.
        # resolve_strategy's key carries the store fingerprint, so these
        # decisions cache and replay on warm restart.
        edge_cost_us: Dict[Tuple[str, str], float] = {}
        flips = []
        for inst in self.instructions:
            if inst.opcode != PipelineInstType.RESHARD or \
                    inst.plan is None or inst.src_sharding is None:
                continue
            try:
                chosen, costs, _cached = _cmr.resolve_strategy(
                    inst.plan.shape, inst.plan.itemsize,
                    inst.src_sharding, inst.dst_sharding)
            except Exception:  # pylint: disable=broad-except
                logger.exception("replan: re-pricing %s failed",
                                 inst.info)
                continue
            edge = (str(inst.src_mesh), str(inst.dst_mesh))
            cost_us = costs.get(chosen, 0.0) * 1e6
            edge_cost_us[edge] = max(edge_cost_us.get(edge, 0.0),
                                     cost_us)
            if chosen != inst.plan.strategy:
                flips.append((inst, inst.plan.strategy, chosen))

        # Predicted critical path of the (re)planned step: measured
        # stage medians for RUNs, the calibrated chooser's edge cost
        # (falling back to the measured wire median) for transfer waits.
        durs = list(report.sim_durs_us)
        for op in report.sim_ops:
            m = None
            stage = _calibration._stage_from_name(op.name)  # pylint: disable=protected-access
            if stage is not None:
                m = store.measured_us("stage_run",
                                      _calibration.stage_signature(stage))
            elif op.kind == "wait":
                edge = _calibration._edge_from_name(op.name)  # pylint: disable=protected-access
                if edge is not None:
                    m = edge_cost_us.get(edge)
                    if m is None:
                        m = store.measured_us(
                            "reshard_wire",
                            _calibration.edge_signature(*edge))
            if m is not None and 0 <= op.idx < len(durs):
                durs[op.idx] = m
        predicted_us, _ = simulate_dag(durs, report.sim_preds)
        verdict = {
            "mode": mode,
            "baseline_critical_path_us": baseline_us,
            "predicted_critical_path_us": predicted_us,
            "predicted_ratio": (predicted_us / baseline_us
                                if baseline_us > 0 else 1.0),
            "n_edges_repriced": len(edge_cost_us),
            "strategy_flips": [
                {"edge": f"{i.src_mesh}->{i.dst_mesh}", "var": i.info,
                 "from": old, "to": new} for i, old, new in flips],
            "applied": False,
            "calibration_fingerprint": store.fingerprint(),
        }
        logger.info(
            "replan(%s): predicted critical path %.1f us vs measured "
            "%.1f us (ratio %.3f), %d strategy flip(s)", mode,
            predicted_us, baseline_us, verdict["predicted_ratio"],
            len(flips))
        if mode != "auto":
            return verdict

        # auto: hot-swap — re-plan flipped edges and re-lower so the
        # verifier re-runs on the swapped plan
        verdict["plan_fingerprint_before"] = self.get_plan_fingerprint()
        if flips:
            from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
                ReshardingTask, plan_resharding)
            for inst, _old, _new in flips:
                try:
                    inst.plan = plan_resharding(
                        inst.plan.shape, inst.plan.itemsize,
                        inst.src_sharding, inst.dst_sharding)
                    inst.task = ReshardingTask(inst.plan,
                                               inst.dst_sharding)
                except Exception:  # pylint: disable=broad-except
                    logger.exception("replan: re-planning %s failed; "
                                     "keeping the old plan", inst.info)
            modes = list(self._register_programs)
            self._register_programs.clear()
            self._register_program = None
            # the instruction stream changed: any accepted superopt
            # layout no longer applies — re-decide on the new stream
            self._superopt_outcome = None
            self._superopt_instructions = None
            for m in modes:
                self._ensure_lowered(m)
        verdict["applied"] = bool(flips)
        verdict["plan_fingerprint_after"] = self.get_plan_fingerprint()
        return verdict

    def get_plan_fingerprint(self) -> str:
        """Content hash of the compiled parallel plan: instruction stream
        plus every stage's input/output shardings.  Two executables with
        equal fingerprints replay identically — used by the compile-cache
        determinism tests (a plan loaded from the persistent cache must
        reproduce a fresh solve exactly).

        ``Var`` reprs embed trace-time object ids, so ids are renumbered
        by first appearance — two independent traces of the same program
        hash identically while distinct vars stay distinct."""
        import hashlib
        import re
        parts = [self.get_instruction_text()]
        for ex in self.stage_execs + [e for e in self.apply_execs
                                      if e is not None]:
            parts.append(ex.name)
            parts.append(repr([str(s) for s in ex.in_shardings]))
            parts.append(repr([str(s) for s in ex.out_shardings]))
        text = "\n".join(parts)
        renumber = {}

        def canon(m):
            return renumber.setdefault(m.group(0),
                                       f"id={len(renumber)}")

        text = re.sub(r"id=\d+", canon, text)
        text = re.sub(r"0x[0-9a-fA-F]+", "0x0", text)
        return hashlib.sha256(text.encode()).hexdigest()

    def dump_stage_execution_trace(self, filename: str):
        """Write the collected per-instruction events as a Chrome trace
        JSON (ref dump_stage_execution_trace_internal,
        pipeshard_executable.py:592).

        Events come from the unified ``telemetry.trace`` recorder — the
        same spans every dispatch mode records (interpreter per-inst
        spans, register/overlap ``op_meta`` hook spans) — plus whatever
        legacy ``timer.Tracer`` instants third-party code still logs.
        Run one executable at a time between ``recorder.clear()`` calls
        to attribute events.  Requires ``global_config.collect_trace``
        (or the telemetry master switch) to be True during execution;
        warns with the active dispatch mode when empty."""
        import json
        all_events = _ttrace.get_recorder().to_chrome_trace().get(
            "traceEvents", [])
        # "M" records are per-track metadata the recorder always emits;
        # real content is spans/instants/counters
        timed = [e for e in all_events if e.get("ph") != "M"]
        # deprecated bridge, imported lazily: third-party code may still
        # log through alpa_tpu.timer.tracer and expects to land here
        from alpa_tpu.timer import tracer
        legacy = tracer.to_chrome_trace()
        if not timed and not legacy:
            mode = (getattr(self, "last_dispatch_stats", None)
                    or {}).get("mode")
            logger.warning(
                "dump_stage_execution_trace: no events collected (last "
                "dispatch mode: %s) — set global_config.collect_trace = "
                "True before running", mode)
        with open(filename, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": all_events + legacy}, f)

    def get_resharding_report(self) -> str:
        """Planned cross-mesh traffic per step (tile-level accounting from
        cross_mesh_resharding.plan_resharding), and, by program, what the
        planner did with the pairs that share a donated buffer and with
        the inputs it was handed as given (``solver.alias_stats``,
        ``solver.given_stats``), and what unification then took from the
        plan (``unify_overrides``)."""
        n = sum(1 for i in self.instructions
                if i.opcode == PipelineInstType.RESHARD and
                i.src_mesh != i.dst_mesh)
        report = (f"{n} cross-mesh transfers, "
                  f"{self._resharding_bytes / 1e6:.3f} MB per step (planned)")
        if self._max_link_bytes:
            report += (f"; max link {self._max_link_bytes / 1e6:.3f} MB "
                       f"(per-device egress/ingress)")
        if self._executed_resharding_bytes:
            report += (
                f"; executed {self._executed_resharding_bytes / 1e6:.3f} MB "
                f"cross-mesh + {self._executed_intra_mesh_bytes / 1e6:.3f} MB "
                f"intra-mesh ({global_config.resharding_execution})")
        from alpa_tpu.telemetry.perf import format_plan_stats
        for name, stats in self._plan_stats_by_program().items():
            report += "".join("\n" + line
                              for line in format_plan_stats(name, stats))
        return report

    def _plan_stats_by_program(self) -> Dict[str, Dict[str, int]]:
        return {
            e.name: dict(e.plan_stats, unify_overrides=e.unify_overrides)
            for e in self.stage_execs + self.apply_execs
            if e is not None and e.plan_stats
        }

    def sync(self):
        self.mesh_group.sync_workers()
