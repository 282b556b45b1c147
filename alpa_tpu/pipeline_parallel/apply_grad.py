"""Gradient split / accumulation / apply-grad partitioning for pipelines.

Analog of ref ``alpa/pipeline_parallel/apply_grad.py`` (SURVEY.md §2.4):

* ``split_compute_grad_and_apply_grad`` (ref :351) — split the train-step
  jaxpr at the gradient marker,
* ``compute_grad_to_accumulate_grad`` (ref :504) — rewrite backward
  computations so each microbatch adds into accumulator invars,
* ``apply_grad_get_mean`` (ref :650) — divide accumulated values by the
  number of microbatches,
* ``process_apply_gradient`` (ref :591) — partition the apply_grad eqns
  across meshes: forward from the gradients and parameters they consume,
  backward from their readers for what has no placed input, so that the
  optimizer's moments live on the mesh of their parameter.
"""
import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend.core import ClosedJaxpr, Literal, Var

from alpa_tpu.pipeline_parallel.computation import JaxPipelineComputation
from alpa_tpu.pipeline_parallel.primitive_def import is_marker
from alpa_tpu.util import OrderedSet, clone_jaxpr, gensym_var, new_jaxpr_eqn

logger = logging.getLogger(__name__)


def split_compute_grad_and_apply_grad(closed_jaxpr: ClosedJaxpr):
    """Split at the gradient marker (ref apply_grad.py:351).

    Returns (compute_eqns, grad_pairs, apply_eqns) where grad_pairs is the
    list of (pre-marker var, post-marker var) for every marked value.
    """
    eqns = closed_jaxpr.jaxpr.eqns
    marker_idx = [i for i, e in enumerate(eqns) if is_marker(e, "grad")]
    if not marker_idx:
        raise ValueError(
            "PipeshardParallel requires alpa_tpu.grad / value_and_grad "
            "inside the parallelized function (gradient marker not found).")
    i = marker_idx[-1]
    marker = eqns[i]
    grad_pairs = [(iv, ov) for iv, ov in zip(marker.invars, marker.outvars)
                  if isinstance(iv, Var)]
    return list(eqns[:i]), grad_pairs, list(eqns[i + 1:])


def compute_grad_to_accumulate_grad(
        computations: List[JaxPipelineComputation],
        grad_vars: Sequence[Var]
) -> Tuple[List[JaxPipelineComputation], Dict[Var, Var]]:
    """Rewrite computations producing gradient values so they *accumulate*
    (ref apply_grad.py:504).

    For each grad var g produced by computation C, add an accumulator invar
    acc_g to C and a summed outvar g_sum = g + acc_g.  The runtime feeds
    zeros for microbatch 0 and the previous sum afterwards, donating the
    accumulator.  Returns ``acc_info``: grad var ->
    (accumulator invar, summed outvar, computation index).
    """
    grad_set = set(grad_vars)
    acc_info: Dict[Var, Tuple[Var, Var, int]] = {}
    for ci, comp in enumerate(computations):
        produced = [v for v in comp.outvars if v in grad_set]
        if not produced:
            continue
        for g in produced:
            acc = gensym_var(g.aval)
            new_out = gensym_var(g.aval)
            add_eqn = _make_add_eqn(g, acc, new_out)
            comp.eqns.append(add_eqn)
            comp.invars.append(acc)
            comp.outvars = [new_out if v is g else v for v in comp.outvars]
            acc_info[g] = (acc, new_out, ci)
    return computations, acc_info


def _make_add_eqn(a: Var, b: Var, out: Var):
    from jax.extend.core import Primitive
    from jax._src.lax import lax as lax_internal
    add_p = lax_internal.add_p
    return new_jaxpr_eqn([a, b], [out], add_p, {})


@dataclasses.dataclass
class ApplyGradConfig:
    """Partitioned apply-grad: one computation per mesh plus metadata."""
    computations: List[JaxPipelineComputation]
    mesh_assignment: List[int]
    # invars of the apply computations that are accumulated gradients
    grad_invars: List[Var]
    num_micro_batches: int


def apply_grad_get_mean(apply_eqns: List, grad_pairs, num_micro_batches: int,
                        gensym=gensym_var):
    """Insert g / num_micro_batches at the head of apply_grad
    (ref apply_grad.py:650).  Returns (new_eqns, substitution): apply eqns
    should consume the divided values."""
    from jax._src.lax import lax as lax_internal

    div_eqns = []
    sub = {}
    for pre, post in grad_pairs:
        scaled = gensym(post.aval)
        # div by scalar: mul by reciprocal via integer_pow? use div_p with
        # a literal denominator of matching dtype.
        denom = Literal(np.array(num_micro_batches, post.aval.dtype),
                        post.aval.update(shape=()))
        div_eqns.append(
            new_jaxpr_eqn([post, denom], [scaled], lax_internal.div_p, {}))
        sub[post] = scaled
    new_apply = []
    for e in apply_eqns:
        new_apply.append(
            e.replace(invars=[sub.get(v, v) if isinstance(v, Var) else v
                              for v in e.invars]))
    return div_eqns + new_apply, sub


def apply_partition_is_acyclic(comps: List[JaxPipelineComputation]) -> bool:
    """Check the comp-level dependency graph for cycles (mutual cross-mesh
    value exchange, e.g. global-norm clipping)."""
    outs_of = {}
    for m, c in enumerate(comps):
        for v in c.outvars:
            outs_of[v] = m
    deps = {m: set() for m in range(len(comps))}
    for m, c in enumerate(comps):
        for v in c.invars:
            src = outs_of.get(v)
            if src is not None and src != m:
                deps[m].add(src)
    # DFS cycle check
    state = {}

    def visit(m):
        if state.get(m) == 2:
            return True
        if state.get(m) == 1:
            return False
        state[m] = 1
        for d in deps[m]:
            if not visit(d):
                return False
        state[m] = 2
        return True

    return all(visit(m) for m in range(len(comps)))


def _numel(v: Var) -> float:
    return float(np.prod(getattr(v.aval, "shape", ())))


def assign_apply_grad_meshes(apply_eqns: List,
                             var_mesh: Dict[Var, int]) -> List[int]:
    """The mesh of every apply-grad eqn, from the eqns' data flow alone
    (ref propagate_mesh_assignment).

    Forward, in program order: an eqn goes to the mesh holding its largest
    placed input; one with no placed input stays open.  Backward, in
    reverse order: an open eqn goes to the mesh that reads most bytes of
    its results (lowest mesh on a tie), which places its results and so
    makes the readers of its own inputs known.  The two sweeps alternate
    to a fixed point, so that ``decay * mu`` lands beside the gradient
    term it is added to, and ``mu`` itself (placed at launch on the mesh
    of its first reader) beside its parameter.  What nothing placed reads
    (``step + 1``) goes to mesh 0.
    """
    placed = dict(var_mesh)
    eqn_mesh: List[Optional[int]] = [None] * len(apply_eqns)
    readers: Dict[Var, List[int]] = {}
    for i, e in enumerate(apply_eqns):
        for v in OrderedSet(v for v in e.invars if isinstance(v, Var)):
            readers.setdefault(v, []).append(i)

    def place(i, m):
        eqn_mesh[i] = m
        for v in apply_eqns[i].outvars:
            placed[v] = m

    def forward():
        changed = False
        for i, e in enumerate(apply_eqns):
            if eqn_mesh[i] is not None:
                continue
            best_m, best_size = None, -1.0
            for v in e.invars:
                if isinstance(v, Var) and v in placed and \
                        _numel(v) > best_size:
                    best_m, best_size = placed[v], _numel(v)
            if best_m is not None:
                place(i, best_m)
                changed = True
        return changed

    def backward():
        changed = False
        for i in reversed(range(len(apply_eqns))):
            if eqn_mesh[i] is not None:
                continue
            read_bytes: Dict[int, float] = {}
            for v in apply_eqns[i].outvars:
                nbytes = _numel(v) * v.aval.dtype.itemsize
                for j in readers.get(v, ()):
                    m = eqn_mesh[j]
                    if m is not None:
                        read_bytes[m] = read_bytes.get(m, 0.0) + nbytes
            if read_bytes:
                place(i, min(read_bytes, key=lambda m: (-read_bytes[m], m)))
                changed = True
        return changed

    forward()
    while backward() and forward():
        pass
    return [0 if m is None else m for m in eqn_mesh]


def partition_apply_grad(apply_eqns: List,
                         var_mesh: Dict[Var, int],
                         num_meshes: int,
                         global_outvars: Sequence[Var],
                         consts_map: Dict[Var, Any],
                         force_mesh: Optional[int] = None
                         ) -> Tuple[List[JaxPipelineComputation], Dict[Var, int]]:
    """Partition the apply-grad eqns into one computation per mesh (ref
    process_apply_gradient:591), each eqn on the mesh
    :func:`assign_apply_grad_meshes` gives it, or all on ``force_mesh``.

    Eqns whose inputs span meshes go to the mesh holding the largest input
    (so gradient-sized values stay put and scalars travel); values are
    ferried by the runtime's cross-mesh resharding.  Returns one computation
    per mesh (possibly empty) and the var->mesh map.
    """
    if force_mesh is not None:
        eqn_mesh = [force_mesh] * len(apply_eqns)
    else:
        eqn_mesh = assign_apply_grad_meshes(apply_eqns, var_mesh)
    local_var_mesh = dict(var_mesh)
    for e, m in zip(apply_eqns, eqn_mesh):
        for v in e.outvars:
            local_var_mesh[v] = m

    comps = []
    global_out_set = {gv for gv in global_outvars if isinstance(gv, Var)}
    for mesh_id in range(num_meshes):
        eqns_m = [e for e, m in zip(apply_eqns, eqn_mesh) if m == mesh_id]
        invars = OrderedSet()
        defined = OrderedSet()
        for e in eqns_m:
            for v in e.invars:
                if isinstance(v, Var) and v not in defined and \
                        v not in consts_map:
                    invars.add(v)
            defined.update(e.outvars)
        outvars = OrderedSet()
        for e in eqns_m:
            for v in e.outvars:
                if v in global_out_set:
                    outvars.add(v)
        # also export vars needed by other meshes
        for e, m in zip(apply_eqns, eqn_mesh):
            if m == mesh_id:
                continue
            for v in e.invars:
                if isinstance(v, Var) and v in defined:
                    outvars.add(v)
        consts = {
            v: consts_map[v] for e in eqns_m for v in e.invars
            if isinstance(v, Var) and v in consts_map
        }
        comps.append(
            JaxPipelineComputation(f"apply_grad_{mesh_id}", list(invars),
                                   list(outvars), eqns_m, consts))
    return comps, local_var_mesh
