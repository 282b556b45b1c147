"""Per-equation sharding strategy enumeration + graph construction.

The jaxpr-level re-architecture of the reference's C++ AutoSharding pass
(``auto_sharding.cc``/``auto_sharding_dot_handler.cc``, reconstructed in
SURVEY.md §2.9; readable Python spec in ref ``playground/
auto_sharding_solver/``).  We build a *strategy graph*:

* **nodes** — decision points: graph invars and "heavy" equations
  (dot_general, conv, reduce, unmappable reshapes, unknown ops).  Each node
  has a finite list of strategies; a strategy fixes the node's output Spec,
  a node communication cost (e.g. the all-reduce of a contracted-dim-sharded
  matmul), and required operand Specs.
* **follow chains** — cheap ops (elementwise, transpose, broadcast,
  mappable reshape, convert) don't get nodes; they reuse their lead
  operand's decision through a dim-mapping (the analog of the reference's
  strategy "following").
* **edges** — (producer node, consumer node) pairs with a dense resharding
  cost matrix C[s_src, s_dst].

The ILP (ilp.py) picks one strategy per node minimizing node + edge costs;
invar decisions become pjit in_shardings, and ``make_constrained_fun``
re-interprets the jaxpr inserting ``with_sharding_constraint`` on every
solved op output (via Node.outvar) so GSPMD realizes the ILP's plan even
where propagation would disagree.
"""
import dataclasses
import functools
import itertools
import logging
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import source_info_util
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

from alpa_tpu.shard_parallel.sharding_spec import (Spec, enumerate_var_specs,
                                                   is_replicated, make_spec,
                                                   num_shards,
                                                   replicated_spec,
                                                   resharding_cost,
                                                   spec_valid, used_axes)

logger = logging.getLogger(__name__)

# Ops followed through without creating a decision node.
ELEMENTWISE_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "max", "min", "pow", "rem",
    "atan2",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter",
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "asin",
    "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh", "sqrt",
    "rsqrt", "cbrt", "logistic", "erf", "erfc", "erf_inv", "abs", "neg",
    "sign", "floor", "ceil", "round", "is_finite", "not", "integer_pow",
    "exp2", "square",
    "eq", "ne", "ge", "gt", "le", "lt", "select_n", "clamp",
    "convert_element_type", "bitcast_convert_type", "stop_gradient",
    "copy", "real", "imag", "conj",
})

# Sub-jaxpr-carrying ops we inline for analysis.
INLINE_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "remat", "checkpoint",
    "remat2", "custom_vjp_call_custom_transpose", "custom_lin",
})

DimMap = Tuple[Optional[int], ...]  # var dim -> node dim (None = fresh dim)


def identity_dimmap(ndim: int) -> DimMap:
    return tuple(range(ndim))


def compose_dimmap(outer: DimMap, inner: DimMap) -> DimMap:
    """outer: var<-mid, inner: mid<-node  =>  var<-node."""
    return tuple(inner[m] if m is not None else None for m in outer)


def map_spec(node_spec: Spec, dimmap: DimMap, ndim: int) -> Tuple[Spec, Tuple[int, ...]]:
    """Map a node's spec through a dim-mapping.

    Returns (mapped_spec, dropped_axes): mesh axes sharding node dims that
    the mapping does not carry (they must be all-gathered to realize the
    follow, charged on the edge).
    """
    mapped = [() for _ in range(ndim)]
    used_node_dims = set()
    for d, nd in enumerate(dimmap):
        if nd is not None and nd < len(node_spec):
            mapped[d] = node_spec[nd]
            used_node_dims.add(nd)
    dropped = []
    for nd, axes in enumerate(node_spec):
        if nd not in used_node_dims:
            dropped.extend(axes)
    return tuple(mapped), tuple(dropped)


@dataclasses.dataclass
class Strategy:
    name: str
    out_spec: Spec
    comm_cost: float
    # required operand specs, parallel to the node's operand list
    operand_specs: Tuple[Spec, ...] = ()
    # resident bytes per device under this strategy (invar nodes: the
    # sharded parameter bytes; used by the ILP memory constraint)
    mem_bytes: float = 0.0
    # collective realizing comm_cost in the compiled HLO: "all_reduce"
    # (contracted-dim sharding) or "ppermute" (spatial halo exchange) —
    # structural tests match the planned kind against the HLO op counts
    comm_kind: str = "all_reduce"
    # tiny objective nudge for breaking genuine cost ties (e.g. prefer
    # batch over out-channel conv sharding, the reference's data-parallel
    # bias); excluded from comm accounting and solution_cost
    tie_bias: float = 0.0
    # gradient-collective codec realizing comm_cost (ISSUE 19): None =
    # full precision; "int8"/"fp8" = the blockwise stochastic-rounding
    # codec (reshard_codec), priced by the *_cost_quantized twins.  Only
    # ever set when global_config.grad_quantize != "off", so default
    # plans stay byte-identical.
    codec: Optional[str] = None


@dataclasses.dataclass
class Node:
    idx: int
    kind: str  # 'invar' | 'op'
    aval: Any
    strategies: List[Strategy]
    label: str = ""
    # invar nodes: which flat invar index they represent
    invar_idx: Optional[int] = None
    # op nodes: the eqn's primary outvar (for constraint emission)
    outvar: Optional[Var] = None
    # a value the graph does not model and holds replicated (an opaque
    # op's output, a constant): GSPMD lays it out, not the plan
    barrier: bool = False


@dataclasses.dataclass
class Edge:
    src: int
    dst: int
    # cost[s_src, s_dst]
    cost: np.ndarray


@dataclasses.dataclass
class StrategyGraph:
    nodes: List[Node]
    edges: List[Edge]
    logical_mesh: Any
    # (source node, dim map var<-node, invar node) of every donated pair
    # (``build_strategy_graph``'s ``alias_pairs``)
    alias_edges: List[Tuple[int, DimMap, int]] = dataclasses.field(
        default_factory=list)
    # outvar position -> invar node, of every donated pair: the output
    # leaves as that invar arrives
    alias_outs: Dict[int, int] = dataclasses.field(default_factory=dict)
    # of every invar planned as given (``fixed_in_specs``): its node, which
    # holds the layout it arrives in -> the node behind it, which holds the
    # layout the program reads it in
    relayouts: Dict[int, int] = dataclasses.field(default_factory=dict)
    # (source node, dim map var<-node) of every outvar, None for a literal
    out_sources: List[Optional[Tuple[int, DimMap]]] = dataclasses.field(
        default_factory=list)

    def stats(self):
        nvars = sum(len(n.strategies) for n in self.nodes)
        nevars = sum(e.cost.size for e in self.edges)
        return (f"{len(self.nodes)} nodes / {nvars} strategy vars / "
                f"{len(self.edges)} edges / {nevars} edge vars")


########################################
# jaxpr flattening (inline sub-jaxprs)
########################################


def _inline_site(eqn, depth: int):
    """Resolve an inlinable call site: ``(sub_jaxpr, consts)`` or None.

    Single source of truth for INLINE_PRIMS membership, the depth cap and
    the param-key lookup.  The flatten traversal, ``_check_evaluable`` and
    the constrained re-interpreter MUST agree on this (constraints attach
    by position in the flattened eqn order), so they all call here.
    """
    if eqn.primitive.name not in INLINE_PRIMS or depth >= 6:
        return None
    sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") or
           eqn.params.get("fun_jaxpr"))
    if sub is None:
        return None
    if isinstance(sub, ClosedJaxpr):
        return sub.jaxpr, sub.consts
    return sub, []


def _align_call_args(outer: list, inner_invars) -> list:
    """pjit-style calls line invars up 1:1; custom_jvp has extra prefix
    args — align from the end.  Pads (with None) when there are fewer
    outer args than inner invars (such sites are not re-evaluable; see
    ``_check_evaluable``)."""
    if len(outer) >= len(inner_invars):
        return outer[len(outer) - len(inner_invars):]
    return list(outer) + [None] * (len(inner_invars) - len(outer))


def _subst(v, env):
    if isinstance(v, Literal):
        return v
    seen = 0
    while v in env and env[v] is not v and seen < 100:
        nxt = env[v]
        if isinstance(nxt, Literal):
            return nxt
        v = nxt
        seen += 1
    return v


def flatten_jaxpr_eqns(jaxpr: Jaxpr, env: Optional[dict] = None,
                       depth: int = 0, info: Optional[dict] = None) -> List:
    """Inline pjit/custom-call/remat sub-jaxprs, returning a flat eqn list
    over substituted vars.  Scan/while/cond are left opaque (barriers).

    ``info`` (optional dict) collects side data for re-evaluation:
    ``captured_consts`` (inner constvar -> value) and ``env`` (the
    substitution, for resolving outer outvars of inlined calls).
    """
    env = env if env is not None else {}
    if info is not None:
        info.setdefault("captured_consts", {})
        if depth == 0:
            # only the top-level substitution maps outer outvars; inner
            # envs must not clobber it
            info["env"] = env
    out = []
    for eqn in jaxpr.eqns:
        site = _inline_site(eqn, depth)
        if site is not None:
            sub_jaxpr, consts = site
            inner_env = {}
            outer_in = [_subst(v, env) for v in eqn.invars]
            inner_invars = list(sub_jaxpr.invars)
            aligned = _align_call_args(outer_in, inner_invars)
            for iv, ov in zip(inner_invars, aligned):
                if ov is not None:
                    inner_env[iv] = ov
            for ci, cv in enumerate(sub_jaxpr.constvars):
                # consts become opaque leaf vars (replicated barriers);
                # record their values for re-evaluation
                inner_env[cv] = cv
                if info is not None and ci < len(consts):
                    info["captured_consts"][cv] = consts[ci]
            inner_eqns = flatten_jaxpr_eqns(sub_jaxpr, inner_env, depth + 1,
                                            info)
            # Freshen every var DEFINED inside this inline site: jax caches
            # traced sub-jaxprs, so two calls of the same function share
            # inner Var objects — without freshening, the second site's
            # eqns would collide with (and overwrite) the first's.
            from alpa_tpu.util import gensym_var
            fresh = {}

            def _fresh(v):
                if isinstance(v, Literal):
                    return v
                return fresh.get(v, v)

            freshened = []
            for ie in inner_eqns:
                new_outs = []
                for ov2 in ie.outvars:
                    nv = gensym_var(ov2.aval)
                    fresh[ov2] = nv
                    new_outs.append(nv)
                freshened.append(
                    ie.replace(invars=[_fresh(v) for v in ie.invars],
                               outvars=new_outs))
            out.extend(freshened)
            # map eqn outvars to (freshened) inner outvars
            for ov, inner_ov in zip(eqn.outvars, sub_jaxpr.outvars):
                if isinstance(inner_ov, Literal):
                    env[ov] = inner_ov
                else:
                    env[ov] = _fresh(_subst(inner_ov, inner_env))
        else:
            out.append(eqn.replace(
                invars=[_subst(v, env) for v in eqn.invars],
                outvars=list(eqn.outvars)))
            # resolve substitutions lazily for later eqns
    # Second pass: apply env to all invars (outvars of inlined eqns may map)
    fixed = []
    for eqn in out:
        fixed.append(eqn.replace(invars=[_subst(v, env) for v in eqn.invars]))
    return fixed


########################################
# dot_general strategy enumeration
########################################


def _dot_semantic_dims(eqn):
    """Classify output dims of a dot_general as (batch, lhs_free, rhs_free)
    and locate contracting dims on the operands."""
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    lhs_free = [d for d in range(len(lhs.shape))
                if d not in lhs_c and d not in lhs_b]
    rhs_free = [d for d in range(len(rhs.shape))
                if d not in rhs_c and d not in rhs_b]
    # out dims: batch..., lhs_free..., rhs_free...
    return (list(lhs_b), list(rhs_b), list(lhs_c), list(rhs_c), lhs_free,
            rhs_free)


def _reduce_scattered(strategy: Strategy, out_av, out_map, out_bytes,
                      ar_axes, logical_mesh) -> List[Strategy]:
    """The variants of a strategy that all-reduces partial results over
    ``ar_axes`` in which one of those axes reduce-scatters them instead,
    over an output dimension that ``out_map`` (dim -> axis) leaves whole
    (``k0@1>1``): half the bytes, and the result leaves sharded."""
    found = []
    ndim = len(out_av.shape)
    for a in ar_axes:
        for d in range(ndim):
            spec = make_spec(ndim, {**out_map, d: a})
            if d in out_map or not spec_valid(out_av, spec,
                                              logical_mesh.shape):
                continue
            found.append(dataclasses.replace(
                strategy, name=f"{strategy.name}>{d}", out_spec=spec,
                comm_cost=strategy.comm_cost -
                logical_mesh.all_reduce_cost(out_bytes, a) +
                logical_mesh.reduce_scatter_cost(out_bytes, a),
                comm_kind="reduce_scatter"))
    return found


def enumerate_dot_strategies(eqn, logical_mesh,
                             scatter: bool = False) -> List[Strategy]:
    """The dot handler (analog of ref ``auto_sharding_dot_handler.cc``).

    Enumerates assignments of each non-trivial mesh axis to one semantic
    role: a batch dim (Sb), an lhs free dim (Si), an rhs free dim (Sj), or
    a contracting dim (Sk -> all-reduce of the output on that axis).

    ``scatter``: a contracting axis may also reduce-scatter the output
    (``_reduce_scattered``): what a weight gradient wants whose operands
    arrive sharded over the positions it contracts and whose sum lands in
    a sharded accumulator.
    """
    mesh_shape = logical_mesh.shape
    lhs_av, rhs_av = eqn.invars[0].aval, eqn.invars[1].aval
    out_av = eqn.outvars[0].aval
    lhs_b, rhs_b, lhs_c, rhs_c, lhs_free, rhs_free = _dot_semantic_dims(eqn)
    nb = len(lhs_b)
    out_ndim = len(out_av.shape)

    nontrivial = [a for a, s in enumerate(mesh_shape) if s > 1]
    if not nontrivial:
        return [Strategy("R", replicated_spec(out_ndim), 0.0,
                         (replicated_spec(len(lhs_av.shape)),
                          replicated_spec(len(rhs_av.shape))))]

    # Role choices per mesh axis: ('b', i) / ('i', i) / ('j', i) / ('k', i)
    role_choices = []
    for bi in range(nb):
        role_choices.append(("b", bi))
    for i_pos, _ in enumerate(lhs_free):
        role_choices.append(("i", i_pos))
    for j_pos, _ in enumerate(rhs_free):
        role_choices.append(("j", j_pos))
    for k_pos, _ in enumerate(lhs_c):
        role_choices.append(("k", k_pos))

    strategies = []
    seen = set()
    for assignment in itertools.product(role_choices, repeat=len(nontrivial)):
        # each (role, pos) may appear at most once across axes
        if len(set(assignment)) != len(assignment):
            continue
        lhs_map, rhs_map, out_map = {}, {}, {}
        ar_axes = []
        ok = True
        for axis, (role, pos) in zip(nontrivial, assignment):
            if role == "b":
                lhs_map[lhs_b[pos]] = axis
                rhs_map[rhs_b[pos]] = axis
                out_map[pos] = axis
            elif role == "i":
                lhs_map[lhs_free[pos]] = axis
                out_map[nb + pos] = axis
            elif role == "j":
                rhs_map[rhs_free[pos]] = axis
                out_map[nb + len(lhs_free) + pos] = axis
            else:  # k
                lhs_map[lhs_c[pos]] = axis
                rhs_map[rhs_c[pos]] = axis
                ar_axes.append(axis)
        lhs_spec = make_spec(len(lhs_av.shape), lhs_map)
        rhs_spec = make_spec(len(rhs_av.shape), rhs_map)
        out_spec = make_spec(out_ndim, out_map)
        if not (spec_valid(lhs_av, lhs_spec, mesh_shape) and
                spec_valid(rhs_av, rhs_spec, mesh_shape) and
                spec_valid(out_av, out_spec, mesh_shape)):
            ok = False
        if not ok:
            continue
        out_bytes = (float(np.prod(out_av.shape)) * out_av.dtype.itemsize /
                     num_shards(out_spec, mesh_shape))
        cost = sum(logical_mesh.all_reduce_cost(out_bytes, a)
                   for a in ar_axes)
        name = "".join(f"{r}{p}@{a}" for a, (r, p) in
                       zip(nontrivial, assignment))
        key = (lhs_spec, rhs_spec, out_spec)
        if key in seen:
            continue
        seen.add(key)
        strategies.append(Strategy(name, out_spec, cost,
                                   (lhs_spec, rhs_spec)))
        if scatter:
            strategies += _reduce_scattered(strategies[-1], out_av, out_map,
                                            out_bytes, ar_axes, logical_mesh)
    if not strategies:
        strategies.append(Strategy("R", replicated_spec(out_ndim), 0.0,
                                   (replicated_spec(len(lhs_av.shape)),
                                    replicated_spec(len(rhs_av.shape)))))
    return strategies


def enumerate_conv_strategies(eqn, logical_mesh) -> List[Strategy]:
    """Conv handler (analog of the reference dot/conv strategy vectors):
    each non-trivial mesh axis takes one role —

      'b': shard the batch dim (lhs batch <-> out batch),
      'o': shard output channels (rhs O <-> out feature),
      'i': shard input channels (lhs C + rhs I contracted -> all-reduce),
      'g': shard channel groups (grouped/depthwise convs: lhs C, rhs O
           and out F all sharded along the group axis, no collective),
      's': shard the first spatial dim (GSPMD inserts the halo exchange;
           costed as one neighbor ppermute of the halo ring).
    """
    mesh_shape = logical_mesh.shape
    dn = eqn.params["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec_dims = (dn.lhs_spec, dn.rhs_spec,
                                         dn.out_spec)
    lhs_av, rhs_av = eqn.invars[0].aval, eqn.invars[1].aval
    out_av = eqn.outvars[0].aval
    feature_group_count = eqn.params.get("feature_group_count", 1)
    batch_group_count = eqn.params.get("batch_group_count", 1)
    lhs_b, lhs_c = lhs_spec[0], lhs_spec[1]
    rhs_o, rhs_i = rhs_spec[0], rhs_spec[1]
    out_b, out_f = out_spec_dims[0], out_spec_dims[1]
    # first spatial dim triple + its kernel extent (for the halo size)
    lhs_s0, rhs_s0, out_s0 = lhs_spec[2], rhs_spec[2], out_spec_dims[2]
    kernel0 = int(rhs_av.shape[rhs_s0])

    nontrivial = [a for a, s in enumerate(mesh_shape) if s > 1]
    if not nontrivial:
        return [Strategy("R", replicated_spec(len(out_av.shape)), 0.0,
                         (replicated_spec(len(lhs_av.shape)),
                          replicated_spec(len(rhs_av.shape))))]

    roles = ["b", "o", "s"]
    # contracting input channels is only valid without feature groups;
    # with groups, the group dim itself is shardable instead
    if feature_group_count == 1:
        roles.append("i")
    else:
        roles.append("g")

    # Like the dot handler: every non-trivial axis must take a role —
    # the strategy space has no fully-replicated entry (with no compute
    # cost in the model, replication would otherwise always win).
    strategies = []
    seen = set()
    for assignment in itertools.product(roles, repeat=len(nontrivial)):
        lhs_map, rhs_map, out_map = {}, {}, {}
        ar_axes, halo_axes = [], []
        for axis, role in zip(nontrivial, assignment):
            if role == "b":
                if lhs_b in lhs_map:
                    break
                # batch groups must stay intact on each shard
                if (batch_group_count > 1 and
                        batch_group_count % mesh_shape[axis] != 0):
                    break
                lhs_map[lhs_b] = axis
                out_map[out_b] = axis
            elif role == "o":
                if rhs_o in rhs_map:
                    break
                if (feature_group_count > 1 and
                        feature_group_count % mesh_shape[axis] != 0):
                    break
                rhs_map[rhs_o] = axis
                out_map[out_f] = axis
            elif role == "i":
                if lhs_c in lhs_map or rhs_i in rhs_map:
                    break
                lhs_map[lhs_c] = axis
                rhs_map[rhs_i] = axis
                ar_axes.append(axis)
            elif role == "g":
                # grouped conv: whole groups split across the axis; lhs
                # channels, rhs out-channels and out features shard
                # together, no collective needed
                if (lhs_c in lhs_map or rhs_o in rhs_map or
                        feature_group_count % mesh_shape[axis] != 0):
                    break
                lhs_map[lhs_c] = axis
                rhs_map[rhs_o] = axis
                out_map[out_f] = axis
            else:  # 's': spatial sharding, halo exchange
                if lhs_s0 in lhs_map:
                    break
                lhs_map[lhs_s0] = axis
                out_map[out_s0] = axis
                halo_axes.append(axis)
        else:
            lhs_s = make_spec(len(lhs_av.shape), lhs_map)
            rhs_s = make_spec(len(rhs_av.shape), rhs_map)
            out_s = make_spec(len(out_av.shape), out_map)
            if not (spec_valid(lhs_av, lhs_s, mesh_shape) and
                    spec_valid(rhs_av, rhs_s, mesh_shape) and
                    spec_valid(out_av, out_s, mesh_shape)):
                continue
            key = (lhs_s, rhs_s, out_s)
            if key in seen:
                continue
            seen.add(key)
            out_bytes = (float(np.prod(out_av.shape)) *
                         out_av.dtype.itemsize /
                         num_shards(out_s, mesh_shape))
            cost = sum(logical_mesh.all_reduce_cost(out_bytes, a)
                       for a in ar_axes)
            # halo ring: (kernel-1) rows of the per-shard input
            # cross-section move to each neighbor (GSPMD's exchange)
            for a in halo_axes:
                shard_elems = (float(np.prod(lhs_av.shape)) /
                               num_shards(lhs_s, mesh_shape))
                spatial_len = max(int(lhs_av.shape[lhs_s0]) //
                                  mesh_shape[a], 1)
                halo_bytes = (shard_elems / spatial_len *
                              max(kernel0 - 1, 0) * lhs_av.dtype.itemsize)
                cost += logical_mesh.ppermute_cost(halo_bytes, a)
            strategies.append(
                Strategy("conv" + str(assignment), out_s, cost,
                         (lhs_s, rhs_s),
                         comm_kind=("ppermute" if halo_axes and
                                    not ar_axes else "all_reduce"),
                         tie_bias=0.0 if "b" in assignment else 1e-6))
    if not strategies:
        strategies.append(
            Strategy("R", replicated_spec(len(out_av.shape)), 0.0,
                     (replicated_spec(len(lhs_av.shape)),
                      replicated_spec(len(rhs_av.shape)))))
    return strategies


def enumerate_reduce_strategies(eqn, logical_mesh) -> List[Strategy]:
    """reduce_sum/reduce_max/...: strategies indexed by the operand spec;
    sharded reduced dims pay an all-reduce on the output."""
    mesh_shape = logical_mesh.shape
    in_av = eqn.invars[0].aval
    out_av = eqn.outvars[0].aval
    red_dims = set(eqn.params.get("axes", ()))
    kept = [d for d in range(len(in_av.shape)) if d not in red_dims]
    strategies = []
    for in_spec in enumerate_var_specs(in_av, mesh_shape):
        out_map = {}
        ar_axes = []
        for d, axes in enumerate(in_spec):
            if not axes:
                continue
            if d in red_dims:
                ar_axes.extend(axes)
            else:
                out_map[kept.index(d)] = tuple(axes) if len(axes) > 1 \
                    else axes[0]
        out_spec = make_spec(len(out_av.shape), out_map)
        if not spec_valid(out_av, out_spec, mesh_shape):
            continue
        out_bytes = (float(np.prod(out_av.shape) if out_av.shape else 1) *
                     out_av.dtype.itemsize / num_shards(out_spec, mesh_shape))
        # Reduction over sharded dims realizes as an all-reduce of the
        # output for every reduction kind (sum/max/min/...).
        cost = sum(logical_mesh.all_reduce_cost(out_bytes, a)
                   for a in ar_axes)
        strategies.append(Strategy(f"red{in_spec}", out_spec, cost,
                                   (in_spec,)))
    return strategies or [
        Strategy("R", replicated_spec(len(out_av.shape)), 0.0,
                 (replicated_spec(len(in_av.shape)),))
    ]


def enumerate_gather_strategies(eqn, logical_mesh) -> Optional[List[Strategy]]:
    """Gather handler (the reference's C++ pass enumerates strategies for
    the full HLO instruction set incl. gather — ref
    playground/auto_sharding_solver/solver.py; absent here until r3).

    Embedding lookups (``jnp.take(table, ids)``) are the headline case.
    Each non-trivial mesh axis takes one role:

      ('ib', k): shard the k-th indices batch dim — the matching output
                 batch dim shards with it, no collective;
      ('pt', d): shard a fully-sliced (passthrough) operand dim — e.g. the
                 embedding feature dim; output offset dim shards, free;
      ('ix', d): shard an indexed operand dim — vocab-parallel embedding:
                 each shard gathers its local rows (GSPMD masks out-of-
                 shard ids) and the partial outputs all-reduce.

    Returns None (fall back to the generic barrier) for exotic forms
    (batching dims, non-trailing index vector dim).
    """
    dn = eqn.params["dimension_numbers"]
    if dn.operand_batching_dims or dn.start_indices_batching_dims:
        return None
    op_av, idx_av = eqn.invars[0].aval, eqn.invars[1].aval
    out_av = eqn.outvars[0].aval
    slice_sizes = eqn.params["slice_sizes"]
    mesh_shape = logical_mesh.shape
    op_ndim, idx_ndim, out_ndim = (len(op_av.shape), len(idx_av.shape),
                                   len(out_av.shape))

    offset_dims = list(dn.offset_dims)
    batch_out_dims = [d for d in range(out_ndim) if d not in set(offset_dims)]
    idx_batch_dims = list(range(idx_ndim - 1))  # index vector dim is last
    if len(batch_out_dims) != len(idx_batch_dims):
        return None
    # operand dims surviving into the output, in order -> offset positions
    passthrough = [d for d in range(op_ndim)
                   if d not in set(dn.collapsed_slice_dims)]
    if len(passthrough) != len(offset_dims):
        return None
    full_passthrough = [d for d in passthrough
                        if slice_sizes[d] == op_av.shape[d]]
    indexed = list(dn.start_index_map)

    nontrivial = [a for a, s in enumerate(mesh_shape) if s > 1]
    if not nontrivial:
        return [Strategy("R", replicated_spec(out_ndim), 0.0,
                         (replicated_spec(op_ndim),
                          replicated_spec(idx_ndim)))]

    role_choices = ([("ib", k) for k in range(len(idx_batch_dims))] +
                    [("pt", d) for d in full_passthrough] +
                    [("ix", d) for d in indexed])
    strategies = []
    seen = set()
    for assignment in itertools.product(role_choices,
                                        repeat=len(nontrivial)):
        if len(set(assignment)) != len(assignment):
            continue
        op_map, idx_map, out_map = {}, {}, {}
        ar_axes = []
        for axis, (role, pos) in zip(nontrivial, assignment):
            if role == "ib":
                idx_map[idx_batch_dims[pos]] = axis
                out_map[batch_out_dims[pos]] = axis
            elif role == "pt":
                op_map[pos] = axis
                out_map[offset_dims[passthrough.index(pos)]] = axis
            else:  # 'ix': vocab-parallel
                op_map[pos] = axis
                ar_axes.append(axis)
        op_spec = make_spec(op_ndim, op_map)
        idx_spec = make_spec(idx_ndim, idx_map)
        out_spec = make_spec(out_ndim, out_map)
        if not (spec_valid(op_av, op_spec, mesh_shape) and
                spec_valid(idx_av, idx_spec, mesh_shape) and
                spec_valid(out_av, out_spec, mesh_shape)):
            continue
        key = (op_spec, idx_spec, out_spec)
        if key in seen:
            continue
        seen.add(key)
        out_bytes = (float(np.prod(out_av.shape) or 1) *
                     out_av.dtype.itemsize / num_shards(out_spec, mesh_shape))
        cost = sum(logical_mesh.all_reduce_cost(out_bytes, a)
                   for a in ar_axes)
        name = "g" + "".join(f"{r}{p}@{a}" for a, (r, p) in
                             zip(nontrivial, assignment))
        strategies.append(Strategy(name, out_spec, cost,
                                   (op_spec, idx_spec)))
    if not strategies:
        strategies.append(Strategy("R", replicated_spec(out_ndim), 0.0,
                                   (replicated_spec(op_ndim),
                                    replicated_spec(idx_ndim))))
    return strategies


SCATTER_PRIMS = frozenset({"scatter", "scatter-add", "scatter-mul",
                           "scatter-min", "scatter-max"})


def enumerate_scatter_strategies(eqn, logical_mesh, scatter: bool = False
                                 ) -> Optional[List[Strategy]]:
    """Scatter handler — the transpose of gather (embedding-gradient
    ``scatter-add`` is the headline case; KV-cache writes that lower to
    scatter take the same roles).  Output has the operand's shape.

      ('w', d):  shard a window (passthrough) operand dim — updates shard
                 along with it, no collective;
      ('sc', d): shard a scattered operand dim — vocab-parallel table:
                 each shard applies the updates landing in its rows
                 (GSPMD masks the rest), updates replicated, free;
      ('ub', k): shard the k-th updates batch dim — each shard scatters
                 its slice of updates, the operand-shaped partials
                 all-reduce (grad-accumulation pattern), or with
                 ``scatter`` reduce-scatter (``_reduce_scattered``).
    """
    dn = eqn.params["dimension_numbers"]
    if dn.operand_batching_dims or dn.scatter_indices_batching_dims:
        return None
    op_av, idx_av, upd_av = (eqn.invars[0].aval, eqn.invars[1].aval,
                             eqn.invars[2].aval)
    out_av = eqn.outvars[0].aval
    mesh_shape = logical_mesh.shape
    op_ndim, idx_ndim, upd_ndim = (len(op_av.shape), len(idx_av.shape),
                                   len(upd_av.shape))

    window_dims = list(dn.update_window_dims)  # positions in updates
    upd_batch_dims = [d for d in range(upd_ndim)
                      if d not in set(window_dims)]
    idx_batch_dims = list(range(idx_ndim - 1))
    if len(upd_batch_dims) != len(idx_batch_dims):
        return None
    # operand window dims (not inserted), in order -> update window positions
    op_window = [d for d in range(op_ndim)
                 if d not in set(dn.inserted_window_dims)]
    if len(op_window) != len(window_dims):
        return None
    full_window = [d for d in op_window
                   if upd_av.shape[window_dims[op_window.index(d)]] ==
                   op_av.shape[d]]
    scattered = list(dn.scatter_dims_to_operand_dims)

    nontrivial = [a for a, s in enumerate(mesh_shape) if s > 1]
    if not nontrivial:
        return [Strategy("R", replicated_spec(op_ndim), 0.0,
                         (replicated_spec(op_ndim), replicated_spec(idx_ndim),
                          replicated_spec(upd_ndim)))]

    role_choices = ([("w", d) for d in full_window] +
                    [("sc", d) for d in scattered] +
                    [("ub", k) for k in range(len(upd_batch_dims))])
    strategies = []
    seen = set()
    for assignment in itertools.product(role_choices,
                                        repeat=len(nontrivial)):
        if len(set(assignment)) != len(assignment):
            continue
        op_map, idx_map, upd_map = {}, {}, {}
        ar_axes = []
        for axis, (role, pos) in zip(nontrivial, assignment):
            if role == "w":
                op_map[pos] = axis
                upd_map[window_dims[op_window.index(pos)]] = axis
            elif role == "sc":
                op_map[pos] = axis
            else:  # 'ub'
                upd_map[upd_batch_dims[pos]] = axis
                idx_map[idx_batch_dims[pos]] = axis
                ar_axes.append(axis)
        op_spec = make_spec(op_ndim, op_map)
        idx_spec = make_spec(idx_ndim, idx_map)
        upd_spec = make_spec(upd_ndim, upd_map)
        if not (spec_valid(op_av, op_spec, mesh_shape) and
                spec_valid(idx_av, idx_spec, mesh_shape) and
                spec_valid(upd_av, upd_spec, mesh_shape)):
            continue
        key = (op_spec, idx_spec, upd_spec)
        if key in seen:
            continue
        seen.add(key)
        out_bytes = (float(np.prod(out_av.shape) or 1) *
                     out_av.dtype.itemsize / num_shards(op_spec, mesh_shape))
        cost = sum(logical_mesh.all_reduce_cost(out_bytes, a)
                   for a in ar_axes)
        name = "s" + "".join(f"{r}{p}@{a}" for a, (r, p) in
                             zip(nontrivial, assignment))
        # out spec == operand spec (scatter writes in place)
        strategies.append(Strategy(name, op_spec, cost,
                                   (op_spec, idx_spec, upd_spec)))
        if scatter:
            strategies += _reduce_scattered(strategies[-1], out_av, op_map,
                                            out_bytes, ar_axes, logical_mesh)
    if not strategies:
        strategies.append(Strategy("R", replicated_spec(op_ndim), 0.0,
                                   (replicated_spec(op_ndim),
                                    replicated_spec(idx_ndim),
                                    replicated_spec(upd_ndim))))
    return strategies


########################################
# follow-through dim mappings
########################################


def follow_dimmap(eqn, operand_idx: int) -> Optional[DimMap]:
    """If eqn's output can follow operand ``operand_idx``'s sharding via a
    pure dim-mapping, return out_dim -> operand_dim, else None."""
    prim = eqn.primitive.name
    if not eqn.outvars or not hasattr(eqn.outvars[0], "aval"):
        return None
    out_shape = eqn.outvars[0].aval.shape
    in_av = eqn.invars[operand_idx].aval if hasattr(
        eqn.invars[operand_idx], "aval") else None
    if in_av is None:
        return None
    in_shape = in_av.shape

    if prim in ELEMENTWISE_PRIMS:
        if in_shape == out_shape:
            return identity_dimmap(len(out_shape))
        # right-aligned broadcasting
        if len(in_shape) <= len(out_shape):
            off = len(out_shape) - len(in_shape)
            dm = []
            for d in range(len(out_shape)):
                if d < off:
                    dm.append(None)
                else:
                    ind = d - off
                    dm.append(ind if in_shape[ind] == out_shape[d] else None)
            return tuple(dm)
        return None
    if prim == "transpose":
        perm = eqn.params["permutation"]
        return tuple(perm)
    if prim == "broadcast_in_dim":
        bdims = eqn.params["broadcast_dimensions"]
        inv = {od: id_ for id_, od in enumerate(bdims)}
        dm = []
        for d in range(len(out_shape)):
            src = inv.get(d)
            if src is not None and in_shape[src] == out_shape[d]:
                dm.append(src)
            else:
                dm.append(None)
        return tuple(dm)
    if prim in ("reshape",):
        # mappable iff the >1-sized dims correspond 1:1 in order
        in_nt = [(d, s) for d, s in enumerate(in_shape) if s > 1]
        out_nt = [(d, s) for d, s in enumerate(out_shape) if s > 1]
        if [s for _, s in in_nt] == [s for _, s in out_nt]:
            dm = [None] * len(out_shape)
            for (od, _), (id_, _) in zip(out_nt, in_nt):
                dm[od] = id_
            return tuple(dm)
        # partial: a preserved leading-dim prefix keeps its sharding
        # (covers dim-split/merge tails like GroupNorm's
        # (N,H,W,C) <-> (N,H,W,G,C/G))
        dm = [None] * len(out_shape)
        for d in range(min(len(in_shape), len(out_shape))):
            if in_shape[d] != out_shape[d]:
                break
            dm[d] = d
        if any(x is not None for x in dm):
            return tuple(dm)
        return None
    if prim in ("squeeze",):
        dims = set(eqn.params["dimensions"])
        kept = [d for d in range(len(in_shape)) if d not in dims]
        return tuple(kept)
    if prim in ("expand_dims",):
        dims = set(eqn.params["dimensions"])
        dm = []
        src = 0
        for d in range(len(out_shape)):
            if d in dims:
                dm.append(None)
            else:
                dm.append(src)
                src += 1
        return tuple(dm)
    if prim in ("rev", "cumsum", "cumprod", "cummax", "cummin",
                "sort", "argsort"):
        if in_shape == out_shape:
            return identity_dimmap(len(out_shape))
        return None
    if prim in ("reduce_window_max", "reduce_window_min",
                "reduce_window_sum", "reduce_window", "select_and_scatter",
                "select_and_scatter_add"):
        # windowed ops keep dim correspondence (spatial sizes shrink but
        # batch/feature shardings carry through; spatial sharding costs
        # are approximated — execution correctness is GSPMD's job)
        if len(in_shape) == len(out_shape):
            return identity_dimmap(len(out_shape))
        return None
    if prim in ("pad", "slice", "dynamic_slice"):
        if len(in_shape) == len(out_shape):
            return identity_dimmap(len(out_shape))
        return None
    if prim == "dynamic_update_slice":
        # KV-cache writes: the output follows the cache operand dim-for-dim
        # (and, for the update operand, on every dim whose extent matches —
        # the updated dim stays unmapped so its sharding isn't forced onto
        # the smaller update).  GSPMD executes the sharded in-place update.
        if len(in_shape) == len(out_shape):
            return tuple(d if in_shape[d] == out_shape[d] else None
                         for d in range(len(out_shape)))
        return None
    return None


def pick_lead_operand(eqn) -> Optional[int]:
    """Choose the operand to follow: the largest non-literal one."""
    best, best_size = None, -1
    for i, v in enumerate(eqn.invars):
        if isinstance(v, Literal):
            continue
        if not hasattr(v, "aval") or not hasattr(v.aval, "shape"):
            continue
        size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
        if size > best_size:
            best, best_size = i, size
    return best


########################################
# graph construction
########################################


def build_strategy_graph(closed_jaxpr: ClosedJaxpr,
                         in_avals: Sequence[Any],
                         logical_mesh,
                         batch_flat_idx: Sequence[int],
                         option,
                         in_paths: Sequence[str] = (),
                         alias_pairs: Sequence[Tuple[int, int]] = (),
                         fixed_in_specs: Optional[Dict[int, Spec]] = None
                         ) -> StrategyGraph:
    """``fixed_in_specs``: flat invar index -> the spec the value arrives
    in whatever this graph chooses (the caller compiles the program with
    it): that invar's node has the one strategy.

    ``alias_pairs``: (flat invar index, flat outvar index) of every
    output that is written into a donated input's buffer (a stage's
    gradient accumulators).  The caller compiles such an output with its
    invar's sharding, so the graph prices that here: an edge from the
    output's source node to the invar node, whose cost is the resharding
    of the value (through the follow chain's dim map) to each spec the
    invar node can choose.  The analog of the reference ILP's alias
    constraints (ref auto_sharding.py:771-823), as a cost instead of a
    hard constraint since GSPMD can always close the gap."""
    jaxpr = closed_jaxpr.jaxpr
    mesh_shape = logical_mesh.shape
    nodes: List[Node] = []
    edges: List[Edge] = []
    # var -> (node_idx, dimmap var<-node)
    var_node: Dict[Var, Tuple[int, DimMap]] = {}
    # flat invar index -> its node (a given invar's holds the layout it
    # arrives in; the program reads the node behind it, ``relayouts``)
    invar_nodes: List[int] = []
    given_invars: List[int] = []
    relayouts: Dict[int, int] = {}

    def new_node(kind, aval, strategies, label="", invar_idx=None,
                 outvar=None):
        n = Node(len(nodes), kind, aval, strategies, label, invar_idx, outvar)
        nodes.append(n)
        return n

    def barrier_node(aval, label):
        nd = len(aval.shape) if hasattr(aval, "shape") else 0
        n = new_node("op", aval,
                     [Strategy("R", replicated_spec(nd), 0.0)], label)
        n.barrier = True
        return n

    # --- invar nodes ---
    from alpa_tpu.shard_parallel.auto_sharding import (
        is_opt_state_path, is_param_path, resolved_zero_stage)
    zero = resolved_zero_stage(option)
    batch_set = set(batch_flat_idx)
    paired = {in_idx for in_idx, _ in alias_pairs}
    for i, (v, aval) in enumerate(zip(jaxpr.invars, in_avals)):
        specs = enumerate_var_specs(aval, mesh_shape)
        if i in batch_set and option.force_batch_dim_to_mesh_dim is not None:
            a = option.force_batch_dim_to_mesh_dim
            forced = make_spec(len(aval.shape), {0: a}) \
                if len(aval.shape) and mesh_shape[a] > 1 else \
                replicated_spec(len(aval.shape))
            if spec_valid(aval, forced, mesh_shape):
                specs = (forced,)
        from alpa_tpu.shard_parallel.sharding_spec import sharded_bytes
        # Weight-update (ZeRO) sharding: optimizer-state leaves (and
        # param leaves under stage 3) get reduce-scatter-aware costed
        # strategies instead of the replication tie preference.
        path = in_paths[i] if i < len(in_paths) else ""
        zero_leaf = (zero != 0 and i not in batch_set and bool(path) and
                     (is_opt_state_path(path) or
                      (zero == 3 and is_param_path(path))))
        if zero_leaf and zero in (2, 3):
            # Forced stages: restrict to sharded layouts when any exist.
            sharded = tuple(s for s in specs if any(bool(d) for d in s))
            if sharded:
                specs = sharded
        given = (fixed_in_specs or {}).get(i)
        if given is not None and spec_valid(aval, given, mesh_shape):
            specs = (given,)
        if zero_leaf:
            nbytes = (float(np.prod(aval.shape) if aval.shape else 1) *
                      aval.dtype.itemsize)
            strategies = []
            for s in specs:
                axes = [a for dim_axes in s for a in dim_axes]
                if axes:
                    # Sharding a weight-update leaf trades the grad
                    # all-reduce for reduce-scatter (credit) but must
                    # all-gather the updated value back (charge); under
                    # the ring model the traffic terms cancel and the
                    # residual is the collective latency — the memory
                    # term (mem_bytes, 1/dp of the leaf) then decides.
                    # (One of a donated pair is charged nothing here: the
                    # gather of what it is updated into is on the pair's
                    # edge and on its kernel's.)
                    charge = 0.0 if i in paired else sum(
                        logical_mesh.all_gather_cost(nbytes, a)
                        for a in axes)
                    credit = sum(
                        logical_mesh.all_reduce_cost(nbytes, a) -
                        logical_mesh.reduce_scatter_cost(nbytes, a)
                        for a in axes)
                    strategies.append(Strategy(
                        f"zero{str(s)}", s, max(0.0, charge - credit),
                        mem_bytes=sharded_bytes(aval, s, mesh_shape),
                        comm_kind="reduce_scatter"))
                    # Quantized gradient reduce-scatter twin (ISSUE 19):
                    # same layout, but the gradient sync runs through
                    # the blockwise stochastic-rounding codec — the
                    # credit prices the *quantized* reduce-scatter, so
                    # the ILP flips per tensor exactly when the wire
                    # saving beats the encode/decode charge.  Only
                    # enumerated when the knob is on and the leaf is
                    # eligible (dtype + grad_quantize_min_bytes), so
                    # grad_quantize=off plans are byte-identical.
                    from alpa_tpu.global_env import global_config
                    gq_mode = getattr(global_config, "grad_quantize",
                                      "off")
                    if gq_mode != "off":
                        from alpa_tpu.pipeline_parallel import (
                            reshard_codec as _codec)
                        if _codec.grad_eligible(
                                aval.shape, aval.dtype, gq_mode,
                                getattr(global_config,
                                        "grad_quantize_min_bytes",
                                        65536)):
                            itemsize = int(aval.dtype.itemsize)
                            credit_q = sum(
                                logical_mesh.all_reduce_cost(nbytes, a) -
                                logical_mesh.reduce_scatter_cost_quantized(
                                    nbytes, a, itemsize)
                                for a in axes)
                            strategies.append(Strategy(
                                f"zero{str(s)}_q{gq_mode}", s,
                                max(0.0, charge - credit_q),
                                mem_bytes=sharded_bytes(
                                    aval, s, mesh_shape),
                                comm_kind="reduce_scatter",
                                codec=gq_mode))
                else:
                    # Replication keeps the full leaf resident; carry the
                    # tie penalty so equal-cost solutions prefer the
                    # sharded (memory-saving) layout.
                    strategies.append(Strategy(
                        str(s), s, 0.0, mem_bytes=sharded_bytes(
                            aval, s, mesh_shape), tie_bias=1e-6))
        else:
            strategies = [
                Strategy(str(s), s, 0.0,
                         mem_bytes=sharded_bytes(aval, s, mesh_shape),
                         # Reference-aligned tie preferences, epsilon-sized
                         # so any real cost difference still dominates:
                         # batch invars prefer a sharded leading (batch)
                         # dim; other invars (params) prefer replication
                         # (the reference's allow_replicated_parameters
                         # default).  Together the ties resolve toward
                         # data parallelism.
                         tie_bias=(1e-6 if (
                             (i in batch_set and len(aval.shape) and
                              (not s or not s[0])) or
                             (i not in batch_set and
                              any(bool(d) for d in s))) else 0.0))
                for s in specs
            ]
        n = new_node("invar", aval, strategies, f"invar{i}", invar_idx=i)
        var_node[v] = (n.idx, identity_dimmap(len(aval.shape)))
        invar_nodes.append(n.idx)
        if specs == (given,):
            given_invars.append(i)

    # constvars: replicated barriers
    for v in jaxpr.constvars:
        n = barrier_node(v.aval, "const")
        var_node[v] = (n.idx, identity_dimmap(len(n.strategies[0].out_spec)))

    def add_edge(src_idx: int, dst_idx: int, dimmap: DimMap, aval,
                 required: List[Spec]):
        """The edge src -> dst with cost[s_src, s_req] of delivering src's
        value (viewed through dimmap) as each spec its reader may
        require."""
        src_node = nodes[src_idx]
        ndim = len(aval.shape) if hasattr(aval, "shape") else 0
        size_bytes = (float(np.prod(aval.shape) if aval.shape else 1) *
                      aval.dtype.itemsize)
        C = np.zeros((len(src_node.strategies), len(required)))
        for si, st in enumerate(src_node.strategies):
            mapped, dropped = map_spec(st.out_spec, dimmap, ndim)
            drop_cost = sum(logical_mesh.all_gather_cost(size_bytes, a)
                            for a in dropped)
            for ri, req in enumerate(required):
                C[si, ri] = drop_cost + resharding_cost(
                    aval, mapped, req, logical_mesh)
        edges.append(Edge(src_idx, dst_idx, C))

    # A given input arrives as it is given (its node has the one strategy)
    # and the program reads it one node behind that, in a layout of the
    # plan's choosing: every reader, as ``make_constrained_fun`` brings the
    # argument itself there at the program's head.  The way there is paid
    # once (one gather serves every op that wants the value whole), where
    # an edge from the input to each reader would pay it once a reader.  At
    # a tie nothing is moved at the head: what can be gathered at the tail
    # instead (an update program's new kernel, where the other way is its
    # sum's gather at the head) leaves the state between them sharded; the
    # bias outweighs the free inputs' preference for replication.
    for i in given_invars:
        inv, given = nodes[invar_nodes[i]], fixed_in_specs[i]
        specs = enumerate_var_specs(inv.aval, mesh_shape)
        behind = new_node("op", inv.aval, [
            Strategy(str(s), s, 0.0, tie_bias=1e-5 if resharding_cost(
                inv.aval, given, s, logical_mesh) else 0.0)
            for s in specs], f"relayout{i}")
        relayouts[inv.idx] = behind.idx
        add_edge(inv.idx, behind.idx, identity_dimmap(len(given)), inv.aval,
                 list(specs))
        var_node[jaxpr.invars[i]] = (behind.idx,
                                     identity_dimmap(len(given)))

    def get_source(v):
        """Node+dimmap for a var, creating a replicated barrier for unknown
        sources (e.g. scan outputs)."""
        if isinstance(v, Literal):
            return None
        if v not in var_node:
            n = barrier_node(v.aval, "opaque")
            var_node[v] = (n.idx, identity_dimmap(
                len(v.aval.shape) if hasattr(v.aval, "shape") else 0))
        return var_node[v]

    flatten_info: Dict = {}
    flat_eqns = flatten_jaxpr_eqns(jaxpr, info=flatten_info)

    for eqn in flat_eqns:
        prim = eqn.primitive.name

        if prim == "pipeline":  # markers: identity pass-through
            for iv, ov in zip(eqn.invars, eqn.outvars):
                if isinstance(iv, Literal):
                    continue
                src = get_source(iv)
                if src is not None:
                    var_node[ov] = src
            continue

        if prim in ("dot_general", "conv_general_dilated"):
            if prim == "dot_general":
                # (only in a graph that is handed something: one that is
                # handed nothing is the graph it was, strategy for strategy)
                strategies = enumerate_dot_strategies(
                    eqn, logical_mesh, scatter=bool(given_invars))
            else:
                strategies = enumerate_conv_strategies(eqn, logical_mesh)
            out_av = eqn.outvars[0].aval
            n = new_node("op", out_av, strategies,
                         f"{prim.split('_')[0]}:{out_av.shape}",
                         outvar=eqn.outvars[0])
            for oi in range(2):
                v = eqn.invars[oi]
                src = get_source(v)
                if src is None:
                    continue
                src_idx, dimmap = src
                add_edge(src_idx, n.idx, dimmap, v.aval,
                         [st.operand_specs[oi] for st in strategies])
            var_node[eqn.outvars[0]] = (n.idx,
                                        identity_dimmap(len(out_av.shape)))
            continue

        if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "argmax", "argmin"):
            strategies = enumerate_reduce_strategies(eqn, logical_mesh)
            out_av = eqn.outvars[0].aval
            n = new_node("op", out_av, strategies, f"{prim}", outvar=None)
            v = eqn.invars[0]
            src = get_source(v)
            if src is not None:
                src_idx, dimmap = src
                add_edge(src_idx, n.idx, dimmap, v.aval,
                         [st.operand_specs[0] for st in strategies])
            var_node[eqn.outvars[0]] = (n.idx,
                                        identity_dimmap(len(out_av.shape)))
            continue

        if prim == "gather" or prim in SCATTER_PRIMS:
            if prim == "gather":
                strategies = enumerate_gather_strategies(eqn, logical_mesh)
            else:
                strategies = enumerate_scatter_strategies(
                    eqn, logical_mesh, scatter=bool(given_invars))
            if strategies is not None:
                out_av = eqn.outvars[0].aval
                n = new_node("op", out_av, strategies,
                             f"{prim}:{out_av.shape}", outvar=eqn.outvars[0])
                n_operands = len(strategies[0].operand_specs)
                for oi in range(n_operands):
                    v = eqn.invars[oi]
                    if isinstance(v, Literal):
                        continue
                    src = get_source(v)
                    if src is None:
                        continue
                    src_idx, dimmap = src
                    add_edge(src_idx, n.idx, dimmap, v.aval,
                             [st.operand_specs[oi] for st in strategies])
                var_node[eqn.outvars[0]] = (
                    n.idx, identity_dimmap(len(out_av.shape)))
                continue
            # exotic gather/scatter forms fall through to the barrier

        # Free nodes: ops whose inputs are all literals/scalars (constant
        # broadcasts, iota, zeros_like chains).  Materializing any sharding
        # of them is free, so they get the full spec space at zero cost and
        # the ILP aligns them with their consumers via consistency edges.
        def _scalar_or_lit(v):
            if isinstance(v, Literal):
                return True
            if not hasattr(v, "aval") or not hasattr(v.aval, "shape"):
                return True
            return (int(np.prod(v.aval.shape)) if v.aval.shape else 1) == 1

        if (eqn.outvars and hasattr(eqn.outvars[0], "aval") and
                getattr(eqn.outvars[0].aval, "shape", None) and
                all(_scalar_or_lit(v) for v in eqn.invars)):
            out_av = eqn.outvars[0].aval
            specs = enumerate_var_specs(out_av, mesh_shape)
            n = new_node("op", out_av,
                         [Strategy(str(s), s, 0.0) for s in specs],
                         f"free:{prim}")
            var_node[eqn.outvars[0]] = (n.idx,
                                        identity_dimmap(len(out_av.shape)))
            for ov in eqn.outvars[1:]:
                if hasattr(ov, "aval") and hasattr(ov.aval, "shape"):
                    bn = barrier_node(ov.aval, f"barrier:{prim}")
                    var_node[ov] = (bn.idx,
                                    identity_dimmap(len(ov.aval.shape)))
            continue

        # follow-through attempt
        lead = pick_lead_operand(eqn)
        dm = follow_dimmap(eqn, lead) if lead is not None else None
        if dm is not None:
            src = get_source(eqn.invars[lead])
            if src is not None:
                src_idx, src_dm = src
                composed = compose_dimmap(dm, src_dm)
                var_node[eqn.outvars[0]] = (src_idx, composed)
                # Side operands (bias adds, residual joins): they must match
                # the followed spec on their (right-aligned broadcast) dims.
                # Add a consistency edge (side node <-> lead node) whose cost
                # is the resharding of the *side* tensor to the lead's spec
                # viewed in output-dim space.
                out_ndim = len(eqn.outvars[0].aval.shape)
                lead_av = eqn.invars[lead].aval
                lead_size = float(np.prod(lead_av.shape) or 1)
                for oi, v in enumerate(eqn.invars):
                    if oi == lead or isinstance(v, Literal):
                        continue
                    if not (hasattr(v, "aval") and hasattr(v.aval, "shape")):
                        continue
                    side_size = float(np.prod(v.aval.shape) or 1)
                    if side_size * 8 < lead_size:
                        # small operands (biases, scalars): GSPMD replicates
                        # or reshards them cheaply; ignore in the model.
                        continue
                    osrc = get_source(v)
                    if osrc is None:
                        continue
                    o_idx, o_dm = osrc
                    if o_idx == src_idx:
                        continue
                    side_dm = follow_dimmap(eqn, oi)
                    if side_dm is None:
                        side_dm = (None,) * out_ndim
                    o_comp = compose_dimmap(side_dm, o_dm)
                    src_node_, o_node_ = nodes[src_idx], nodes[o_idx]
                    C = np.zeros((len(o_node_.strategies),
                                  len(src_node_.strategies)))
                    for si, st_o in enumerate(o_node_.strategies):
                        o_spec, o_drop = map_spec(st_o.out_spec, o_comp,
                                                  out_ndim)
                        sb = side_size * v.aval.dtype.itemsize
                        drop_cost = sum(
                            logical_mesh.all_gather_cost(sb, a)
                            for a in o_drop)
                        for li, st_l in enumerate(src_node_.strategies):
                            l_spec, _ = map_spec(st_l.out_spec, composed,
                                                 out_ndim)
                            C[si, li] = drop_cost + resharding_cost(
                                v.aval if len(v.aval.shape) == out_ndim
                                else eqn.outvars[0].aval,
                                o_spec, l_spec, logical_mesh)
                    edges.append(Edge(o_idx, src_idx, C))
                continue

        # barrier: unknown op -> replicated node per output
        for ov in eqn.outvars:
            if hasattr(ov, "aval") and hasattr(ov.aval, "shape"):
                n = barrier_node(ov.aval, f"barrier:{prim}")
                var_node[ov] = (n.idx, identity_dimmap(len(ov.aval.shape)))
                # charge gathering of inputs into the barrier
                for v in eqn.invars:
                    if isinstance(v, Literal) or not hasattr(v, "aval"):
                        continue
                    if not hasattr(v.aval, "shape"):
                        continue
                    src = get_source(v)
                    if src is None:
                        continue
                    src_idx, dimmap = src
                    add_edge(src_idx, n.idx, dimmap, v.aval,
                             [replicated_spec(len(v.aval.shape))])

    graph = StrategyGraph(nodes, edges, logical_mesh, relayouts=relayouts)
    graph.closed_jaxpr = closed_jaxpr
    graph.flat_eqns = flat_eqns
    graph.invars = list(jaxpr.invars)
    graph.constvars = list(jaxpr.constvars)
    sub_env = flatten_info.get("env", {})
    graph.outvars = [_subst(v, sub_env) for v in jaxpr.outvars]

    graph.captured_consts = flatten_info.get("captured_consts", {})

    graph.out_sources = [
        None if isinstance(ov, Literal) else var_node.get(ov)
        for ov in graph.outvars]
    # donated pairs: the output leaves in its invar's spec, whatever its
    # producer chose, and the way there is on the objective
    for in_idx, out_idx in alias_pairs:
        inv_node = nodes[invar_nodes[in_idx]]
        src = graph.out_sources[out_idx]
        if src is None:
            continue
        graph.alias_edges.append((src[0], src[1], inv_node.idx))
        graph.alias_outs[out_idx] = inv_node.idx
        if src == (inv_node.idx, identity_dimmap(len(inv_node.aval.shape))):
            continue  # the invar itself, or a sum that follows it
        add_edge(src[0], inv_node.idx, src[1], inv_node.aval,
                 [st.out_spec for st in inv_node.strategies])
    return graph


def _check_evaluable(jaxpr: Jaxpr, depth: int = 0) -> bool:
    """Mirror of the flatten traversal: True iff every inline site can be
    re-evaluated (enough outer args to bind the inner jaxpr's invars)."""
    for eqn in jaxpr.eqns:
        site = _inline_site(eqn, depth)
        if site is None:
            continue
        sub_jaxpr, _ = site
        if len(eqn.invars) < len(sub_jaxpr.invars):
            return False
        if not _check_evaluable(sub_jaxpr, depth + 1):
            return False
    return True


def make_constrained_fun(graph: StrategyGraph, choice, jax_mesh,
                         axis_names, consts, min_elements: int = 1 << 16):
    """Build a function re-evaluating the ORIGINAL jaxpr with
    ``with_sharding_constraint`` inserted on every solved dot output — so
    GSPMD realizes exactly the ILP's intra-op plan instead of relying on
    propagation (the fidelity upgrade promised by this module's header).

    The interpreter recurses into the same call primitives the analysis
    flattening inlines, in the same order, so the ILP's decisions (keyed by
    position in ``graph.flat_eqns``) attach to the right ``bind`` even
    though flattening freshens variable identities.  remat/checkpoint
    bodies are re-wrapped in ``jax.checkpoint`` (same policy/prevent_cse),
    preserving rematerialization — the constraint lands INSIDE the
    checkpointed body.
    """
    import jax as _jax
    from alpa_tpu.shard_parallel.sharding_spec import (is_replicated,
                                                       spec_to_partition_spec)

    from jax.sharding import NamedSharding

    def _sharding(spec):
        return NamedSharding(jax_mesh,
                             spec_to_partition_spec(spec, axis_names))

    def _too_small(aval):
        return (min_elements and getattr(aval, "shape", None) and
                int(np.prod(aval.shape)) < min_elements)

    # Solved op node -> constraints on its outvar AND its operands.
    # Pinning only the output is not enough for fidelity: for a
    # contracting-dim (k) dot strategy GSPMD is free to all-gather the
    # operands and compute the full dot locally unless the operands'
    # chosen shardings are pinned too (the reference C++ pass annotates
    # operand shardings for the same reason).  Tensors below
    # ``min_elements`` (AutoShardingOption.constrain_min_elements) are
    # left to propagation: pinning tiny tensors can force GSPMD
    # transitions that cost more than the constraint is worth.
    var_pos = {}
    for ei, e in enumerate(graph.flat_eqns):
        for oi, ov in enumerate(e.outvars):
            if isinstance(ov, Var):
                var_pos[ov] = (ei, oi)
    flat_eqns = graph.flat_eqns
    out_cons = {}   # (eqn_pos, out_idx) -> NamedSharding
    in_cons = {}    # (eqn_pos, operand_idx) -> NamedSharding
    for node, s in zip(graph.nodes, choice):
        if node.kind != "op" or node.outvar is None:
            continue
        if node.outvar not in var_pos:
            continue
        pos, oi = var_pos[node.outvar]
        strat = node.strategies[s]
        if not is_replicated(strat.out_spec) and not _too_small(
                node.outvar.aval):
            out_cons[(pos, oi)] = _sharding(strat.out_spec)
        eqn = flat_eqns[pos]
        for ii, op_spec in enumerate(strat.operand_specs):
            if ii >= len(eqn.invars) or is_replicated(op_spec):
                continue
            v = eqn.invars[ii]
            if isinstance(v, Literal) or _too_small(v.aval):
                continue
            in_cons[(pos, ii)] = _sharding(op_spec)
    # A given input that the program reads in another layout than it
    # arrives in (``StrategyGraph.relayouts``) is brought there once, at
    # the program's head: what the plan paid for, and what keeps GSPMD from
    # carrying the arriving layout into the ops and resharding around each.
    head_cons = {}  # flat invar index -> NamedSharding
    for inv_idx, behind_idx in graph.relayouts.items():
        inv = graph.nodes[inv_idx]
        spec = graph.nodes[behind_idx].strategies[choice[behind_idx]].out_spec
        if spec != inv.strategies[0].out_spec and not _too_small(inv.aval):
            head_cons[inv.invar_idx] = _sharding(spec)
    if not out_cons and not in_cons and not head_cons:
        return None

    root = graph.closed_jaxpr
    if root is None or not _check_evaluable(root.jaxpr):
        logger.warning(
            "skipping sharding-constraint emission: an inlined call site "
            "cannot be re-evaluated (fewer outer args than inner invars)")
        return None

    def constrained(*args):
        counter = [0]  # position in the flattened eqn order

        def _bind(eqn, env, read, depth):
            prim = eqn.primitive.name
            site = _inline_site(eqn, depth)
            if site is not None:
                sub_jaxpr, sub_consts = site
                outer_in = [read(v) for v in eqn.invars]
                aligned = _align_call_args(outer_in, sub_jaxpr.invars)
                if prim in ("remat", "checkpoint", "remat2"):
                    # ``jax.checkpoint`` binds the block anew as one that
                    # has not been differentiated, which lowers with no
                    # barrier; the block of a backward pass keeps the one
                    # its own lowering would put on its inputs, or the
                    # scheduler is free to recompute every block first
                    # and hold them all
                    if eqn.params.get("differentiated") and \
                            eqn.params.get("prevent_cse", True):
                        aligned = _jax.lax.optimization_barrier(aligned)
                    fn = functools.partial(
                        _remat_body, eval_jaxpr, sub_jaxpr, sub_consts,
                        depth)
                    fn = _jax.checkpoint(
                        fn,
                        policy=eqn.params.get("policy"),
                        prevent_cse=eqn.params.get("prevent_cse", True))
                    ans = fn(*aligned)
                else:
                    ans = eval_jaxpr(sub_jaxpr, sub_consts, aligned,
                                     depth + 1)
                for ov, a in zip(eqn.outvars, ans):
                    env[ov] = a
                return
            if prim == "pipeline":
                # boundary marker: identity passthrough (one flat slot)
                counter[0] += 1
                for iv, ov in zip(eqn.invars, eqn.outvars):
                    env[ov] = read(iv)
                return
            pos = counter[0]
            counter[0] += 1
            vals = [read(v) for v in eqn.invars]
            for ii in range(len(vals)):
                sh = in_cons.get((pos, ii))
                if sh is not None:
                    vals[ii] = _jax.lax.with_sharding_constraint(
                        vals[ii], sh)
            ans = eqn.primitive.bind(*vals, **eqn.params)
            if not eqn.primitive.multiple_results:
                ans = [ans]
            for oi, (ov, a) in enumerate(zip(eqn.outvars, ans)):
                sh = out_cons.get((pos, oi))
                if sh is not None:
                    a = _jax.lax.with_sharding_constraint(a, sh)
                env[ov] = a

        def eval_jaxpr(jaxpr, jconsts, jargs, depth):
            env = {}
            for v, c in zip(jaxpr.constvars, jconsts):
                env[v] = c
            for v, a in zip(jaxpr.invars, jargs):
                env[v] = a

            def read(v):
                if isinstance(v, Literal):
                    return v.val
                return env[v]

            for eqn in jaxpr.eqns:
                # the equation keeps its place in the model (and its
                # traceback): the name stack it was traced under, below
                # the one it is bound under here, as jax.core.eval_jaxpr
                # does.  A device trace is read by these names
                # (telemetry/device_time.py).
                with source_info_util.user_context(
                        eqn.source_info.traceback,
                        name_stack=source_info_util.current_name_stack() +
                        eqn.source_info.name_stack):
                    _bind(eqn, env, read, depth)
            return [read(v) for v in jaxpr.outvars]

        args = [_jax.lax.with_sharding_constraint(a, head_cons[i])
                if i in head_cons else a for i, a in enumerate(args)]
        return eval_jaxpr(root.jaxpr, consts, args, 0)

    return constrained


def _remat_body(eval_jaxpr, sub_jaxpr, sub_consts, depth, *args):
    return eval_jaxpr(sub_jaxpr, sub_consts, list(args), depth + 1)
