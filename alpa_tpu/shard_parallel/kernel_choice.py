"""A ``lax.platform_dependent`` choice whose TPU branch is a kernel, on a
mesh of more than one device: the planner plans and binds its ``default``
branch.

A Pallas kernel is one opaque call to the sharding planner and to the
compiler's partitioner: ``strategy.py`` plans a primitive it does not know
as a replicated barrier, so on a mesh of two the operands of a
``pallas_call`` would be gathered and the whole kernel run on both
devices.  The model's own choice between a kernel and plain ``jax.numpy``
(``gpt_model.attention``: the fused attention kernels where the program is
lowered for a TPU, ``reference_attention`` anywhere else) is made for ONE
device's program.  So where the mesh is first known to hold more than one
device (``compile_shard_executable``, a pipeshard stage's ``plan``) the
program is rewritten before anything is planned: every such ``cond`` (the
platform's index its first operand, ``branches_platforms`` naming the
branches) becomes a ``closed_call`` of its ``default`` branch, which the
planner inlines, plans and constrains as it did the same equations before
there was a kernel.  A differentiated choice is two such ``cond``s, the
forward pass's and the backward pass's, and both are rebound: what the
forward ``default`` saved for the backward ``default`` flows on, what the
kernel's branch would have saved is dead and goes with the program's
other dead equations (``bind_defaults``).  On a one-device mesh
nothing is rewritten: the barrier costs nothing there.

A kernel under ``shard_map`` with a strategy node of its own (batch, heads
or query positions) is what would let a sharded stage keep it (PERF.md
section 7).
"""
from typing import Callable, Dict, Sequence, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.extend.core.primitives import closed_call_p
from jax.interpreters import partial_eval as pe

# name of a ``jit`` that wraps a choice -> what to call, with the avals of
# the choice's results, each time the choice inside it is bound to its
# default (the model's gauge of which core its layers run)
_ON_DEFAULT: Dict[str, Callable[[Sequence], None]] = {}


def on_default(jit_name: str, callback: Callable[[Sequence], None]):
    """Register ``callback(avals)`` for the choices inside the ``jit``
    named ``jit_name``: the forward pass's results, and the backward
    pass's (its operands' gradients)."""
    _ON_DEFAULT[jit_name] = callback


def _sub_jaxprs(value):
    if isinstance(value, (ClosedJaxpr, Jaxpr)):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            if isinstance(item, (ClosedJaxpr, Jaxpr)):
                yield item


def _holds_kernel(jaxpr: Jaxpr) -> bool:
    """Whether ``jaxpr`` calls a compiled Pallas kernel at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                not eqn.params.get("interpret"):
            return True
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                if _holds_kernel(getattr(sub, "jaxpr", sub)):
                    return True
    return False


def _default_of(eqn):
    """The ``default`` branch of a platform choice whose other branches
    hold a kernel, else None."""
    platforms = eqn.params.get("branches_platforms")
    if eqn.primitive.name != "cond" or not platforms or \
            None not in platforms:
        return None
    branches = eqn.params["branches"]
    if not any(_holds_kernel(branch.jaxpr)
               for branch, names in zip(branches, platforms)
               if names is not None):
        return None
    return branches[platforms.index(None)]


def _rebound_in(value, told):
    """A parameter's value (a jaxpr, closed or not, or a sequence of
    them; anything else as it is) rebound, and whether anything was."""
    if isinstance(value, ClosedJaxpr):
        inner, hit = _rebound(value.jaxpr, told)
        return (value.replace(jaxpr=inner) if hit else value), hit
    if isinstance(value, Jaxpr):
        return _rebound(value, told)
    if isinstance(value, (tuple, list)) and value and all(
            isinstance(item, (ClosedJaxpr, Jaxpr)) for item in value):
        items, hits = zip(*(_rebound_in(item, told) for item in value))
        return type(value)(items), any(hits)
    return value, False


def _rebound(jaxpr: Jaxpr, told=None) -> Tuple[Jaxpr, bool]:
    """``jaxpr`` with every such choice bound to its default, at any
    depth, and whether there was one.  ``told``: what was registered for
    the ``jit`` around ``jaxpr``, if anything."""
    eqns, changed = [], False
    for eqn in jaxpr.eqns:
        default = _default_of(eqn)
        if default is not None:
            eqn = eqn.replace(
                primitive=closed_call_p, invars=eqn.invars[1:],
                params=dict(call_jaxpr=_rebound_in(default, told)[0]))
            if told is not None:
                told([v.aval for v in eqn.outvars])
            changed = True
        else:
            inner_told = _ON_DEFAULT.get(eqn.params.get("name"), told) \
                if eqn.primitive.name in ("jit", "pjit") else told
            params = {key: _rebound_in(value, inner_told)
                      for key, value in eqn.params.items()}
            if any(hit for _, hit in params.values()):
                eqn = eqn.replace(
                    params={key: value for key, (value, _) in params.items()})
                changed = True
        eqns.append(eqn)
    return (jaxpr.replace(eqns=eqns) if changed else jaxpr), changed


def bind_defaults(closed: ClosedJaxpr) -> ClosedJaxpr:
    """``closed`` with every platform choice whose TPU branch is a kernel
    bound to its ``default`` branch, less its dead equations; ``closed``
    itself where it holds no such choice."""
    jaxpr, changed = _rebound(closed.jaxpr)
    if not changed:
        return closed
    # a differentiated choice saves, for its backward pass, what EVERY
    # branch would need: the forward default fills the kernel's share with
    # zeros and the backward default never reads it.  Dead, here and in
    # the stage that would hand it on, and inside a rematerialised block
    # the compiler keeps what the program keeps (a backward stage of the
    # four-chip cell holds 0.65 GB more with it): out with the dead
    # equations, the platform's index among them.  The same pass over the
    # program traced with ``reference_attention`` alone gives this
    # program, equation for equation
    # (``tests/shard_parallel/test_kernel_choice.py``).
    jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars),
                            instantiate=True)
    return closed.replace(jaxpr=jaxpr)


def for_mesh_of(num_devices: int, fun: Callable) -> Callable:
    """``fun`` (flat arguments, flat results) as a mesh of ``num_devices``
    plans and compiles it: itself on one device; on more, its program
    with ``bind_defaults`` applied, traced once a set of argument
    shapes."""
    if num_devices == 1:
        return fun
    traced = {}

    def planned(*args):
        key = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        if key not in traced:
            closed = jax.make_jaxpr(fun)(*args)
            rebound = bind_defaults(closed)
            # a program that holds no such choice is traced as it was
            traced[key] = None if rebound is closed else rebound
        closed = traced[key]
        if closed is None:
            return fun(*args)
        return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)

    return planned
