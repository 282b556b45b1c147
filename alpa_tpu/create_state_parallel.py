"""CreateStateParallel: initialize the train state *already sharded*.

Analog of ref ``alpa/create_state_parallel.py`` (SURVEY.md §2.1): the state
initialization function is compiled with output shardings copied from an
already-compiled train step's input placement, so big models materialize
directly in their distributed layout (never unsharded on one host).
"""
import logging
from typing import Any, Callable, Optional, Sequence

import jax

from alpa_tpu.mesh_executable import NormalMeshExecutable
from alpa_tpu.parallel_method import ParallelMethod

logger = logging.getLogger(__name__)


class CreateStateParallel(ParallelMethod):
    """method=CreateStateParallel(train_step, state_example_args) for
    ``parallelize``-ing an init function (ref CreateStateParallel:336).

    ``train_step`` must be a ParallelizedFunc already compiled (or
    compilable) whose first argument is the state.
    """

    def __init__(self, train_step, train_step_args: Sequence[Any]):
        self.train_step = train_step
        self.train_step_args = train_step_args

    def compile_executable(self, fun, in_avals, in_tree, in_paths,
                           donated_invars, batch_invars):
        # Compile/fetch the target executable to read its input placement.
        executable, _ = self.train_step.get_executable(
            *self.train_step_args)

        from alpa_tpu.pipeline_parallel.pipeshard_executable import (
            PipeshardDriverExecutable)
        if isinstance(executable, PipeshardDriverExecutable):
            return _compile_create_state_pipeshard(fun, in_avals,
                                                   executable)
        # ShardParallel target: state leaves are the leading invars of the
        # train step; their shardings become our output shardings.
        n_out = len(jax.tree_util.tree_leaves(
            jax.eval_shape(fun, *in_avals)))
        out_shardings = list(executable.in_shardings[:n_out])
        jitted = jax.jit(fun, out_shardings=out_shardings)
        lowered = jitted.lower(*in_avals)
        compiled = lowered.compile()
        return NormalMeshExecutable(
            executable.physical_mesh, compiled,
            in_avals=in_avals, out_avals=None,
            in_shardings=[None] * len(in_avals),
            out_shardings=out_shardings,
            in_tree=in_tree, out_tree=None)


def _compile_create_state_pipeshard(fun, in_avals, pipeshard_exec):
    """Pipeshard target: every state leaf must materialize on the mesh its
    consuming stage lives on (ref compile_create_state_executable:73 /
    propagate_mesh_assignment:151), in the sharding the step reads it
    with.  One program a mesh, whose outputs are that mesh's leaves with
    their shardings (the compiler drops what the others need): no leaf is
    first made whole on one device and re-laid out from there, which held
    the whole state, and a second copy of every leaf that is sharded, on
    the first device."""
    gin = pipeshard_exec.global_invars
    place = pipeshard_exec.input_place
    n_out = len(jax.eval_shape(fun, *in_avals))
    # mesh (None: a leaf the step places nowhere) -> [(leaf, sharding)]
    by_mesh = {}
    for i in range(n_out):
        v = gin[i] if i < len(gin) else None
        mesh_id, sharding = place[v][0] if v in place else (None, None)
        by_mesh.setdefault(mesh_id, []).append((i, sharding))

    def program(mesh_id, members):
        keep = [i for i, _ in members]

        def leaves(*flat_args):
            outs = fun(*flat_args)
            return [outs[i] for i in keep]

        if mesh_id is None:
            return keep, jax.jit(leaves)
        return keep, jax.jit(leaves, out_shardings=[s for _, s in members])

    programs = [program(m, members) for m, members in by_mesh.items()]

    class _CreateStatePipeshardExecutable:

        def __init__(self):
            self.out_tree = None
            self.in_avals = in_avals

        def launch_on_driver(self, *flat_args):
            placed = [None] * n_out
            for keep, jitted in programs:
                for i, x in zip(keep, jitted(*flat_args)):
                    placed[i] = x
            return placed

    return _CreateStatePipeshardExecutable()
