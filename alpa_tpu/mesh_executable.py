"""Mesh executables: compiled artifacts that run on one physical mesh.

TPU-native analog of the reference's ``alpa/mesh_executable.py`` (1195 LoC).
The driver/worker split collapses: there are no Ray workers, so each
``*MeshDriverExecutable``/``*MeshWorkerExecutable`` pair becomes a single
class wrapping a jit-compiled callable with explicit in/out shardings.

Key translations (SURVEY.md §2.5):
* ``NormalMeshDriverExecutable/NormalMeshWorkerExecutable``
  (ref mesh_executable.py:186/429) -> ``NormalMeshExecutable``.
* ``GradAccMeshDriverExecutable`` (ref :499) and its
  ``XLA_SKIP_NCCL_COLLECTIVE_IDS`` grad-sync-skip env hack (ref :855-894)
  -> ``GradAccMeshExecutable``: gradient accumulation is compiled *into* the
  program (shard_map local accumulation + one final reduction), since the TPU
  runtime cannot skip collectives dynamically (SURVEY.md §2.9).
* ``AllocZeroBufferDriverExecutable`` (ref :1018) -> zeros are created by XLA
  inside the compiled program; a helper remains for the pipeline runtime.
"""
import logging
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from alpa_tpu.device_mesh import PhysicalDeviceMesh
from alpa_tpu.telemetry import device_time as _device_time
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.util import benchmark_func

logger = logging.getLogger(__name__)

# dispatch (enqueue) latency of single-mesh executables — replaces the
# deprecated per-executable timers(f"exec-{uuid}-dispatch") bridge
_DISPATCH_SECONDS = _tmetrics.get_registry().histogram(
    "alpa_mesh_dispatch_seconds",
    "launch_on_driver enqueue latency per mesh executable call")

mesh_executable_counter = 0


def next_mesh_executable_uuid() -> int:
    global mesh_executable_counter
    mesh_executable_counter += 1
    return mesh_executable_counter


class MeshExecutable:
    """Base class (ref mesh_executable.py:108 MeshDriverExecutable)."""

    def __init__(self, physical_mesh: PhysicalDeviceMesh):
        self.physical_mesh = physical_mesh
        self.exec_uuid = next_mesh_executable_uuid()

    def launch_on_driver(self, *args):
        raise NotImplementedError

    def __call__(self, *args):
        return self.launch_on_driver(*args)

    # ---- introspection ----
    def get_hlo_text(self) -> str:
        raise NotImplementedError

    def get_total_allocation_size(self) -> int:
        raise NotImplementedError

    def profile_with_dummy_inputs(self, repeat=3, number=3) -> np.ndarray:
        raise NotImplementedError

    def sync(self):
        self.physical_mesh.sync_workers()


class NormalMeshExecutable(MeshExecutable):
    """A plain SPMD executable: one compiled XLA program over one mesh.

    ``compiled`` is the result of ``jax.jit(...).lower(...).compile()``;
    ``in_shardings``/``out_shardings`` are flat lists of NamedSharding;
    ``in_tree``/``out_tree`` handle pytree (un)flattening at the boundary
    (ref launch_on_driver mesh_executable.py:264: shard args -> execute ->
    wrap outs; here jax.jit does arg placement via committed shardings).
    """

    def __init__(self,
                 physical_mesh: PhysicalDeviceMesh,
                 compiled,
                 in_avals,
                 out_avals,
                 in_shardings,
                 out_shardings,
                 in_tree,
                 out_tree,
                 static_argnums: Sequence[int] = (),
                 donated_invars: Optional[Sequence[bool]] = None,
                 flop_count: Optional[float] = None):
        super().__init__(physical_mesh)
        self.compiled = compiled
        self.in_avals = in_avals
        self.out_avals = out_avals
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self.in_tree = in_tree
        self.out_tree = out_tree
        self.static_argnums = static_argnums
        self.donated_invars = donated_invars or (False,) * len(in_avals)
        self.flop_count = flop_count
        self.timer_name = f"exec-{self.exec_uuid}"
        # a capture finds the program's HLO text by the name the profiler
        # gives its runs (telemetry/device_time.py)
        _device_time.register_program(
            _device_time.compiled_name(compiled), self,
            NormalMeshExecutable.get_hlo_text)

    def launch_on_driver(self, *flat_args):
        """Execute on flat (already tree-flattened) args.

        Dispatch is async (jax futures); the
        ``alpa_mesh_dispatch_seconds`` histogram measures enqueue
        latency only.  Use ``profile_with_dummy_inputs`` or block on the
        outputs for wall-clock execution time.
        """
        t0 = time.perf_counter()
        try:
            args = self._prepare_args(flat_args)
            return self.compiled(*args)
        finally:
            _DISPATCH_SECONDS.observe(time.perf_counter() - t0)

    def _prepare_args(self, flat_args):
        """Commit plain host arrays to the mesh per the input shardings.

        jax's compiled.call path requires committed, correctly-sharded
        inputs; this is the analog of the driver's ``shard_args_to_bufs``
        (ref device_mesh.py:1287).
        """
        out = []
        for a, s in zip(flat_args, self.in_shardings):
            if (isinstance(a, jax.Array) and a.committed and
                    a.sharding.is_equivalent_to(s, a.ndim)):
                out.append(a)
            elif not s.is_fully_addressable:
                # multi-process mesh: device_put rejects shardings with
                # non-addressable devices — build the global array from
                # this process's local shards instead (every process holds
                # the full host value here)
                arr = np.asarray(a)
                out.append(jax.make_array_from_callback(
                    arr.shape, s, lambda idx, _arr=arr: _arr[idx]))
            else:
                out.append(jax.device_put(a, s))
        return out

    def get_hlo_text(self) -> str:
        return self.compiled.as_text()

    def get_plan_fingerprint(self) -> str:
        """Content hash of this executable's parallel plan (mesh extent +
        input/output avals and shardings) — the shard-parallel analog of
        ``PipeshardDriverExecutable.get_plan_fingerprint``, consumed by
        ``checkpoint.CheckpointManager`` resume validation."""
        import hashlib
        parts = [repr(tuple(self.physical_mesh.shape))]
        parts.extend(str(a) for a in self.in_avals)
        parts.extend(str(a) for a in self.out_avals)
        parts.extend(str(s) for s in self.in_shardings)
        parts.extend(str(s) for s in self.out_shardings)
        return hashlib.sha256("\n".join(parts).encode()).hexdigest()

    def get_total_allocation_size(self) -> int:
        try:
            m = self.compiled.memory_analysis()
            return int(m.temp_size_in_bytes + m.argument_size_in_bytes +
                       m.output_size_in_bytes)
        except Exception:  # pylint: disable=broad-except
            return -1

    def profile_with_dummy_inputs(self, repeat=3, number=3) -> np.ndarray:
        """Time the executable on zero inputs (ref
        profile_with_dummy_inputs, mesh_executable.py).  Donated args are
        recreated every run since execution consumes their buffers."""
        make = lambda a, s: jax.device_put(jnp.zeros(a.shape, a.dtype), s)
        persistent = [
            None if d else make(a, s) for a, s, d in zip(
                self.in_avals, self.in_shardings, self.donated_invars)
        ]

        def run():
            args = [
                make(a, s) if p is None else p for a, s, p in zip(
                    self.in_avals, self.in_shardings, persistent)
            ]
            outs = self.compiled(*args)
            jax.block_until_ready(outs)

        return benchmark_func(run, warmup=1, repeat=repeat, number=number)


class GradAccMeshExecutable(NormalMeshExecutable):
    """Executable whose program internally loops over microbatches.

    The reference runs the accumulate-grad binary N times with grad-sync
    all-reduces skipped on all but the last microbatch via env-var runtime
    hooks (ref mesh_executable.py:855-894, §2.9 grad-sync skip).  Here the
    microbatch loop is a ``lax.scan`` compiled into the single program and —
    when the batch axis is a mesh axis — gradients accumulate *locally*
    inside a shard_map with one reduction at the end, which is the same
    communication volume without any runtime hook.
    """
    # Same execution surface as NormalMeshExecutable; the difference is in
    # how shard_parallel/compile_executable.py builds the traced function.
    pass


def alloc_zero_buffers(mesh: PhysicalDeviceMesh, avals, shardings):
    """Allocate zeroed arrays on a mesh (ref AllocZeroBufferExecutable
    mesh_executable.py:1018) — used by the pipeshard runtime for gradient
    accumulators."""
    zeros_fn = jax.jit(
        lambda: [jnp.zeros(a.shape, a.dtype) for a in avals],
        out_shardings=list(shardings))
    return zeros_fn()


def get_index_select_executable(mesh: PhysicalDeviceMesh, aval, sharding,
                                dim: int):
    """Compiled index_select used by serving for beam-search KV-cache reorder
    (ref mesh_executable.py:1168)."""

    def index_select(x, idx):
        return jnp.take(x, idx, axis=dim)

    idx_aval = jax.ShapeDtypeStruct((aval.shape[dim],), jnp.int32)
    return (jax.jit(index_select,
                    in_shardings=(sharding, NamedSharding(sharding.mesh,
                                                          PartitionSpec())),
                    out_shardings=sharding)
            .lower(jax.ShapeDtypeStruct(aval.shape, aval.dtype), idx_aval)
            .compile())
