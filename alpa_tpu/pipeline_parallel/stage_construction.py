"""Stage construction: cluster layers into stages and assign submeshes.

Analog of ref ``alpa/pipeline_parallel/stage_construction.py`` (SURVEY.md
§2.4).  This module provides the option surface
(``UniformStageOption``/``ManualStageOption``/``AutoStageOption``), submesh
enumeration, and mesh slicing; the OSDI'22 auto DP algorithm lives in
``stage_dp.py`` (with a C++ native implementation) and is driven from here
when ``AutoStageOption`` is used.
"""
import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from alpa_tpu.device_mesh import VirtualPhysicalMesh
from alpa_tpu.telemetry import trace as _ttrace

logger = logging.getLogger(__name__)


def _cal_key_parts() -> List[str]:
    """Calibration-store fingerprint as extra cache-key parts (ISSUE 12):
    empty under ``replan_mode=off`` — keys stay byte-identical to a
    build without calibration — else one ``cal:<fingerprint>`` part, so
    a measured-cost re-solve caches separately and a warm restart with
    an unchanged store replays it with zero solves."""
    from alpa_tpu.telemetry.calibration import calibration_cache_token
    tok = calibration_cache_token()
    return [tok] if tok else []


@dataclasses.dataclass
class StageOption:
    """Base (ref stage_construction.py)."""


@dataclasses.dataclass
class UniformStageOption(StageOption):
    """Evenly assign layers to stages = meshes (ref :70)."""
    num_stages: Optional[int] = None


@dataclasses.dataclass
class ManualStageOption(StageOption):
    """Explicit layer->stage and stage->submesh assignment (ref :57)."""
    forward_stage_layer_ids: List[List[int]] = None
    submesh_physical_shapes: List[Sequence[int]] = None
    submesh_logical_shapes: List[Sequence[int]] = None
    submesh_autosharding_option_dicts: List[Dict] = None


@dataclasses.dataclass
class AutoStageOption(StageOption):
    """Search layer->stage clustering + submesh shapes with the OSDI'22 DP
    (ref :28)."""
    submesh_physical_shape_space: str = "power_of_two"
    # NOTE: logical-shape search within each submesh is delegated to the
    # per-stage intra-op planner's mesh-shape search; this field is kept
    # for reference API parity and logged if set to a non-default.
    submesh_logical_shape_space: str = "single_node_model_parallel"
    # Prune DP thresholds above tolerance * (best balanced stage cost).
    stage_imbalance_tolerance: float = np.inf
    # True (default): exact merged-span ILP comm costs for small search
    # spaces, additive per-layer ILP (prefix sums) for large ones.
    # False: exact merged-span ILP everywhere (slower; large merged spans
    # may hit the solver time limit).
    use_hlo_cost_model: bool = True
    profiling_database_filename: Optional[str] = None
    # "cost_model" (default) | "measured": compile + time the shortlisted
    # candidate stages on real devices (ref ProfileWorker path; SURVEY §7
    # hard part 2 — cost model default, real profiling opt-in)
    profiling_mode: str = "cost_model"
    # max candidates compiled+timed in "measured" mode
    measured_candidates_limit: int = 16
    # concurrent compile workers for "measured" mode (timing stays serial)
    measured_compile_workers: int = 4
    # Path to an .npz caching the (costs, mem_param, mem_act) tensors for
    # this model+mesh (the analog of ref compute-cost-<time>.npy,
    # stage_profiling.py:53).  Loaded when the content key matches;
    # recomputed and overwritten otherwise.
    cached_compute_cost: Optional[str] = None
    # Per-device memory budget in bytes (None = unconstrained).
    memory_budget_per_device: Optional[float] = None


def get_submesh_choices(num_hosts: int, num_devices_per_host: int,
                        space: str = "power_of_two"
                        ) -> List[Tuple[int, int]]:
    """Enumerate candidate submesh shapes (ref get_submesh_choices:414):
    (1, 2^k) within a host plus (k, full host) across hosts."""
    choices = []
    i = 1
    while i <= num_devices_per_host:
        choices.append((1, i))
        i *= 2
    assert choices[-1][1] == num_devices_per_host, (
        "num_devices_per_host must be a power of two")
    if space == "all":
        for k in range(2, num_hosts + 1):
            choices.append((k, num_devices_per_host))
    elif space == "power_of_two":
        k = 2
        while k <= num_hosts:
            choices.append((k, num_devices_per_host))
            k *= 2
    elif space == "small_power_of_two":
        k = 2
        while k <= min(num_hosts, 4):
            choices.append((k, num_devices_per_host))
            k *= 2
    else:
        raise ValueError(f"invalid submesh space: {space!r}")
    return choices


def get_sliced_virtual_submeshes(virtual_mesh: VirtualPhysicalMesh,
                                 submesh_shapes: List[Sequence[int]]
                                 ) -> List[VirtualPhysicalMesh]:
    """Carve the cluster into the requested submeshes
    (ref get_sliced_virtual_submeshes:529).

    Host-spanning submeshes take whole hosts; sub-host submeshes pack into
    hosts left to right.
    """
    num_hosts = virtual_mesh.num_hosts
    ndph = virtual_mesh.num_devices_per_host
    total_requested = sum(int(np.prod(s)) for s in submesh_shapes)
    assert total_requested <= virtual_mesh.num_devices, (
        f"requested {total_requested} devices > {virtual_mesh.num_devices}")
    # Pack largest-first (whole-host slices before sub-host fragments) so
    # fragments fill the gaps — mirrors ref stage_construction.py:536-539's
    # size-sorted packing; results are returned in the original order.
    order = sorted(range(len(submesh_shapes)),
                   key=lambda i: (-int(submesh_shapes[i][0]),
                                  -int(np.prod(submesh_shapes[i]))))
    submeshes = [None] * len(submesh_shapes)
    host_ptr = 0
    dev_ptr = 0
    for i in order:
        h, d = int(submesh_shapes[i][0]), int(submesh_shapes[i][1])
        if h > 1 or d == ndph:
            # whole-host slices
            if dev_ptr != 0:
                host_ptr += 1
                dev_ptr = 0
            assert host_ptr + h <= num_hosts, (
                f"not enough hosts packing submeshes {submesh_shapes}")
            sub = virtual_mesh.slice_2d(range(host_ptr, host_ptr + h),
                                        range(d))
            host_ptr += h
        else:
            if dev_ptr + d > ndph:
                host_ptr += 1
                dev_ptr = 0
            assert host_ptr < num_hosts, (
                f"not enough devices packing submeshes {submesh_shapes}")
            sub = virtual_mesh.slice_2d([host_ptr],
                                        range(dev_ptr, dev_ptr + d))
            dev_ptr += d
        submeshes[i] = sub
    return submeshes


def uniform_layer_to_stage(num_layers: int, num_stages: int
                           ) -> List[List[int]]:
    """Evenly group forward layers into stages."""
    base, rem = divmod(num_layers, num_stages)
    out, start = [], 0
    for i in range(num_stages):
        size = base + (1 if i < rem else 0)
        out.append(list(range(start, start + size)))
        start += size
    return out


def cluster_layers_and_slice_mesh(
        num_forward_layers: int,
        virtual_mesh: VirtualPhysicalMesh,
        stage_option: Optional[StageOption],
        layer_flops: Optional[Sequence[float]] = None,
        layer_comps=None,
        donation_mapping=None,
        num_micro_batches: int = 1,
        auto_sharding_option=None,
        objective: str = "training",
        schedule: str = "1f1b"):
    """Decide (forward_stage_layer_ids, submeshes, logical shapes, per-stage
    autosharding dicts) (ref cluster_layers_and_slice_mesh:571)."""
    stage_option = stage_option or UniformStageOption()

    if isinstance(stage_option, ManualStageOption):
        fwd_ids = stage_option.forward_stage_layer_ids
        phys_shapes = stage_option.submesh_physical_shapes
        logical_shapes = (stage_option.submesh_logical_shapes or
                          [None] * len(fwd_ids))
        as_dicts = (stage_option.submesh_autosharding_option_dicts or
                    [{}] * len(fwd_ids))
        submeshes = get_sliced_virtual_submeshes(virtual_mesh, phys_shapes)
        return fwd_ids, submeshes, logical_shapes, as_dicts

    if isinstance(stage_option, AutoStageOption):
        from alpa_tpu.compile_cache import cache_enabled, get_compile_cache
        from alpa_tpu.pipeline_parallel.stage_dp import auto_stage_dp

        # The DP decision is a pure function of the layer jaxprs, the
        # cluster extent, and the options — replay it from the compile
        # cache (submeshes are re-sliced from the live virtual mesh; only
        # their shapes are persisted).
        cache = key = None
        if cache_enabled():
            cache = get_compile_cache()
            comp_texts = [str(c.closed_jaxpr() if hasattr(c, "closed_jaxpr")
                              else c) for c in (layer_comps or [])]
            key = cache.make_key("stage_dp", [
                "cluster_layers_and_slice_mesh",
                repr(num_forward_layers),
                repr((virtual_mesh.num_hosts,
                      virtual_mesh.num_devices_per_host)),
                stage_option,
                repr(list(layer_flops) if layer_flops is not None else None),
                repr(num_micro_batches),
                auto_sharding_option if auto_sharding_option is not None
                else "no-as-option",
                objective,
                schedule,
            ] + comp_texts + _cal_key_parts())
            entry = cache.get("stage_dp", key)
            if entry is not None:
                try:
                    submeshes = get_sliced_virtual_submeshes(
                        virtual_mesh, entry["phys_shapes"])
                    cache.record_saved_seconds(
                        "stage_dp", entry.get("solve_seconds", 0.0))
                    return (entry["fwd_ids"], submeshes,
                            entry["logical_shapes"], entry["as_dicts"])
                except Exception:  # pylint: disable=broad-except
                    logger.warning("cached stage-DP decision failed to "
                                   "replay; re-solving", exc_info=True)

        import time
        tic = time.time()
        with _ttrace.span("stage-dp", "compile",
                          {"layers": num_forward_layers}
                          if _ttrace.enabled() else None):
            fwd_ids, submeshes, logical_shapes, as_dicts = auto_stage_dp(
                num_forward_layers, virtual_mesh, stage_option,
                layer_flops, layer_comps, num_micro_batches,
                auto_sharding_option, objective=objective,
                schedule=schedule)
        if cache is not None and key is not None:
            solve_seconds = time.time() - tic
            cache.record_solve_seconds("stage_dp", solve_seconds)
            cache.put("stage_dp", key, {
                "fwd_ids": [list(s) for s in fwd_ids],
                "phys_shapes": [(sub.num_hosts, sub.num_devices_per_host)
                                for sub in submeshes],
                "logical_shapes": list(logical_shapes),
                "as_dicts": list(as_dicts),
                "solve_seconds": solve_seconds,
            })
        return fwd_ids, submeshes, logical_shapes, as_dicts

    # Uniform: num_stages = num_hosts (or all devices as equal slices)
    num_stages = (stage_option.num_stages if isinstance(
        stage_option, UniformStageOption) and stage_option.num_stages else
        None)
    if num_stages is None:
        num_stages = (virtual_mesh.num_hosts if virtual_mesh.num_hosts > 1
                      else min(num_forward_layers,
                               virtual_mesh.num_devices_per_host))
    num_stages = min(num_stages, num_forward_layers)
    fwd_ids = uniform_layer_to_stage(num_forward_layers, num_stages)
    # split devices evenly
    if virtual_mesh.num_hosts >= num_stages and \
            virtual_mesh.num_hosts % num_stages == 0:
        hosts_per = virtual_mesh.num_hosts // num_stages
        phys_shapes = [(hosts_per, virtual_mesh.num_devices_per_host)
                       for _ in range(num_stages)]
    else:
        devs_per = virtual_mesh.num_devices // num_stages
        assert devs_per >= 1 and \
            virtual_mesh.num_devices % num_stages == 0, (
                f"cannot split {virtual_mesh.num_devices} devices into "
                f"{num_stages} equal pipeline stages; pass a stage_option "
                f"with num_stages dividing the device count")
        phys_shapes = [(1, devs_per) for _ in range(num_stages)]
    submeshes = get_sliced_virtual_submeshes(virtual_mesh, phys_shapes)
    logical_shapes = [None] * num_stages
    as_dicts = [{}] * num_stages
    return fwd_ids, submeshes, logical_shapes, as_dicts
