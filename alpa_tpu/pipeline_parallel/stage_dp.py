"""Auto stage construction: cost tensor + the OSDI'22 dynamic program.

Analog of ref ``get_compute_cost`` (stage_profiling.py:1163) +
``training_dp`` (stage_construction.py:235-311).  The compute-cost tensor
C[i, j, m] (layers i..j on submesh choice m) is filled by the static cost
model (mesh_profiling.estimate_stage_cost — the HloCostModelProfileWorker
analog, default on TPU) and the DP minimizing
``sum(stage costs) + (B-1) * max(stage cost)`` runs in native C++
(csrc/stage_dp.cc, built to alpa_tpu/_native/libstage_dp.so) with a pure
Python fallback.
"""
import ctypes
import logging
import os
import subprocess
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from alpa_tpu.global_env import global_config

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libstage_dp.so")
_CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_NATIVE_DIR)),
                         "csrc")

_lib = None
_lib_tried = False

# Must match csrc/stage_dp.cc kAbiVersion.  A stale .so called through a
# newer ctypes signature silently corrupts the output buffers, so the
# loader refuses any library that can't prove the right version.
_ABI_VERSION = 2

# inflight_mode codes (csrc/stage_dp.cc inflight_count)
_INFLIGHT_MODES = {"1f1b": 0, "pipedream_flush": 0, "gpipe": 1,
                   "1f1b_overlap_friendly": 2, "inference": 3}


def _load_native():
    """Load (building if needed) the C++ DP solver.

    ``make`` runs unconditionally — it is timestamp-incremental, so this is
    a no-op when the .so is fresh, and it transparently rebuilds after a
    source change (an in-place upgrade otherwise keeps a stale binary with
    an incompatible ABI).
    """
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    makefile = os.path.join(_CSRC_DIR, "Makefile")
    if os.path.exists(makefile):
        try:
            subprocess.run(["make", "-C", _CSRC_DIR], check=True,
                           capture_output=True, timeout=120)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning("building libstage_dp.so failed: %s", e)
    if os.path.exists(_LIB_PATH):
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            try:
                lib.stage_dp_abi_version.restype = ctypes.c_int32
                abi = int(lib.stage_dp_abi_version())
            except AttributeError:
                abi = -1
            if abi != _ABI_VERSION:
                logger.warning(
                    "libstage_dp.so ABI %d != expected %d (stale build?); "
                    "using the Python fallback", abi, _ABI_VERSION)
                return None
            lib.stage_dp_solve.restype = ctypes.c_int
            lib.stage_dp_solve.argtypes = [
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                ctypes.c_double,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ]
            _lib = lib
        except OSError as e:
            logger.warning("loading libstage_dp.so failed: %s", e)
    return _lib


def stage_dp_solve(costs: np.ndarray,
                   submesh_sizes: Sequence[int],
                   num_devices: int,
                   num_micro_batches: int,
                   mem_param: Optional[np.ndarray] = None,
                   mem_act: Optional[np.ndarray] = None,
                   mem_budget: float = 0.0,
                   inflight_mode: str = "1f1b"
                   ) -> Optional[List[Tuple[int, int, int]]]:
    """Solve the stage-construction DP.

    costs: (L, L, M) float64; costs[i, j, m] = cost of layers i..j (incl.)
    on submesh m (inf = infeasible).  Memory feasibility is position-aware
    (ref max_n_succ_stages, stage_profiling.py:756): the s-th stage from
    the pipeline end holds inflight(s) microbatches of activations, where
    inflight depends on the schedule (``inflight_mode``): "1f1b" min(s, B),
    "gpipe" B, "1f1b_overlap_friendly" min(2s-1, B) (eager forwards),
    "inference" 1 (forward-only — nothing stacks).  The check is
    ``mem_param + inflight(s) * mem_act <= mem_budget``.
    Returns list of (start_layer, end_layer_exclusive, submesh_idx) or
    None if infeasible.
    """
    L, _, M = costs.shape
    costs = np.ascontiguousarray(costs, np.float64)
    sizes = np.ascontiguousarray(submesh_sizes, np.int64)
    if mem_param is None:
        mem_param = np.zeros_like(costs)
    if mem_act is None:
        mem_act = np.zeros_like(costs)
    mem_param = np.ascontiguousarray(mem_param, np.float64)
    mem_act = np.ascontiguousarray(mem_act, np.float64)
    mode = _INFLIGHT_MODES.get(inflight_mode, 0)

    lib = _load_native()
    if lib is not None:
        starts = np.zeros(L, np.int32)
        meshes = np.zeros(L, np.int32)
        S = lib.stage_dp_solve(L, M, num_devices, num_micro_batches, mode,
                               costs, sizes, mem_param, mem_act, mem_budget,
                               starts, meshes)
        if S < 0:
            return None
        out = []
        for t in range(S):
            end = starts[t + 1] if t + 1 < S else L
            out.append((int(starts[t]), int(end), int(meshes[t])))
        return out
    return _stage_dp_python(costs, sizes, num_devices, num_micro_batches,
                            mem_param, mem_act, mem_budget, mode)


def _inflight_count(s, B, mode):
    b = max(B, 1)
    if mode == 1:  # gpipe
        return b
    if mode == 2:  # overlap-friendly 1f1b
        return min(2 * s - 1, b)
    if mode == 3:  # inference
        return 1
    return min(s, b)  # 1f1b


def _stage_dp_python(C, sizes, D, B, mem_param, mem_act, mem_budget, mode=0):
    """Pure-Python fallback, same algorithm as csrc/stage_dp.cc
    (f[l][d][s] with the suffix-stage-count dimension for position-aware
    schedule-dependent memory feasibility)."""
    L, _, M = C.shape
    INF = float("inf")
    finite = C[np.isfinite(C)]
    if finite.size == 0:
        return None
    candidates = np.unique(finite)
    best_obj, best_part = INF, None

    for t_max in candidates:
        if best_part is not None and (B - 1) * t_max >= best_obj:
            break
        f = np.full((L + 1, D + 1, L + 1), INF)
        cj = np.full((L + 1, D + 1, L + 1), -1, np.int32)
        cm = np.full((L + 1, D + 1, L + 1), -1, np.int32)
        f[L][0][0] = 0.0
        for l in range(L - 1, -1, -1):
            for d in range(1, D + 1):
                for s in range(1, L - l + 1):
                    inflight = _inflight_count(s, B, mode)
                    for j in range(l, L):
                        for m in range(M):
                            n = int(sizes[m])
                            if n > d:
                                continue
                            c = C[l, j, m]
                            if not np.isfinite(c) or c > t_max:
                                continue
                            if mem_budget > 0 and \
                                    mem_param[l, j, m] + inflight * \
                                    mem_act[l, j, m] > mem_budget:
                                continue
                            rest = f[j + 1][d - n][s - 1]
                            if rest == INF:
                                continue
                            if c + rest < f[l][d][s]:
                                f[l][d][s] = c + rest
                                cj[l][d][s] = j
                                cm[l][d][s] = m
        s_best = int(np.argmin(f[0][D]))
        if f[0][D][s_best] == INF:
            continue
        obj = f[0][D][s_best] + (B - 1) * t_max
        if obj < best_obj:
            part = []
            l, d, s = 0, D, s_best
            ok = True
            while l < L:
                j, m = int(cj[l][d][s]), int(cm[l][d][s])
                if j < 0:
                    ok = False
                    break
                part.append((l, j + 1, m))
                d -= int(sizes[m])
                l = j + 1
                s -= 1
            if ok and d == 0 and s == 0:
                best_obj, best_part = obj, part
    return best_part


########################################
# compute-cost tensor disk cache
########################################


def compute_cost_cache_key(layer_comps, choices, profiling_mode,
                           with_memory=False, calibration=None,
                           db_file=None, measured_limit=None,
                           exact_ilp=None, sharding_option=None,
                           objective: str = "training") -> str:
    """Content key: the layers' jaxprs + the submesh search space + the
    profiling mode + whether memory tensors were computed + the effective
    calibration.  Any change invalidates the cache.

    ``with_memory`` matters because the stored mem_param/mem_act tensors
    are all-zero when no memory budget was set at write time; reusing them
    under a budget would make the DP's feasibility check vacuous.
    ``calibration``/``db_file`` matter because the cost tensor bakes in the
    profiling DB's fit — switching DBs or TPU generations must miss (an
    in-place re-profile changes the fitted dot_points/collective_ab and so
    the key).  ``measured_limit`` matters in measured mode: a wider
    refinement sweep produces a different tensor.  ``exact_ilp`` (merged
    -span ILP vs additive prefix sums) and ``sharding_option`` (feeds
    every per-span ILP solve) also shape the tensor and must miss.
    """
    import hashlib
    h = hashlib.sha256()
    for c in layer_comps:
        h.update(str(c.closed_jaxpr() if hasattr(c, "closed_jaxpr")
                     else c).encode())
    h.update(repr(list(choices)).encode())
    h.update(profiling_mode.encode())
    h.update(b"mem" if with_memory else b"nomem")
    h.update(repr(db_file).encode())
    if profiling_mode == "measured":
        h.update(repr(measured_limit).encode())
    h.update(repr(exact_ilp).encode())
    h.update(repr(sharding_option).encode())
    # the memory tensors carry the objective-dependent optimizer-state
    # (ZeRO) term; training vs inference tensors must not alias
    h.update(objective.encode())
    if calibration is not None:
        h.update(repr(sorted(calibration.dot_points)).encode())
        h.update(repr(sorted(calibration.collective_ab.items())).encode())
    # the cost tensor bakes estimate_stage_cost's calibration-store
    # consults in (ISSUE 12) — no token under replan_mode=off
    from alpa_tpu.telemetry.calibration import calibration_cache_token
    tok = calibration_cache_token()
    if tok:
        h.update(tok.encode())
    return h.hexdigest()[:16]


def load_compute_cost_cache(path, key, shape):
    """(costs, mem_param, mem_act) from ``path`` if the stored key and
    shapes match, else None."""
    if not os.path.exists(path):
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            if str(z["key"]) != key or z["costs"].shape != shape:
                logger.info("compute-cost cache %s stale (key/shape "
                            "mismatch); recomputing", path)
                return None
            return z["costs"], z["mem_param"], z["mem_act"]
    except Exception as e:  # pylint: disable=broad-except
        logger.warning("compute-cost cache %s unreadable: %s", path, e)
        return None


def save_compute_cost_cache(path, key, costs, mem_param, mem_act):
    try:
        np.savez(path, key=np.str_(key), costs=costs, mem_param=mem_param,
                 mem_act=mem_act)
        logger.info("auto-stage DP: saved compute-cost cache %s", path)
    except OSError as e:
        logger.warning("saving compute-cost cache %s failed: %s", path, e)


########################################
# orchestration: cost tensor + DP -> stage assignment
########################################


def auto_stage_dp(num_layers, virtual_mesh, stage_option, layer_flops,
                  layer_comps, num_micro_batches, auto_sharding_option,
                  objective: str = "training", schedule: str = "1f1b"):
    """Fill the cost tensor with the static cost model and run the DP
    (ref cluster_layers_and_slice_mesh auto branch, stage_construction.py:
    571 + SURVEY.md §3.4)."""
    from alpa_tpu.mesh_profiling import (estimate_stage_cost,
                                         estimate_stage_memory_split)
    from alpa_tpu.pipeline_parallel.stage_construction import (
        get_sliced_virtual_submeshes, get_submesh_choices)

    tic = time.time()
    choices = get_submesh_choices(
        virtual_mesh.num_hosts, virtual_mesh.num_devices_per_host,
        getattr(stage_option, "submesh_physical_shape_space",
                "power_of_two"))
    sizes = [h * d for (h, d) in choices]
    L, M = num_layers, len(choices)
    D = virtual_mesh.num_devices

    from alpa_tpu.device_mesh import LogicalDeviceMesh

    if getattr(stage_option, "submesh_logical_shape_space",
               "single_node_model_parallel") != "single_node_model_parallel":
        logger.warning(
            "submesh_logical_shape_space=%r: per-stage logical shapes are "
            "searched by the intra-op planner, not here",
            stage_option.submesh_logical_shape_space)

    # Calibrate from a profiling DB (ref ProfilingResultDatabase path):
    # an explicit per-option DB wins, else the process-global one
    # (global_config.profiling_database_filename).  The fit supplies
    # size-dependent sec/flop and per-collective alpha-beta in real
    # seconds, so the DP's decisions trace back to measurements.
    from alpa_tpu.mesh_profiling import (calibration_from_file,
                                         get_effective_calibration)
    db_file = getattr(stage_option, "profiling_database_filename", None)
    cal = calibration_from_file(db_file) if db_file else None
    if cal is None:
        # measured DB backfilled with analytic per-generation link
        # constants on TPU (published ICI bandwidths; VERDICT r2 next #8)
        cal = get_effective_calibration()

    # Span cost estimation strategy: exact merged-span ILP for small
    # search spaces (or when forced via use_hlo_cost_model=False);
    # otherwise ADDITIVE per-layer ILP — L*M solves whose prefix sums give
    # every span.  Running the merged ILP on huge spans is both slow and
    # wrong: past the solver time limit the greedy fallback returns
    # replication-heavy plans whose comm terms invert the cost ladder
    # (wide submeshes looked slower than one device).
    exact_ilp = not getattr(stage_option, "use_hlo_cost_model", True) or \
        (L * L * M <= 256)
    mem_budget = float(
        getattr(stage_option, "memory_budget_per_device", None) or 0.0)
    measured_limit = getattr(stage_option, "measured_candidates_limit", 16)

    # Disk cache of the cost tensors (ref compute-cost-<time>.npy,
    # stage_profiling.py:53), keyed by the model + search-space content so
    # auto-stage decisions are reproducible across runs without re-running
    # the cost model / measured sweep.
    cache_file = getattr(stage_option, "cached_compute_cost", None)
    cache_key = None
    if cache_file:
        cache_key = compute_cost_cache_key(
            layer_comps, choices,
            getattr(stage_option, "profiling_mode", "cost_model"),
            with_memory=mem_budget > 0, calibration=cal, db_file=db_file,
            measured_limit=measured_limit, exact_ilp=exact_ilp,
            sharding_option=auto_sharding_option, objective=objective)
        cached = load_compute_cost_cache(cache_file, cache_key, (L, L, M))
        if cached is not None:
            costs, mem_param, mem_act = cached
            logger.info("auto-stage DP: loaded compute-cost cache %s",
                        cache_file)
            cache_file = None  # hit: skip recompute + rewrite

    if cache_key is None or cache_file:
        costs = np.full((L, L, M), np.inf)
        mem_param = np.zeros((L, L, M))
        mem_act = np.zeros((L, L, M))
        for m, (h, d) in enumerate(choices):
            # cost-model-only logical mesh of the candidate submesh shape
            shape = (h * d, 1) if h == 1 else (h, d)
            logical = LogicalDeviceMesh(
                None, np.arange(h * d).reshape(shape),
                mesh_beta=(0.1 if h > 1 else 0.01, 0.01),
                calibration=cal)
            kwargs = {}
            if cal is not None:
                kwargs["sec_per_flop"] = cal.sec_per_flop
            if exact_ilp:
                for i in range(L):
                    for j in range(i, L):
                        costs[i, j, m] = estimate_stage_cost(
                            layer_comps[i:j + 1], logical,
                            auto_sharding_option, use_ilp=True, **kwargs)
            else:
                per_layer = [
                    estimate_stage_cost([layer_comps[l]], logical,
                                        auto_sharding_option, use_ilp=True,
                                        **kwargs)
                    for l in range(L)
                ]
                pref = np.concatenate([[0.0], np.cumsum(per_layer)])
                for i in range(L):
                    for j in range(i, L):
                        costs[i, j, m] = pref[j + 1] - pref[i]
            if mem_budget > 0:
                for i in range(L):
                    for j in range(i, L):
                        mem_param[i, j, m], mem_act[i, j, m] = \
                            estimate_stage_memory_split(
                                layer_comps[i:j + 1], logical,
                                as_option=auto_sharding_option,
                                objective=objective)

        if getattr(stage_option, "profiling_mode",
                   "cost_model") == "measured":
            from alpa_tpu.mesh_profiling import refine_costs_measured
            n = refine_costs_measured(
                costs, layer_comps, sizes, auto_sharding_option,
                limit=measured_limit,
                compile_workers=getattr(stage_option,
                                        "measured_compile_workers", 4))
            logger.info("measured stage profiling refined %d candidates", n)

        if cache_file:
            save_compute_cost_cache(cache_file, cache_key, costs, mem_param,
                                    mem_act)

    # stage_imbalance_tolerance: cap the DP's max-stage-cost threshold at
    # tolerance * (best perfectly-balanced stage cost estimate).
    tol = float(getattr(stage_option, "stage_imbalance_tolerance", np.inf))
    if np.isfinite(tol):
        finite = costs[np.isfinite(costs)]
        if finite.size:
            balanced = float(np.nanmin(
                [costs[0, L - 1, m] for m in range(M)
                 if np.isfinite(costs[0, L - 1, m])] or [np.inf]))
            cap = tol * balanced / max(1, 1)
            costs = np.where(costs <= cap, costs, np.inf)

    # objective="inference" (ref inference_dp, stage_construction.py:403):
    # a forward-only pipeline's throughput is bottlenecked by the slowest
    # stage, so minimize max stage cost first (sum as tie-break) — the
    # training objective with B -> large.  The memory feasibility check is
    # decoupled from B_eff via inflight_mode: a forward-only pipeline holds
    # ~1 microbatch per stage regardless of the objective's B, and training
    # schedules each have their own in-flight profile.
    if objective == "inference":
        B_eff, inflight_mode = 4096, "inference"
    else:
        B_eff, inflight_mode = num_micro_batches, schedule
    part = stage_dp_solve(costs, sizes, D, B_eff, mem_param,
                          mem_act, mem_budget=mem_budget,
                          inflight_mode=inflight_mode)
    if part is None:
        raise RuntimeError(
            "auto stage construction found no feasible partition")
    logger.info("auto-stage DP: %d stages in %.2f s: %s",
                len(part), time.time() - tic,
                [(a, b, choices[m]) for a, b, m in part])

    fwd_ids = [list(range(a, b)) for a, b, _m in part]
    phys_shapes = [list(choices[m]) for _a, _b, m in part]
    submeshes = get_sliced_virtual_submeshes(virtual_mesh, phys_shapes)
    logical_shapes = [None] * len(part)
    as_dicts = [{}] * len(part)
    return fwd_ids, submeshes, logical_shapes, as_dicts
