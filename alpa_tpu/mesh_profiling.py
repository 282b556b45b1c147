"""Mesh profiling database + calibrated cost estimation.

Analog of ref ``alpa/mesh_profiling.py`` (SURVEY.md §2.8): the cost-model
side of auto stage construction.  Two paths, like the reference:

* ``ProfilingResultDatabase`` — measured dot/collective costs per mesh
  signature, JSON-persisted, filled by ``profile_all`` on real hardware
  (ref ProfilingResultDatabase:162 / profile_all:725).  A
  ``CalibratedCostModel`` fitted from the measurements supplies
  seconds-per-flop (size-dependent) and per-collective alpha/beta in real
  seconds, which the ``LogicalDeviceMesh`` cost queries and the stage DP
  consume — so "auto" decisions trace back to measured numbers instead of
  abstract units.
* ``estimate_stage_cost`` — static model (ref
  ``estimate_hlo_module_cost:901`` / HloCostModelProfileWorker): analytic
  flops + the intra-op ILP objective, used as the default on TPU where
  spinning up submeshes to profile is slow (SURVEY.md §7 hard part 2).
  When the logical mesh carries a calibration, every term is in seconds.
"""
import dataclasses
import json
import logging
import math
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from alpa_tpu.device_mesh import LogicalDeviceMesh
from alpa_tpu.util import benchmark_func, jaxpr_eqn_flops

logger = logging.getLogger(__name__)

# Fallback per-chip peak when no profiling DB is loaded (abstract units are
# fine then: the DP only compares costs).  Seconds per flop.
DEFAULT_SEC_PER_FLOP = 1.0 / 100e12

COLLECTIVE_KINDS = ("all_reduce", "all_gather", "reduce_scatter",
                    "all_to_all")

# Blockwise-codec wire accounting (ISSUE 19): elements per scaling
# block, mirrored from pipeline_parallel/reshard_codec.BLOCK so the
# cost model and the codec agree on wire bytes without importing jax at
# module load.
QUANT_BLOCK = 256


def quantized_wire_bytes(num_bytes: float, itemsize: int = 4) -> float:
    """Wire bytes a gradient collective moves under the blockwise codec:
    1 byte per element plus one fp32 scale per 256-element block —
    ``n + 4 * ceil(n / 256)`` for ``n = num_bytes / itemsize`` elements
    (a ~3.94x cut for block-aligned fp32 payloads)."""
    itemsize = max(1, int(itemsize))
    elems = max(0.0, float(num_bytes)) / itemsize
    nblocks = math.ceil(elems / QUANT_BLOCK) if elems else 0
    return elems + 4.0 * nblocks

# Version stamp written into saved profiling DBs (ISSUE 12 satellite):
# load() validates it and warns on mismatch; stampless files are the
# pre-stamp legacy layout and load with a warning.
PROF_DB_SCHEMA_VERSION = 1

# (kind, key) pairs already warned for out-of-range lookups — one
# warning per distinct query, not one per call.
_warned_out_of_range: set = set()


@dataclasses.dataclass
class CalibratedCostModel:
    """Fitted from a MeshProfilingResult; all values in real seconds.

    ``dot_points``: sorted (flops, sec/flop) samples — matmul efficiency
    is size-dependent (small ops underutilize the MXU), so seconds-per-
    flop interpolates over the measured ladder.
    ``collective_ab``: kind -> (alpha latency s, beta s/byte), fitted by
    least squares on t = alpha + beta * ring_bytes.
    """
    dot_points: List[Tuple[float, float]]
    collective_ab: Dict[str, Tuple[float, float]]

    def __post_init__(self):
        pts = sorted(self.dot_points)
        self._dot_xs = np.array([p[0] for p in pts], float)
        self._dot_ys = np.array([p[1] for p in pts], float)

    def sec_per_flop(self, flops: float = 1e12) -> float:
        if not len(self._dot_xs):
            return DEFAULT_SEC_PER_FLOP
        return float(np.interp(flops, self._dot_xs, self._dot_ys))

    def alpha_beta(self, kind: str) -> Optional[Tuple[float, float]]:
        return self.collective_ab.get(kind)


class MeshProfilingResult:
    """Measured costs for one mesh signature (ref MeshProfilingResult:18).

    Collective entries record (ring_bytes, seconds) where ring_bytes
    already includes the ring factor ((n-1)/n per pass), so alpha-beta
    fits transfer across axis sizes.
    """

    def __init__(self):
        # kind -> key -> list[(size, seconds)]
        self.dot_cost_dict: Dict[Tuple, List] = {}
        self.all_reduce_cost_dict: Dict[Tuple, List] = {}
        self.all_gather_cost_dict: Dict[Tuple, List] = {}
        self.reduce_scatter_cost_dict: Dict[Tuple, List] = {}
        self.all_to_all_cost_dict: Dict[Tuple, List] = {}

    def record(self, kind: str, key: Tuple, size: float, seconds: float):
        getattr(self, f"{kind}_cost_dict").setdefault(tuple(key), []).append(
            (float(size), float(seconds)))

    def estimate(self, kind: str, key: Tuple, size: float) -> Optional[float]:
        """Linear interpolation on measured (size, time) points.

        A lookup outside the profiled size range WARNs (once per (kind,
        key) — the key carries the mesh/axis shape for collectives)
        instead of silently clamping to the nearest measured entry, so a
        query the DB cannot honestly answer is visible (ISSUE 12
        satellite)."""
        points = getattr(self, f"{kind}_cost_dict").get(tuple(key))
        if not points:
            return None
        points = sorted(points)
        sizes = np.array([p[0] for p in points], dtype=float)
        times = np.array([p[1] for p in points], dtype=float)
        if size < sizes[0] or size > sizes[-1]:
            wkey = (kind, tuple(key))
            if wkey not in _warned_out_of_range:
                _warned_out_of_range.add(wkey)
                logger.warning(
                    "profiling DB lookup out of measured range: kind=%s "
                    "key=%s size=%.3g not in [%.3g, %.3g] — clamping to "
                    "the nearest profiled entry", kind, tuple(key), size,
                    sizes[0], sizes[-1])
        return float(np.interp(size, sizes, times))

    def fit(self) -> CalibratedCostModel:
        """Least-squares alpha-beta per collective kind + dot efficiency
        curve (ref: the reference interpolates its profiled op dicts;
        here we additionally expose the fitted line so costs extrapolate
        to unmeasured sizes)."""
        dot_points = []
        for points in self.dot_cost_dict.values():
            for flops, sec in points:
                if flops > 0:
                    dot_points.append((float(flops), sec / flops))
        ab = {}
        for kind in COLLECTIVE_KINDS:
            pts = []
            for points in getattr(self, f"{kind}_cost_dict").values():
                pts.extend(points)
            if len(pts) >= 2:
                x = np.array([p[0] for p in pts], float)
                y = np.array([p[1] for p in pts], float)
                A = np.stack([np.ones_like(x), x], axis=1)
                (alpha, beta), *_ = np.linalg.lstsq(A, y, rcond=None)
                ab[kind] = (max(float(alpha), 0.0), max(float(beta), 1e-15))
            elif len(pts) == 1:
                size, sec = pts[0]
                ab[kind] = (0.0, max(sec / max(size, 1.0), 1e-15))
        return CalibratedCostModel(sorted(dot_points), ab)

    # ---- (de)serialization: JSON-friendly ----
    def to_json(self) -> Dict:
        out = {}
        for kind in ("dot",) + COLLECTIVE_KINDS:
            d = getattr(self, f"{kind}_cost_dict")
            out[kind] = {json.dumps(list(k)): v for k, v in d.items()}
        return out

    @classmethod
    def from_json(cls, data: Dict) -> "MeshProfilingResult":
        r = cls()
        for kind in ("dot",) + COLLECTIVE_KINDS:
            d = {}
            for k, v in data.get(kind, {}).items():
                d[tuple(json.loads(k))] = [tuple(p) for p in v]
            setattr(r, f"{kind}_cost_dict", d)
        return r


class ProfilingResultDatabase:
    """cluster-signature -> MeshProfilingResult (ref :162)."""

    def __init__(self, data: Optional[Dict] = None):
        self.data: Dict[str, MeshProfilingResult] = data or {}

    def query(self, cluster_key: str) -> Optional[MeshProfilingResult]:
        return self.data.get(cluster_key)

    def best_result(self) -> Optional[MeshProfilingResult]:
        """Any-mesh fallback: the entry with the most dot samples."""
        best = None
        for res in self.data.values():
            n = sum(len(v) for v in res.dot_cost_dict.values())
            if best is None or n > best[0]:
                best = (n, res)
        return best[1] if best else None

    def update_one_mesh(self, cluster_key: str,
                        result: MeshProfilingResult):
        self.data[cluster_key] = result

    def save(self, filename: str):
        with open(filename, "w", encoding="utf-8") as f:
            json.dump({"schema_version": PROF_DB_SCHEMA_VERSION,
                       "meshes": {k: v.to_json()
                                  for k, v in self.data.items()}}, f,
                      indent=1)

    @classmethod
    def load(cls, filename: str) -> "ProfilingResultDatabase":
        """Load + validate a profiling DB file (ISSUE 12 satellite):
        the stamped ``{"schema_version": N, "meshes": {...}}`` layout is
        checked against :data:`PROF_DB_SCHEMA_VERSION`; bare-dict legacy
        files (pre-stamp ``prof_database_*.json``) still load, with a
        warning suggesting a re-save."""
        with open(filename, encoding="utf-8") as f:
            raw = json.load(f)
        if "schema_version" in raw:
            version = raw["schema_version"]
            if version != PROF_DB_SCHEMA_VERSION:
                logger.warning(
                    "profiling DB %s has schema_version=%s (this build "
                    "reads %s); attempting to load anyway", filename,
                    version, PROF_DB_SCHEMA_VERSION)
            meshes = raw.get("meshes", {})
        else:
            logger.warning(
                "profiling DB %s has no schema_version stamp (legacy "
                "layout); re-save it to stamp schema_version=%s",
                filename, PROF_DB_SCHEMA_VERSION)
            meshes = raw
        return cls({k: MeshProfilingResult.from_json(v)
                    for k, v in meshes.items()})


# ---- analytic per-generation interconnect defaults ----
#
# Published single-chip/link characteristics per TPU generation (public
# spec sheets; same numbers the "How to Scale Your Model" book tabulates):
# one-way ICI bandwidth per link (GB/s), DCN per-host bandwidth (GB/s),
# and peak bf16 matmul TFLOPS.  These are the fallback where
# ``prof_database_tpu.json`` has no collective measurements (a single
# attached chip cannot measure multi-chip collectives) — the stage DP's
# comm terms then ride published link constants instead of abstract
# placeholder units (r2 VERDICT weak #4; the reference keeps an explicit
# per-cluster DB instead, ref alpa/mesh_profiling.py:162).
TPU_GENERATION_SPECS = {
    "v4": dict(ici_gbps=45.0, dcn_gbps=25.0, peak_bf16_tflops=275.0),
    "v5e": dict(ici_gbps=45.0, dcn_gbps=25.0, peak_bf16_tflops=197.0),
    "v5p": dict(ici_gbps=90.0, dcn_gbps=25.0, peak_bf16_tflops=459.0),
    "v6e": dict(ici_gbps=90.0, dcn_gbps=25.0, peak_bf16_tflops=918.0),
}
ICI_ALPHA_S = 1e-6    # per-hop launch latency over ICI
DCN_ALPHA_S = 10e-6   # cross-host (data-center network) latency

# MXU efficiency ladder for the analytic dot curve: tiny ops underfeed the
# systolic array, big ones approach (but don't reach) peak.
_ANALYTIC_DOT_EFFICIENCY = ((1e8, 0.15), (1e10, 0.40), (1e12, 0.55),
                            (1e14, 0.60))


#: ``jax.Device.device_kind`` of each generation in TPU_GENERATION_SPECS
TPU_DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
}


def detect_tpu_generation(device_kind: Optional[str] = None) -> str:
    """Key of TPU_GENERATION_SPECS for ``device_kind`` (default: the
    first jax device's).  A kind that is not in the table raises: there
    is no default peak."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in TPU_DEVICE_KINDS:
        raise ValueError(
            f"device kind {device_kind!r} is not one of "
            f"{sorted(TPU_DEVICE_KINDS)}: no published peak for it in "
            f"TPU_GENERATION_SPECS")
    return TPU_DEVICE_KINDS[device_kind]


def analytic_calibration(generation: str = "v5e",
                         fabric: str = "ici") -> CalibratedCostModel:
    """A CalibratedCostModel built from published link constants.

    Collective (alpha, beta) use the generation's one-way link bandwidth
    — the recorded x-values in this module already carry the ring factors,
    so beta is simply seconds-per-wire-byte.  The dot curve scales peak
    bf16 flops by the MXU-efficiency ladder.
    """
    spec = TPU_GENERATION_SPECS[generation]
    bw = spec["ici_gbps" if fabric == "ici" else "dcn_gbps"] * 1e9
    alpha = ICI_ALPHA_S if fabric == "ici" else DCN_ALPHA_S
    beta = 1.0 / bw
    ab = {kind: (alpha, beta) for kind in COLLECTIVE_KINDS}
    peak = spec["peak_bf16_tflops"] * 1e12
    dot_points = [(flops, 1.0 / (eff * peak))
                  for flops, eff in _ANALYTIC_DOT_EFFICIENCY]
    return CalibratedCostModel(dot_points, ab)


def merge_calibrations(primary: Optional[CalibratedCostModel],
                       fallback: CalibratedCostModel) -> CalibratedCostModel:
    """Measured entries win; the fallback fills what was never measured
    (dot curve or individual collective kinds)."""
    if primary is None:
        return fallback
    dot = primary.dot_points or fallback.dot_points
    ab = dict(fallback.collective_ab)
    ab.update(primary.collective_ab)
    return CalibratedCostModel(dot, ab)


def get_effective_calibration(platform: Optional[str] = None,
                              generation: Optional[str] = None
                              ) -> Optional[CalibratedCostModel]:
    """The calibration cost queries should use on this process's backend:
    the configured/measured DB, backfilled with the analytic generation
    defaults on TPU (where single-chip rigs can't measure collectives).
    Non-TPU platforms return the measured DB as-is (CPU meshes have their
    own measured collective DB)."""
    cal = get_global_calibration()
    if platform is None:
        try:
            import jax
            platform = jax.default_backend()
        except Exception:  # pylint: disable=broad-except
            return cal
    if platform != "tpu":
        return cal
    return merge_calibrations(
        cal, analytic_calibration(generation or detect_tpu_generation()))


# ---- global calibration ----
_global_calibration: Optional[CalibratedCostModel] = None
_calibration_explicit = False
_calibration_loaded_from: Optional[str] = None


def calibration_from_file(fname: str) -> Optional[CalibratedCostModel]:
    """Load + fit a profiling DB file; None (with a warning) on failure."""
    try:
        res = ProfilingResultDatabase.load(fname).best_result()
        if res is None:
            return None
        cal = res.fit()
        logger.info("loaded profiling DB %s (sec/flop@1T=%.3e)", fname,
                    cal.sec_per_flop())
        return cal
    except Exception as e:  # pylint: disable=broad-except
        logger.warning("loading profiling DB %s failed: %s", fname, e)
        return None


def set_global_calibration(model: Optional[CalibratedCostModel]):
    global _global_calibration, _calibration_explicit
    _global_calibration = model
    _calibration_explicit = True


def get_global_calibration() -> Optional[CalibratedCostModel]:
    """The process-wide calibration from
    ``global_config.profiling_database_filename`` (re-read whenever the
    configured filename changes, so setting the flag after meshes were
    already created still takes effect) unless set explicitly."""
    global _global_calibration, _calibration_loaded_from
    if _calibration_explicit:
        return _global_calibration
    from alpa_tpu.global_env import global_config
    fname = global_config.profiling_database_filename
    # Cache key includes the file identity (ns mtime + size) so a DB
    # written later to the same path (e.g. profile_all saving to the
    # configured filename in this process) is picked up instead of the
    # stale/failed first load.
    try:
        import os
        st = os.stat(fname) if fname else None
        ident = (st.st_mtime_ns, st.st_size) if st else None
    except OSError:
        ident = None
    key = (fname, ident)
    if key != _calibration_loaded_from:
        _calibration_loaded_from = key
        _global_calibration = calibration_from_file(fname) if fname else None
    return _global_calibration


def profile_one_mesh(physical_mesh,
                     sizes=(1 << 16, 1 << 20, 1 << 23),
                     dot_ns=(512, 1024, 2048, 4096),
                     dtype=None) -> MeshProfilingResult:
    """Measure matmul + collective times on a live mesh
    (ref profile_one_hlo_op:392, simplified: jit-timed instead of
    while-loop executables).  Collectives run as explicit shard_map
    lax collectives so the measured op is exactly the modeled one.

    Stays inside small shapes (largest dot: 4096^2 bf16 = 32 MB/operand).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    result = MeshProfilingResult()
    n_dev = physical_mesh.num_devices
    dtype = dtype or (jnp.bfloat16
                      if physical_mesh.flat_devices[0].platform == "tpu"
                      else jnp.float32)

    # dots: a ladder of sizes so MXU efficiency vs size is captured.
    # Timing protocol (ref _compile_profiling_executable_while_loop:274):
    # a dependent-chain fori_loop of k matmuls inside ONE program ending
    # in a scalar D2H readback, at two iteration counts — the difference
    # cancels both the fixed dispatch/readback cost and the loop setup.
    for n in dot_ns:
        # iteration counts scale inversely with op size so the measured
        # chain rises well above timing noise even for tiny matmuls
        k1 = 8
        k2 = max(40, int(2e11 / (2.0 * n**3)))
        a = jnp.asarray(np.random.RandomState(0).randn(n, n) * 0.01,
                        dtype)

        def chain(a, iters):
            def body(_, x):
                y = x @ a
                # keep magnitudes bounded without leaving the MXU path
                return y * jnp.asarray(0.5, dtype)
            out = jax.lax.fori_loop(0, iters, body, a)
            return out.astype(jnp.float32).sum()

        t = {}
        for k in (k1, k2):
            f = jax.jit(partial(chain, iters=k))
            t[k] = benchmark_func(lambda f=f: float(f(a)),
                                  warmup=2, repeat=2, number=3).min()
        sec = max((t[k2] - t[k1]) / (k2 - k1), 1e-9)
        result.record("dot", (np.dtype(dtype).name,), 2.0 * n**3, sec)

    if n_dev > 1:
        mesh = physical_mesh.get_logical_mesh((n_dev,)).get_jax_mesh(("x",))

        def _time(fn, x):
            f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x"),
                                  out_specs=fn.out_specs,
                                  check_vma=False))
            return benchmark_func(
                lambda: jax.block_until_ready(f(x)),
                warmup=2, repeat=2, number=5).min()

        n = n_dev
        for size in sizes:
            # multiple of n*n so P("x") sharding and the all_to_all
            # reshape(n, -1) divide evenly on any device count
            elems = -(-max(size // 4, n * n) // (n * n)) * (n * n)
            x = jax.device_put(
                jnp.zeros((elems,), jnp.float32),
                NamedSharding(mesh, P("x")))
            nbytes = float(elems * 4)

            def ar(x):
                return jax.lax.psum(x, "x")
            ar.out_specs = P("x")

            def ag(x):
                return jax.lax.all_gather(x, "x", tiled=True)
            ag.out_specs = P()

            def rs(x):
                return jax.lax.psum_scatter(x, "x", tiled=True)
            rs.out_specs = P("x")

            def a2a(x):
                y = x.reshape(n, -1)
                return jax.lax.all_to_all(y, "x", 0, 0, tiled=True)
            a2a.out_specs = P("x")

            # x-values are the effective wire bytes multiplying beta in
            # LogicalDeviceMesh's cost formulas, so fitted (alpha, beta)
            # transfer across axis sizes.  Per-device block = nbytes / n.
            ring = (n - 1) / n
            block = nbytes / n
            result.record("all_reduce", ("f32", n), 2 * ring * block,
                          _time(ar, x))
            result.record("all_gather", ("f32", n), ring * nbytes,
                          _time(ag, x))
            result.record("reduce_scatter", ("f32", n), ring * block,
                          _time(rs, x))
            result.record("all_to_all", ("f32", n), ring * block,
                          _time(a2a, x))
    return result


def profile_all(cluster, filename: Optional[str] = None
                ) -> ProfilingResultDatabase:
    """Profile the whole cluster (ref profile_all:725)."""
    db = ProfilingResultDatabase()
    mesh = cluster.get_physical_mesh()
    key = (f"{mesh.num_hosts}x{mesh.num_devices_per_host}-"
           f"{mesh.flat_devices[0].platform}")
    db.update_one_mesh(key, profile_one_mesh(mesh))
    if filename:
        db.save(filename)
    return db


########################################
# static stage cost model
########################################


def estimate_stage_cost(stage_comps,
                        logical_mesh: LogicalDeviceMesh,
                        as_option,
                        sec_per_flop: Any = None,
                        use_ilp: bool = True) -> float:
    """Estimate execution time of a merged stage on a logical mesh.

    compute = total flops * sec/flop / devices; communication = the
    intra-op strategy graph's solved ILP objective.  With a calibration
    (``sec_per_flop`` callable / calibrated logical mesh) both terms are
    real seconds; otherwise abstract units with a fixed exchange rate.
    This replaces the reference's compile-and-profile workers as the
    default path (HloCostModelProfileWorker analog).

    Under ``replan_mode != off`` (ISSUE 12), a measured stage cost from
    the calibration store — keyed by the content fingerprint (flops,
    submesh size) this function computes — supersedes the whole
    analytic estimate once it clears ``calibration_min_samples``; the
    analytic value is recorded on the entry as the drift denominator.
    """
    from alpa_tpu.pipeline_parallel.computation import merge_computations
    from alpa_tpu.telemetry import calibration as _calibration

    comp = (merge_computations(stage_comps, "cost_probe")
            if len(stage_comps) > 1 else stage_comps[0])
    flops = sum(jaxpr_eqn_flops(e) for e in comp.eqns)
    n_dev = logical_mesh.num_devices

    if sec_per_flop is None:
        cal = get_global_calibration()
        if cal is not None:
            sec_per_flop = cal.sec_per_flop(flops / max(n_dev, 1))
        else:
            sec_per_flop = DEFAULT_SEC_PER_FLOP
    elif callable(sec_per_flop):
        sec_per_flop = sec_per_flop(flops / max(n_dev, 1))
    compute_cost = flops * sec_per_flop / max(n_dev, 1)

    comm_cost = 0.0
    if use_ilp and n_dev > 1:
        try:
            from alpa_tpu.shard_parallel.ilp import (solution_cost,
                                                     solve_strategy_graph)
            from alpa_tpu.shard_parallel.strategy import build_strategy_graph
            closed = comp.closed_jaxpr()
            graph = build_strategy_graph(closed,
                                         [v.aval for v in comp.invars],
                                         logical_mesh, [], as_option)
            choice = solve_strategy_graph(graph, time_limit=10)
            units = solution_cost(graph, choice)
            if logical_mesh.calibrated:
                comm_cost = units  # already seconds
            else:
                # abstract alpha-beta units: fixed exchange rate (relative
                # ranking is what matters to the DP without a calibration)
                comm_cost = units * 1e-7
        except Exception as e:  # pylint: disable=broad-except
            logger.debug("stage ILP cost estimate failed: %s", e)
    analytic = compute_cost + comm_cost
    if _calibration.replan_active():
        store = _calibration.get_calibration_store()
        sig = _calibration.stage_cost_signature(flops, n_dev)
        store.set_modeled("stage_run", sig, analytic * 1e6)
        measured = store.measured_us("stage_run", sig)
        if measured is not None:
            return measured * 1e-6
    return analytic


#: optimizer-state bytes per parameter byte (Adam-family: mu + nu)
OPT_STATE_MULT = 2.0


def estimate_stage_memory_split(stage_comps,
                                logical_mesh: LogicalDeviceMesh,
                                as_option=None,
                                objective: str = "training"
                                ) -> Tuple[float, float]:
    """(per-device param bytes, per-device per-microbatch activation
    bytes).

    Split so the stage DP can apply the position-aware schedule-dependent
    in-flight factor (ref max_n_succ_stages, stage_profiling.py:756):
    total = param + inflight(stages_from_end, B) * act.

    Activations = outvars the stage actually produces; vars that merely
    pass through (appear among the stage's invars, e.g. parameters
    forwarded across layer slices) are excluded, and duplicates across the
    stage's layer comps count once.  Both terms divide by the submesh size:
    the intra-op planner shards parameters AND activations across it.

    When ``as_option`` is given and ``objective == "training"``, the
    param term also carries the stage's optimizer state
    (:data:`OPT_STATE_MULT` x param bytes, Adam-family): replicated
    per device under ``zero_stage=0``, divided by the submesh size
    under ZeRO weight-update sharding (``zero_stage`` 2/3 — and
    ``auto``, because the memory-budgeted ILP resolves auto to sharded
    exactly when this budget matters).  That makes the ZeRO saving
    visible to the stage DP, so stage boundaries can shift.
    """
    produced = {id(v) for c in stage_comps for v in c.outvars}
    param_bytes = 0.0
    stage_inputs = set()
    for c in stage_comps:
        for v in c.invars:
            if id(v) in produced or id(v) in stage_inputs or \
                    not hasattr(v.aval, "shape"):
                continue
            stage_inputs.add(id(v))
            param_bytes += float(np.prod(v.aval.shape) or 1) * \
                v.aval.dtype.itemsize
    act_bytes = 0.0
    counted = set()
    for c in stage_comps:
        for v in c.outvars:
            if id(v) in counted or id(v) in stage_inputs or \
                    not hasattr(v.aval, "shape"):
                continue
            counted.add(id(v))
            act_bytes += float(np.prod(v.aval.shape) or 1) * \
                v.aval.dtype.itemsize
    n = max(logical_mesh.num_devices, 1)
    opt_bytes = 0.0
    if as_option is not None and objective == "training":
        from alpa_tpu.shard_parallel.auto_sharding import (
            resolved_zero_stage)
        zero = resolved_zero_stage(as_option)
        opt_bytes = OPT_STATE_MULT * param_bytes
        if zero != 0:
            opt_bytes /= n
    return param_bytes / n + opt_bytes, act_bytes / n


def estimate_stage_memory(stage_comps, logical_mesh: LogicalDeviceMesh,
                          num_in_flight: int = 1, as_option=None) -> float:
    """Rough per-device bytes: params/devices + activations in flight."""
    p, a = estimate_stage_memory_split(stage_comps, logical_mesh,
                                       as_option=as_option)
    return p + a * num_in_flight


########################################
# measured stage profiling (opt-in)
########################################


def compile_stage_candidate(stage_comps, num_devices: int, as_option):
    """Plan + compile one candidate stage on the first ``num_devices``
    available devices; returns ``(jitted, args)`` ready for timing.

    The candidate runs under the SAME intra-op planner the final compile
    uses, so the measured time includes its collectives.  Compilation is
    thread-safe (XLA compiles under the hood), so candidates compile
    concurrently; the *timing* must stay serial.
    """
    import jax
    import jax.numpy as jnp
    from jax._src.core import jaxpr_as_fun

    from alpa_tpu.pipeline_parallel.computation import merge_computations

    comp = (merge_computations(list(stage_comps), "profile_probe")
            if len(stage_comps) > 1 else stage_comps[0])
    closed = comp.closed_jaxpr()
    fun = jaxpr_as_fun(closed)
    avals = [v.aval for v in comp.invars]

    devices = jax.devices()[:num_devices]
    if len(devices) < num_devices:
        raise ValueError(
            f"cannot profile a {num_devices}-device candidate on "
            f"{len(jax.devices())} devices")

    in_shardings = None
    if num_devices > 1 and as_option is not None and \
            getattr(as_option, "enable_auto_sharding", True):
        try:
            from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
            from alpa_tpu.shard_parallel.solver import plan_auto_sharding
            pm = LocalPhysicalDeviceMesh(devices)
            _mesh, in_shardings, cfn, _ = plan_auto_sharding(
                fun, avals, [""] * len(avals), [], pm, as_option)
            if cfn is not None:
                fun = cfn
        except Exception as e:  # pylint: disable=broad-except
            logger.debug("profile candidate planning failed: %s", e)
            in_shardings = None

    def wrapped(*args):
        outs = fun(*args)
        acc = jnp.zeros((), jnp.float32)
        for o in outs:
            if hasattr(o, "astype"):
                acc = acc + o.astype(jnp.float32).sum()
        return acc

    jitted = (jax.jit(wrapped, in_shardings=tuple(in_shardings))
              if in_shardings is not None else jax.jit(wrapped))
    args = [jnp.zeros(a.shape, a.dtype) if hasattr(a, "shape") else 0
            for a in avals]
    float(jitted(*args))  # compile + one warmup execution
    return jitted, args


def time_compiled_candidate(jitted, args, niter: int = 3) -> float:
    """Serially time a compiled candidate; ends in a scalar readback."""
    tic = time.perf_counter()
    val = None
    for _ in range(niter):
        val = jitted(*args)
    float(val)
    return (time.perf_counter() - tic) / niter


def profile_stage_cost(stage_comps, num_devices: int, as_option,
                       niter: int = 3) -> float:
    """Compile + time one candidate stage (ref ProfileWorker._profile_impl,
    stage_profiling.py:321: real submesh, dummy inputs)."""
    jitted, args = compile_stage_candidate(stage_comps, num_devices,
                                           as_option)
    return time_compiled_candidate(jitted, args, niter)


def shortlist_candidates(costs, submesh_sizes, n_avail, limit: int):
    """Pick candidates to measure, bucketed by (span length, submesh) so
    refinement touches the stage spans the DP actually considers instead
    of only the globally cheapest (= shortest-span) entries (ADVICE r2).
    Round-robins over buckets in modeled-cost order until ``limit``."""
    L, _, M = costs.shape
    buckets: Dict[Tuple[int, int], List[Tuple[float, int, int, int]]] = {}
    for i in range(L):
        for j in range(i, L):
            for m in range(M):
                if np.isfinite(costs[i, j, m]) and \
                        submesh_sizes[m] <= n_avail:
                    buckets.setdefault((j - i, m), []).append(
                        (costs[i, j, m], i, j, m))
    for b in buckets.values():
        b.sort()
    out = []
    rank = 0
    while len(out) < limit and any(len(b) > rank for b in buckets.values()):
        for key in sorted(buckets):
            b = buckets[key]
            if rank < len(b) and len(out) < limit:
                out.append(b[rank])
        rank += 1
    return out


def refine_costs_measured(costs, layer_comps, submesh_sizes, as_option,
                          limit: int = 16, compile_workers: int = 4):
    """Replace the most promising cost-model entries with measured times
    (the TPU adaptation of ref get_compute_cost's full profile sweep,
    SURVEY.md §7 hard part 2: cost model as default, real profiling as
    refinement).

    Industrial shape (ref CompileWorkerPool/ProfileWorkerPool,
    stage_profiling.py:291): candidates are shortlisted per (span,
    submesh) bucket, COMPILED concurrently on a thread pool, then TIMED
    serially (concurrent timing would corrupt the measurements).
    Failures are surfaced as warnings and the count is returned; if every
    candidate fails, raises so a broken measured mode can't silently
    masquerade as the cost model.
    """
    import concurrent.futures

    import jax

    n_avail = len(jax.devices())
    cands = shortlist_candidates(costs, submesh_sizes, n_avail, limit)
    if not cands:
        return 0
    compiled = {}
    failures = []
    with concurrent.futures.ThreadPoolExecutor(compile_workers) as pool:
        futs = {
            pool.submit(compile_stage_candidate, layer_comps[i:j + 1],
                        int(submesh_sizes[m]), as_option): (i, j, m)
            for _cost, i, j, m in cands
        }
        for fut in concurrent.futures.as_completed(futs):
            ijm = futs[fut]
            try:
                compiled[ijm] = fut.result()
            except Exception as e:  # pylint: disable=broad-except
                failures.append((ijm, repr(e)))
                logger.warning("measured profile: compiling %s failed: %s",
                               ijm, e)
    refined = 0
    for (i, j, m), (jitted, args) in sorted(compiled.items()):
        try:
            costs[i, j, m] = time_compiled_candidate(jitted, args)
            refined += 1
        except Exception as e:  # pylint: disable=broad-except
            failures.append(((i, j, m), repr(e)))
            logger.warning("measured profile: timing (%d,%d,%d) failed: %s",
                           i, j, m, e)
    if not refined and failures:
        raise RuntimeError(
            f"measured stage profiling failed for all {len(failures)} "
            f"candidates; first: {failures[0]}")
    return refined
