"""Cross-mesh resharding: tiling math + transfer planning.

Analog of ref ``alpa/pipeline_parallel/cross_mesh_resharding.py`` +
``resharding_tensor.py`` (SURVEY.md §2.4, hard part #1 in §7): when an
activation produced with sharding A on mesh X is consumed with sharding B
on mesh Y, plan the minimal set of tile transfers.

TPU redesign: the reference drives NCCL P2P per tile; here each planned
``TileSlice`` transfer executes as a ``jax.device_put`` of the source
shard slice to the destination devices (the jax runtime carries it over
ICI/DCN), and whole-array moves use a single device_put.  The value of the
planner is (a) minimal bytes on DCN — only the tiles a destination
actually needs move, with load-balanced source selection when a tile is
replicated on several sources (ref load-balancing solvers :1448-1884) —
and (b) the **local-allgather rewrite** (MLSys'23, ref
``_rewrite_allgather_spec:995``): when the destination sharding replicates
over some mesh axis, send each destination device only a 1/k slice and
all-gather inside the destination mesh over ICI instead of pulling full
tiles over DCN.
"""
import dataclasses
import itertools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from alpa_tpu import fault
from alpa_tpu.telemetry import metrics as _tmetrics
from alpa_tpu.telemetry import trace as _ttrace

logger = logging.getLogger(__name__)


########################################
# tiling math (ref resharding_tensor.py)
########################################

Slice = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Tile:
    """An axis-aligned hyper-rectangle of the global array
    (ref resharding_tensor.py:197)."""
    slices: Tuple[Slice, ...]

    @property
    def shape(self):
        return tuple(b - a for a, b in self.slices)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.slices else 1

    def intersect(self, other: "Tile") -> Optional["Tile"]:
        out = []
        for (a1, b1), (a2, b2) in zip(self.slices, other.slices):
            lo, hi = max(a1, a2), min(b1, b2)
            if lo >= hi:
                return None
            out.append((lo, hi))
        return Tile(tuple(out))

    def offset_in(self, outer: "Tile") -> Tuple[Slice, ...]:
        """This tile's index range relative to ``outer``'s origin."""
        return tuple((a - oa, b - oa)
                     for (a, b), (oa, _ob) in zip(self.slices, outer.slices))


@dataclasses.dataclass
class TileSlice:
    """A piece of a source tile headed to one destination
    (ref resharding_tensor.py:234)."""
    tile: Tile                 # global coordinates of the moved piece
    src_shard_index: int       # which source shard holds it
    offset_in_src: Tuple[Slice, ...]


class VirtualDistributedArray:
    """Sharding-as-tiling view of one array on one mesh
    (ref resharding_tensor.py:25).

    ``shard_tiles``: per device-shard the global Tile it holds;
    replicated shardings produce identical tiles on several shards.
    """

    def __init__(self, shape: Tuple[int, ...], device_tiles: List[Tile],
                 device_ids: List[int]):
        self.shape = tuple(shape)
        self.device_tiles = device_tiles
        self.device_ids = device_ids

    @classmethod
    def from_sharding(cls, shape, sharding) -> "VirtualDistributedArray":
        """Build from a NamedSharding via its device index map."""
        index_map = sharding.devices_indices_map(tuple(shape))
        tiles, ids = [], []
        for dev, idx in index_map.items():
            sl = tuple(
                (s.start or 0, s.stop if s.stop is not None else dim)
                for s, dim in zip(idx, shape)) if len(shape) else ()
            tiles.append(Tile(sl))
            ids.append(dev.id)
        return cls(shape, tiles, ids)

    @property
    def unique_tiles(self) -> Dict[Tuple, List[int]]:
        """tile slices -> list of shard positions holding it."""
        out: Dict[Tuple, List[int]] = {}
        for i, t in enumerate(self.device_tiles):
            out.setdefault(t.slices, []).append(i)
        return out


########################################
# transfer plan (ref ReshardingTaskSpec:674)
########################################


@dataclasses.dataclass
class DstTileRequest:
    """One destination shard's needs: the tile slices covering it."""
    dst_shard_index: int
    dst_tile: Tile
    srcs: List[TileSlice]


@dataclasses.dataclass
class ReshardingTaskSpec:
    """Complete plan for one (array, src sharding, dst sharding) pair
    (ref cross_mesh_resharding.py:674)."""
    shape: Tuple[int, ...]
    requests: List[DstTileRequest]
    # total bytes crossing meshes under this plan
    transfer_bytes: float = 0.0
    # whether the local-allgather rewrite applies (dst replicated axes
    # served by intra-mesh collectives instead of repeated sends)
    allgather_rewrite: bool = False
    # device ids aligned with shard indexes (source / destination VDAs),
    # so an executor can route each planned TileSlice to real devices
    src_device_ids: Tuple[int, ...] = ()
    dst_device_ids: Tuple[int, ...] = ()
    # per source/destination shard, the FULL tile it holds; src_tiles lets
    # the executor verify the runtime array's layout matches the plan
    src_tiles: Tuple[Tile, ...] = ()
    dst_tiles: Tuple[Tile, ...] = ()
    # element size of the payload dtype (ISSUE 4 link accounting)
    itemsize: int = 1
    # bytes crossing under broadcast execution: each unique fetched tile
    # of each replica group crosses ONCE (vs transfer_bytes, which counts
    # the send_recv plan — once per requesting dst shard)
    broadcast_bytes: float = 0.0
    # planner objective (ISSUE 4, arXiv:2211.05322 load balancing): the
    # busiest single link — max over per-src-device egress bytes and
    # per-dst-device ingress bytes — under this plan's routing, and under
    # the naive routing (first-holder selection) for comparison
    max_link_bytes: float = 0.0
    max_link_bytes_naive: float = 0.0
    # same objective for broadcast execution (unique tiles routed across
    # replica-group members vs all to the group's first holder)
    max_link_bytes_broadcast: float = 0.0
    max_link_bytes_broadcast_naive: float = 0.0
    # greedy least-loaded-egress ordering of the plan's (request, src)
    # moves; empty = plan order (see plan_send_order)
    send_order: Tuple[Tuple[int, int], ...] = ()
    # whether load-balanced source selection / routing was applied
    loadbalanced: bool = True
    # ---- collective lowering (ISSUE 7) ----
    # chosen per-edge strategy for the executor path (one of
    # RESHARD_STRATEGIES); generalizes the allgather_rewrite boolean —
    # the tile ``requests`` above stay the interpreter/tiled-mode source
    # of truth, this field only drives the register/overlap executors
    strategy: str = "direct_p2p"
    # per-candidate cost-model estimates in seconds (reports / tooling)
    strategy_costs: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # per-candidate cross-mesh link stats: candidate -> dict with
    # max_link_messages / max_link_bytes / total_bytes of the wire leg
    strategy_stats: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    # the CHOSEN strategy's busiest-link message count and total wire
    # bytes (reports)
    wire_messages: int = 1
    wire_bytes: float = 0.0
    # whether the strategy decision came from the compile cache
    strategy_cached: bool = False

    def total_tiles(self):
        return sum(len(r.srcs) for r in self.requests)


def _cover_tile(dst_tile: Tile, src_vda: VirtualDistributedArray,
                load: Dict[int, float], itemsize: int,
                balance: bool = True) -> List[TileSlice]:
    """Cover ``dst_tile`` with pieces of source shards, choosing the least
    loaded source when a piece is replicated (ref load-balanced sender
    selection, cross_mesh_resharding.py:1448+).  ``balance=False`` always
    picks the first holder — the naive baseline the planner reports
    against."""
    pieces: List[TileSlice] = []
    # Collect candidate intersections per unique source tile.
    for tile_slices, holders in src_vda.unique_tiles.items():
        src_tile = Tile(tile_slices)
        inter = dst_tile.intersect(src_tile)
        if inter is None:
            continue
        if balance:
            # pick least-loaded holder
            best = min(holders, key=lambda i: load.get(i, 0.0))
        else:
            best = holders[0]
        load[best] = load.get(best, 0.0) + inter.size * itemsize
        pieces.append(
            TileSlice(inter, best, inter.offset_in(src_tile)))
    return pieces


def _build_requests(src_vda: VirtualDistributedArray,
                    dst_vda: VirtualDistributedArray,
                    itemsize: int, allgather_rewrite: bool,
                    balance: bool) -> Tuple[List[DstTileRequest], float]:
    """The tile-coverage core of :func:`plan_resharding`: one
    DstTileRequest per destination shard (or per replica-group split part
    under the allgather rewrite), plus the total planned cross bytes."""
    dst_unique = dst_vda.unique_tiles
    load: Dict[int, float] = {}
    requests: List[DstTileRequest] = []
    total = 0.0
    if allgather_rewrite:
        # fetch each unique tile once, split across its replica group
        for tile_slices, holders in dst_unique.items():
            dst_tile = Tile(tile_slices)
            k = len(holders)
            # split along the largest dim divisible by k (fallback: no
            # split, single fetch)
            dims = dst_tile.shape
            split_dim = None
            for d in np.argsort(dims)[::-1]:
                if dims[d] % k == 0 and dims[d] >= k:
                    split_dim = int(d)
                    break
            for gi, holder in enumerate(holders):
                if split_dim is None and gi > 0:
                    continue  # single member fetches; others gather
                if split_dim is None:
                    part = dst_tile
                else:
                    a, b = dst_tile.slices[split_dim]
                    step = (b - a) // k
                    sl = list(dst_tile.slices)
                    sl[split_dim] = (a + gi * step, a + (gi + 1) * step)
                    part = Tile(tuple(sl))
                srcs = _cover_tile(part, src_vda, load, itemsize, balance)
                requests.append(DstTileRequest(holder, part, srcs))
                total += sum(s.tile.size for s in srcs) * itemsize
    else:
        for i, dst_tile in enumerate(dst_vda.device_tiles):
            srcs = _cover_tile(dst_tile, src_vda, load, itemsize, balance)
            requests.append(DstTileRequest(i, dst_tile, srcs))
            total += sum(s.tile.size for s in srcs) * itemsize
    return requests, total


########################################
# link-load accounting + broadcast routing (ISSUE 4)
########################################


def route_broadcast(spec: ReshardingTaskSpec,
                    loadbalance: bool = True) -> Dict[Tuple, int]:
    """Route each unique fetched tile of each replica group to ONE group
    member for broadcast execution.

    Naive routing (``loadbalance=False``, the pre-ISSUE-4 behavior) sends
    every unique tile to the group's FIRST holder, concentrating the
    whole group's ingress on one device; balanced routing spreads unique
    tiles across the group by least accumulated ingress bytes, so a
    source tile fanning out to many destination devices loads each
    destination link evenly (arXiv:2211.05322 send-order balancing).  The
    intra-mesh assembly leg unions pieces across the whole group, so any
    member may receive any tile.

    Returns ``(group_tile_slices, tile_slices) -> dst shard index``.
    """
    groups = VirtualDistributedArray(
        spec.shape, list(spec.dst_tiles),
        list(spec.dst_device_ids)).unique_tiles
    itemsize = spec.itemsize or 1
    ingress: Dict[int, float] = {}
    routes: Dict[Tuple, int] = {}
    for req in spec.requests:
        gslices = spec.dst_tiles[req.dst_shard_index].slices
        holders = groups[gslices]
        for ts in req.srcs:
            key = (gslices, ts.tile.slices)
            if key in routes:
                continue
            if loadbalance:
                target = min(holders,
                             key=lambda h: (ingress.get(h, 0.0), h))
            else:
                target = holders[0]
            routes[key] = target
            ingress[target] = (ingress.get(target, 0.0) +
                               ts.tile.size * itemsize)
    return routes


def compute_link_loads(spec: ReshardingTaskSpec,
                       broadcast: bool = False,
                       loadbalance: bool = True) -> Dict[str, Any]:
    """Per-device link loads of one plan: egress bytes per source device,
    ingress bytes per destination device, their max (the planner's
    max-link objective), and the total bytes crossing.

    ``broadcast=True`` accounts broadcast execution — each unique fetched
    tile of a replica group crosses once, to the device
    :func:`route_broadcast` picks; otherwise every (request, src) move of
    the send_recv plan is counted."""
    itemsize = spec.itemsize or 1
    egress: Dict[int, float] = {}
    ingress: Dict[int, float] = {}
    total = 0.0
    if broadcast:
        routes = route_broadcast(spec, loadbalance)
        seen = set()
        for req in spec.requests:
            gslices = spec.dst_tiles[req.dst_shard_index].slices
            for ts in req.srcs:
                key = (gslices, ts.tile.slices)
                if key in seen:
                    continue
                seen.add(key)
                b = ts.tile.size * itemsize
                src_dev = spec.src_device_ids[ts.src_shard_index]
                dst_dev = spec.dst_device_ids[routes[key]]
                egress[src_dev] = egress.get(src_dev, 0.0) + b
                ingress[dst_dev] = ingress.get(dst_dev, 0.0) + b
                total += b
    else:
        for req in spec.requests:
            dst_dev = spec.dst_device_ids[req.dst_shard_index]
            for ts in req.srcs:
                b = ts.tile.size * itemsize
                src_dev = spec.src_device_ids[ts.src_shard_index]
                egress[src_dev] = egress.get(src_dev, 0.0) + b
                ingress[dst_dev] = ingress.get(dst_dev, 0.0) + b
                total += b
    links = list(egress.values()) + list(ingress.values())
    return {
        "egress": egress,
        "ingress": ingress,
        "total_bytes": total,
        "max_link_bytes": max(links) if links else 0.0,
    }


def plan_send_order(spec: ReshardingTaskSpec
                    ) -> Tuple[Tuple[int, int], ...]:
    """Greedy send ordering: repeatedly issue the pending (request, src)
    move whose SOURCE device has the least bytes already issued, so no
    single egress link runs far ahead of the others early in the step
    (the send-order half of arXiv:2211.05322's balancing; ties break by
    plan order for determinism)."""
    itemsize = spec.itemsize or 1
    pending = [(ri, si) for ri, req in enumerate(spec.requests)
               for si in range(len(req.srcs))]
    issued: Dict[int, float] = {}
    order: List[Tuple[int, int]] = []
    while pending:
        best = min(
            pending,
            key=lambda p: (issued.get(
                spec.src_device_ids[
                    spec.requests[p[0]].srcs[p[1]].src_shard_index],
                0.0), p))
        pending.remove(best)
        ts = spec.requests[best[0]].srcs[best[1]]
        dev = spec.src_device_ids[ts.src_shard_index]
        issued[dev] = issued.get(dev, 0.0) + ts.tile.size * itemsize
        order.append(best)
    return tuple(order)


# process-global planner counters, kept in the central metrics registry
# (ISSUE 5: exported on GET /metrics as alpa_resharding_*) and surfaced
# by monitoring.get_overlap_stats with the pre-telemetry dict shape.
_PLANNER_REG = _tmetrics.get_registry()
_PLANS = _PLANNER_REG.counter(
    "alpa_resharding_plans_total", "Resharding plans computed")
_PLAN_BYTES = _PLANNER_REG.counter(
    "alpa_resharding_planned_bytes_total",
    "Planned cross-mesh payload bytes (send_recv accounting)")
_PLAN_BCAST_BYTES = _PLANNER_REG.counter(
    "alpa_resharding_planned_broadcast_bytes_total",
    "Planned cross-mesh payload bytes under broadcast routing")
_PLAN_MAX_LINK = _PLANNER_REG.gauge(
    "alpa_resharding_max_link_bytes",
    "Max per-device link bytes over all plans, balanced routing")
_PLAN_MAX_LINK_NAIVE = _PLANNER_REG.gauge(
    "alpa_resharding_max_link_bytes_naive",
    "Max per-device link bytes over all plans, naive routing")


def _record_plan(spec: ReshardingTaskSpec):
    _PLANS.inc()
    _PLAN_BYTES.inc(spec.transfer_bytes)
    _PLAN_BCAST_BYTES.inc(spec.broadcast_bytes)
    _PLAN_MAX_LINK.set_max(max(spec.max_link_bytes,
                               spec.max_link_bytes_broadcast))
    _PLAN_MAX_LINK_NAIVE.set_max(max(spec.max_link_bytes_naive,
                                     spec.max_link_bytes_broadcast_naive))


def get_planner_stats() -> Dict[str, float]:
    """Snapshot of the resharding planner counters (plans made, planned
    total/broadcast bytes, max-link objective balanced vs naive) — a
    thin view over the metrics registry, same dict shape as before."""
    return {
        "plans": int(_PLANS.value),
        "total_bytes": _PLAN_BYTES.value,
        "broadcast_bytes": _PLAN_BCAST_BYTES.value,
        "max_link_bytes": _PLAN_MAX_LINK.value,
        "max_link_bytes_naive": _PLAN_MAX_LINK_NAIVE.value,
    }


def reset_planner_stats():
    for fam in (_PLANS, _PLAN_BYTES, _PLAN_BCAST_BYTES, _PLAN_MAX_LINK,
                _PLAN_MAX_LINK_NAIVE):
        fam.reset()


def plan_resharding(shape: Tuple[int, ...],
                    itemsize: int,
                    src_sharding,
                    dst_sharding,
                    allow_allgather_rewrite: bool = True,
                    loadbalance: Optional[bool] = None
                    ) -> ReshardingTaskSpec:
    """Compute the transfer plan for one cross-mesh value
    (ref CrossMeshCommunicator._compile_resharding_specs:935).

    ``loadbalance`` (default: from
    ``global_config.resharding_loadbalance_mode``) selects balanced
    source-holder selection, broadcast fan-out routing, and greedy send
    ordering; off = first-holder / plan-order naive baseline.  Both
    variants' max-link objectives are computed so reports can show the
    balancing win without re-planning."""
    if loadbalance is None:
        from alpa_tpu.global_env import global_config
        loadbalance = (getattr(global_config,
                               "resharding_loadbalance_mode",
                               "normal") != "no_loadbalance")
    tok = _ttrace.begin("plan_resharding", "resharding")
    src_vda = VirtualDistributedArray.from_sharding(shape, src_sharding)
    dst_vda = VirtualDistributedArray.from_sharding(shape, dst_sharding)

    # Local-allgather rewrite (MLSys'23): if several destination shards
    # request the SAME tile (dst replicates over some axis), fetching it
    # once per replica wastes DCN.  Rewrite: each replica group member
    # fetches a disjoint 1/k slice; the destination mesh all-gathers over
    # ICI.  We mark the spec; the executor realizes the gather with a
    # resharded device_put + with_sharding_constraint (XLA collective).
    dst_unique = dst_vda.unique_tiles
    replication = max(len(v) for v in dst_unique.values()) \
        if dst_unique else 1
    allgather_rewrite = allow_allgather_rewrite and replication > 1

    requests, total = _build_requests(src_vda, dst_vda, itemsize,
                                      allgather_rewrite, loadbalance)

    spec = ReshardingTaskSpec(tuple(shape), requests, total,
                              allgather_rewrite,
                              src_device_ids=tuple(src_vda.device_ids),
                              dst_device_ids=tuple(dst_vda.device_ids),
                              src_tiles=tuple(src_vda.device_tiles),
                              dst_tiles=tuple(dst_vda.device_tiles),
                              itemsize=itemsize,
                              loadbalanced=bool(loadbalance))

    # planner objective: max-link bytes under this plan's routing …
    loads = compute_link_loads(spec, broadcast=False)
    spec.max_link_bytes = loads["max_link_bytes"]
    bloads = compute_link_loads(spec, broadcast=True,
                                loadbalance=loadbalance)
    spec.broadcast_bytes = bloads["total_bytes"]
    spec.max_link_bytes_broadcast = bloads["max_link_bytes"]
    # … and under the naive baseline (first-holder selection + routing),
    # re-covered only when they can differ
    if loadbalance:
        nreq, _ = _build_requests(src_vda, dst_vda, itemsize,
                                  allgather_rewrite, balance=False)
        nspec = dataclasses.replace(spec, requests=nreq)
        spec.max_link_bytes_naive = compute_link_loads(
            nspec, broadcast=False)["max_link_bytes"]
        spec.max_link_bytes_broadcast_naive = compute_link_loads(
            nspec, broadcast=True, loadbalance=False)["max_link_bytes"]
        spec.send_order = plan_send_order(spec)
    else:
        spec.max_link_bytes_naive = spec.max_link_bytes
        spec.max_link_bytes_broadcast_naive = spec.max_link_bytes_broadcast
    # collective lowering (ISSUE 7): pick the per-edge strategy by the
    # cost model (cache-backed so warm restarts replay identically) and
    # record the decision for dump_debug_info / reshard_tool
    try:
        strat, costs, cached = resolve_strategy(shape, itemsize,
                                                src_sharding, dst_sharding)
        opts = collective_options(shape, itemsize, src_sharding,
                                  dst_sharding)
        spec.strategy = strat if strat in opts else "direct_p2p"
        spec.strategy_costs = costs
        spec.strategy_stats = {k: dict(o["stats"])
                               for k, o in opts.items()}
        st = opts[spec.strategy]["stats"]
        spec.wire_messages = int(st["max_link_messages"])
        spec.wire_bytes = float(st["total_bytes"])
        spec.strategy_cached = bool(cached)
        _STRATEGY_COUNT.labels(spec.strategy).inc()
        _RECENT_PLANS.append({
            "shape": tuple(shape),
            "itemsize": int(itemsize),
            "src": _sharding_key(src_sharding),
            "dst": _sharding_key(dst_sharding),
            "strategy": spec.strategy,
            "costs": dict(costs),
            "cached": bool(cached),
            "wire_messages": spec.wire_messages,
            "wire_bytes": spec.wire_bytes,
            "transfer_bytes": spec.transfer_bytes,
            "max_link_bytes": spec.max_link_bytes,
        })
    except Exception:  # pylint: disable=broad-except
        logger.warning("collective strategy planning failed; "
                       "keeping direct_p2p", exc_info=True)
    _record_plan(spec)
    _ttrace.end(tok)
    return spec


def naive_transfer_bytes(shape, itemsize, dst_sharding,
                         mode: str = "send_recv") -> float:
    """Bytes moved by the naive plan (no dedup/allgather) — for tests and
    reporting.

    ``mode="send_recv"``: the full per-shard need of every destination
    shard crosses (a replicated destination pays once PER REPLICA).
    ``mode="broadcast"``: each unique destination tile crosses exactly
    once regardless of replication — the correct baseline for
    broadcast-mode execution, where counting per replica overstates the
    wire bytes k-fold (ISSUE 4 accounting audit)."""
    vda = VirtualDistributedArray.from_sharding(shape, dst_sharding)
    if mode == "broadcast":
        return float(sum(Tile(sl).size
                         for sl in vda.unique_tiles)) * itemsize
    if mode != "send_recv":
        raise ValueError(f"unknown naive_transfer_bytes mode: {mode}")
    return float(sum(t.size for t in vda.device_tiles)) * itemsize


########################################
# collective strategy planning (ISSUE 7)
########################################

# Per-edge lowering strategies, generalizing the allgather_rewrite
# boolean ("Memory-efficient array redistribution through portable
# collective communication", PAPERS.md):
#
# * ``direct_p2p`` — today's path: one cross-mesh device_put straight to
#   the destination sharding.
# * ``slice_all_gather`` — destination replicates over some mesh axis:
#   each destination device receives only a disjoint 1/k slice
#   cross-mesh and the destination mesh all-gathers over its own links.
# * ``all_to_all`` — destination is a permuted/transposed layout of the
#   source: land source-shaped shards 1:1 (one message per link), then
#   re-lay inside the destination mesh with an all-to-all.
# * ``reduce_scatter_gather`` — source is replicated / partial-
#   reducible: pull disjoint scattered pieces from distinct source
#   replicas, then gather inside the destination mesh.
# * ``aligned_relayout`` — the edge crosses in the layout whose move is
#   1:1 and is re-laid out on ONE of the two meshes: a sharded source
#   lands in its own spec written on the destination mesh and is re-laid
#   out there; a replicated source is first sliced on its own mesh to
#   the destination's spec written there, and then crosses.  What
#   ``auto`` takes wherever the direct move is not chip to chip.
RESHARD_STRATEGIES = ("direct_p2p", "slice_all_gather", "all_to_all",
                      "reduce_scatter_gather", "aligned_relayout")


def _sharding_key(sharding) -> str:
    """Device-id-free canonical form of a NamedSharding (cache keys and
    reports): mesh axis sizes + partition spec."""
    try:
        return f"{dict(sharding.mesh.shape)}|{sharding.spec}"
    except Exception:  # pylint: disable=broad-except
        return str(sharding)


def _spec_entries(sharding, ndim) -> Optional[Tuple]:
    """PartitionSpec as a length-``ndim`` tuple of None | axis name.
    None (whole) when the spec uses tuple entries — the conservative
    strategies below skip those edges."""
    try:
        entries = tuple(sharding.spec)
    except Exception:  # pylint: disable=broad-except
        return None
    entries = entries + (None,) * (ndim - len(entries))
    if any(isinstance(e, (tuple, list)) for e in entries):
        return None
    return entries


def _mesh_axis_sizes(sharding) -> Dict[str, int]:
    return dict(sharding.mesh.shape)


def _replication(sharding, shape) -> int:
    vda = VirtualDistributedArray.from_sharding(shape, sharding)
    uniq = vda.unique_tiles
    return max(len(v) for v in uniq.values()) if uniq else 1


def _scatter_sharding(dst_sharding, shape):
    """The 1/k "slice" landing layout: the destination spec with unused
    destination-mesh axes attached to the largest still-whole dims they
    divide.  The gather leg restores the true destination layout."""
    from jax.sharding import NamedSharding, PartitionSpec
    entries = _spec_entries(dst_sharding, len(shape))
    if entries is None:
        return None
    entries = list(entries)
    sizes = _mesh_axis_sizes(dst_sharding)
    used = {e for e in entries if e is not None}
    changed = False
    for ax, k in sizes.items():
        if ax in used or k <= 1:
            continue
        cands = [(shape[d], d) for d, e in enumerate(entries)
                 if e is None and shape[d] % k == 0 and shape[d] >= k]
        if not cands:
            continue
        # largest dim, lowest index on ties: aligns the scatter with the
        # leading-dim shardings sources usually carry (fewer wire msgs)
        best = max(sz for sz, _ in cands)
        entries[min(d for sz, d in cands if sz == best)] = ax
        changed = True
    if not changed:
        return None
    return NamedSharding(dst_sharding.mesh, PartitionSpec(*entries))


def _translate_spec(src_sharding, dst_sharding, shape):
    """``src_sharding``'s layout re-expressed on ``dst_sharding``'s mesh
    (the landing layout of ``all_to_all`` and ``aligned_relayout``), or
    None when the meshes' axis structures do not line up (conservative:
    same-named equal-size axes only)."""
    from jax.sharding import NamedSharding, PartitionSpec
    entries = _spec_entries(src_sharding, len(shape))
    if entries is None:
        return None
    src_sizes = _mesh_axis_sizes(src_sharding)
    dst_sizes = _mesh_axis_sizes(dst_sharding)
    for e in entries:
        if e is not None and dst_sizes.get(e) != src_sizes.get(e):
            return None
    return NamedSharding(dst_sharding.mesh, PartitionSpec(*entries))


def _strategy_link_stats(shape, itemsize, src_sharding,
                         landing_sharding) -> Dict[str, float]:
    """Cross-mesh wire-leg stats when each landing-layout shard pulls its
    tile from (load-balanced) source holders: busiest-link message count,
    busiest-link bytes, and total bytes crossing."""
    src_vda = VirtualDistributedArray.from_sharding(shape, src_sharding)
    land_vda = VirtualDistributedArray.from_sharding(shape,
                                                     landing_sharding)
    load: Dict[int, float] = {}
    eg_m: Dict[int, int] = {}
    in_m: Dict[int, int] = {}
    eg_b: Dict[int, float] = {}
    in_b: Dict[int, float] = {}
    total = 0.0
    for i, dtile in enumerate(land_vda.device_tiles):
        ddev = land_vda.device_ids[i]
        for ts in _cover_tile(dtile, src_vda, load, itemsize, True):
            b = ts.tile.size * itemsize
            sdev = src_vda.device_ids[ts.src_shard_index]
            eg_m[sdev] = eg_m.get(sdev, 0) + 1
            in_m[ddev] = in_m.get(ddev, 0) + 1
            eg_b[sdev] = eg_b.get(sdev, 0.0) + b
            in_b[ddev] = in_b.get(ddev, 0.0) + b
            total += b
    msgs = list(eg_m.values()) + list(in_m.values())
    byts = list(eg_b.values()) + list(in_b.values())
    return {
        "max_link_messages": int(max(msgs)) if msgs else 0,
        "max_link_bytes": float(max(byts)) if byts else 0.0,
        "total_bytes": float(total),
    }


def moves_chip_to_chip(shape, src_sharding, dst_sharding) -> bool:
    """Whether ``jax.device_put`` carries ``src_sharding -> dst_sharding``
    from device to device: a 1:1 shard move, or fully replicated on both
    sides.  Any other cross-mesh move fetches the array to the host
    (``ArrayImpl._value``) and slices it back in, which first waits for
    whatever produces the array."""
    if shard_structures_match(shape, src_sharding, dst_sharding):
        return True
    try:
        return bool(src_sharding.is_fully_replicated and
                    dst_sharding.is_fully_replicated)
    except Exception:  # pylint: disable=broad-except
        return False


def collective_options(shape, itemsize, src_sharding, dst_sharding
                       ) -> Dict[str, Dict[str, Any]]:
    """Eligible strategies for one edge, in preference (tie-break)
    order: name -> {"landing": sharding of the staged value, "kind":
    intra-mesh collective kind (None where there is none), "stats":
    wire-leg link stats, "chip_to_chip": whether the wire leg stays off
    the host (:func:`moves_chip_to_chip`), "relayout_first": whether the
    landing lies on the SOURCE mesh, so that the relayout runs before
    the wire leg}.  ``direct_p2p`` is always present."""
    opts: Dict[str, Dict[str, Any]] = {}

    def add(name, landing, kind, relayout_first=False):
        wire = (landing, dst_sharding) if relayout_first else \
            (src_sharding, landing)
        opts[name] = {
            "landing": landing,
            "kind": kind,
            "stats": _strategy_link_stats(shape, itemsize, *wire),
            "chip_to_chip": moves_chip_to_chip(shape, *wire),
            "relayout_first": relayout_first,
        }

    add("direct_p2p", dst_sharding, None)
    try:
        src_repl = _replication(src_sharding, shape)
        dst_repl = _replication(dst_sharding, shape)
        src_whole = src_sharding.is_fully_replicated
    except Exception:  # pylint: disable=broad-except
        return opts
    if not opts["direct_p2p"]["chip_to_chip"]:
        # the layout whose move is 1:1: a whole source is sliced on its
        # own mesh first (no collective) and then crosses; a sharded one
        # crosses as it lies and is re-laid out on the destination mesh
        landing = _translate_spec(dst_sharding, src_sharding, shape) \
            if src_whole else \
            _translate_spec(src_sharding, dst_sharding, shape)
        if landing is not None:
            add("aligned_relayout", landing,
                None if src_whole else
                "all_gather" if dst_repl > 1 else "all_to_all",
                relayout_first=src_whole)
            if not opts["aligned_relayout"]["chip_to_chip"]:
                del opts["aligned_relayout"]
    dst_entries = _spec_entries(dst_sharding, len(shape))
    scattered = _scatter_sharding(dst_sharding, shape) \
        if dst_entries is not None else None
    if src_repl > 1 and scattered is not None:
        # distinct source replicas serve disjoint scattered pieces
        add("reduce_scatter_gather", scattered, "reduce_scatter")
    if dst_repl > 1 and scattered is not None:
        add("slice_all_gather", scattered, "all_gather")
    if src_repl == 1 and dst_repl == 1:
        translated = _translate_spec(src_sharding, dst_sharding, shape)
        if (translated is not None and dst_entries is not None and
                _spec_entries(translated, len(shape)) != dst_entries):
            add("all_to_all", translated, "all_to_all")
    return opts


def _strategy_cost(kind: Optional[str], nbytes: float, cal,
                   intra_us: Optional[float] = None) -> float:
    """Estimated edge seconds: the intra-destination collective leg from
    mesh_profiling's calibrated (alpha, beta) cost dicts.

    ``intra_us`` (ISSUE 12): a measured collective cost from the
    calibration store that supersedes the alpha-beta estimate."""
    if intra_us is not None:
        return intra_us * 1e-6
    if kind is not None and cal is not None:
        ab = cal.alpha_beta(kind)
        if ab is not None:
            return ab[0] + ab[1] * nbytes
    return 0.0


def choose_strategy(shape, itemsize, src_sharding, dst_sharding
                    ) -> Tuple[str, Dict[str, float],
                               Dict[str, Dict[str, Any]]]:
    """Pick the cheapest eligible strategy for one cross-mesh edge
    (``global_config.reshard_strategy`` forces a specific one when not
    "auto"; ineligible forced strategies fall back to direct_p2p).
    Returns (strategy, per-candidate costs, candidate options).

    ``auto`` chooses among the candidates whose wire leg moves chip to
    chip (``collective_options``' ``chip_to_chip``, read off the two
    shardings), since any other leg goes through the host; where no
    candidate does (the meshes' axes do not line up) the edge stays
    ``direct_p2p``.  Among those the cross-mesh leg has no analytic
    price, so a candidate costs its intra-mesh collective leg only and
    ties go to the first offered: ``direct_p2p`` wherever it is chip to
    chip, ``aligned_relayout`` elsewhere.

    Under ``replan_mode != off`` (ISSUE 12) the calibration store
    supersedes the analytic price wherever it has enough measured
    samples: per-candidate wire cost by the edge signature (only the
    strategies that actually ran get measured overrides — the rest stay
    analytic, so a mispriced edge can flip the choice), and the intra
    collective leg by mesh_profiling-style (kind, byte-bucket) keys.
    The analytic prediction each override supersedes is recorded on the
    entry as the drift denominator."""
    from alpa_tpu.global_env import global_config
    from alpa_tpu.mesh_profiling import get_effective_calibration
    from alpa_tpu.telemetry import calibration as _calibration
    opts = collective_options(shape, itemsize, src_sharding, dst_sharding)
    try:
        cal = get_effective_calibration()
    except Exception:  # pylint: disable=broad-except
        cal = None
    nbytes = float(np.prod(shape, dtype=np.int64)) * itemsize \
        if shape else float(itemsize)
    store = _calibration.get_calibration_store() \
        if _calibration.replan_active() else None

    def _intra_us(kind):
        if store is None or kind is None:
            return None
        return store.measured_us(
            "collective", _calibration.collective_signature(kind, nbytes))

    costs = {name: _strategy_cost(o["kind"], nbytes, cal,
                                  intra_us=_intra_us(o["kind"]))
             for name, o in opts.items()}
    if store is not None:
        src_key = _sharding_key(src_sharding)
        dst_key = _sharding_key(dst_sharding)
        # Codec bucket (ISSUE 19): a quantized edge moves ~4x fewer
        # bytes, so its measured samples must not re-price the
        # full-precision signature.  Mirror the transfer factory's
        # eligibility (fp32/bf16 payload over the min-bytes floor).
        q_mode = getattr(global_config, "reshard_quantize", "off")
        q_min = getattr(global_config, "reshard_quantize_min_bytes",
                        65536)
        codec = q_mode if (q_mode != "off" and itemsize in (2, 4) and
                           nbytes >= q_min) else None
        for name in opts:
            sig = _calibration.wire_signature(shape, itemsize, src_key,
                                              dst_key, name, codec=codec)
            # attach the analytic price this entry would supersede
            # (drift denominator) before consulting it
            store.set_modeled("reshard_wire", sig, costs[name] * 1e6)
            measured = store.measured_us("reshard_wire", sig)
            if measured is not None:
                costs[name] = measured * 1e-6
    forced = getattr(global_config, "reshard_strategy", "auto")
    if forced != "auto":
        chosen = forced if forced in opts else "direct_p2p"
    else:
        order = [n for n in opts if opts[n]["chip_to_chip"]] or \
            ["direct_p2p"]
        chosen = min(order, key=lambda n: (costs[n], order.index(n)))
    return chosen, costs, opts


def resolve_strategy(shape, itemsize, src_sharding, dst_sharding
                     ) -> Tuple[str, Dict[str, float], bool]:
    """Cache-backed :func:`choose_strategy`: per-edge decisions persist
    in the compile cache (namespace ``reshard_strategy``), so a warm
    restart replays the identical plan without re-costing.  The key
    covers the edge signature, every knob the cost model reads AND the
    name of ``auto``'s rule (a decision cached by a tree in which
    ``auto`` meant ``direct_p2p`` everywhere must not replay) —
    plus, when replanning is active, the calibration-store fingerprint
    (ISSUE 12): a calibrated re-solve caches like any other plan, an
    unchanged store replays it, and ``replan_mode=off`` keys stay
    byte-identical to a build without calibration.
    Returns (strategy, costs, from_cache)."""
    from alpa_tpu.compile_cache import cache_enabled, get_compile_cache
    from alpa_tpu.global_env import global_config
    from alpa_tpu.telemetry.calibration import calibration_cache_token
    tok = calibration_cache_token()
    parts = (tuple(shape), int(itemsize),
             _sharding_key(src_sharding), _sharding_key(dst_sharding),
             getattr(global_config, "reshard_strategy", "auto"),
             "auto=chip_to_chip") + ((tok,) if tok else ())
    cache = get_compile_cache() if cache_enabled() else None
    key = cache.make_key("reshard_strategy", parts) if cache else None
    if cache is not None:
        hit = cache.get("reshard_strategy", key)
        if isinstance(hit, dict) and hit.get("strategy") in \
                RESHARD_STRATEGIES:
            return hit["strategy"], dict(hit.get("costs", {})), True
    chosen, costs, _opts = choose_strategy(shape, itemsize, src_sharding,
                                           dst_sharding)
    if cache is not None:
        cache.put("reshard_strategy", key,
                  {"strategy": chosen, "costs": costs})
    return chosen, costs, False


# last-N per-edge strategy decisions, for dump_debug_info's
# resharding_plan.txt and scripts/reshard_tool.py
from collections import deque as _deque  # noqa: E402

_RECENT_PLANS: "_deque" = _deque(maxlen=128)

_STRATEGY_COUNT = _PLANNER_REG.counter(
    "alpa_reshard_strategy_total",
    "Cross-mesh resharding edges planned, per chosen strategy",
    labelnames=("kind",))
# a planned two-leg edge that found another source layout at run time
# than the plan assumed, and took plain device_put (per call, where the
# family above counts per planned edge)
_RUNTIME_FALLBACKS = _PLANNER_REG.counter(
    "alpa_reshard_runtime_fallback_total",
    "Calls of a two-leg resharding edge that fell back to device_put "
    "because the runtime array's sharding was not the planned one")


def strategy_plan_fingerprint() -> str:
    """Content hash over the recorded per-edge strategy decisions (in
    recording order): two runs that planned the same edges to the same
    strategies fingerprint identically (the warm-restart replay
    check)."""
    import hashlib
    h = hashlib.sha256()
    for p in _RECENT_PLANS:
        h.update(f"{p['shape']}|{p['itemsize']}|{p['src']}|{p['dst']}|"
                 f"{p['strategy']}".encode())
    return h.hexdigest()


def reset_recent_plans():
    _RECENT_PLANS.clear()


def format_resharding_plan() -> str:
    """Human-readable per-edge strategy report (dump_debug_info's
    resharding_plan.txt; scripts/reshard_tool.py)."""
    if not _RECENT_PLANS:
        return "resharding plan: (no cross-mesh edges planned yet)"
    lines = [f"resharding plan ({len(_RECENT_PLANS)} most recent edges; "
             "strategy chosen by the collective cost model):"]
    for p in _RECENT_PLANS:
        costs = " ".join(f"{k}={v * 1e3:.3f}ms"
                         for k, v in sorted(p["costs"].items()))
        lines.append(
            f"  {p['shape']} x{p['itemsize']}B {p['src']} -> {p['dst']}")
        lines.append(
            f"    strategy={p['strategy']}"
            f"{' (cached)' if p['cached'] else ''} "
            f"wire_msgs={p['wire_messages']} "
            f"wire_bytes={p['wire_bytes']:.0f} "
            f"planned_bytes={p['transfer_bytes']:.0f} "
            f"max_link={p['max_link_bytes']:.0f}")
        if costs:
            lines.append(f"    est: {costs}")
    return "\n".join(lines)


########################################
# execution
########################################

_warned_fallback = False


def shard_structures_match(shape, src_sharding, dst_sharding) -> bool:
    """True when moving ``src_sharding -> dst_sharding`` is a pure 1:1
    shard move: each source shard maps onto the destination shard at the
    same position in the device-assignment order (same per-shard index
    maps).  That is exactly the case the runtime's batched C++ copy
    (``batched_copy_array_to_devices_with_sharding``) handles without any
    resharding logic; every other move needs the full device_put path."""
    try:
        src_map = src_sharding.devices_indices_map(tuple(shape))
        dst_map = dst_sharding.devices_indices_map(tuple(shape))
    except Exception:  # pylint: disable=broad-except
        return False
    return list(src_map.values()) == list(dst_map.values())


class DirectTransfer:
    """Pre-resolved, reusable executor for one RESHARD edge (ISSUE 2:
    "plan once, replay as pre-resolved tasks", arXiv:2211.05322).

    Built once at instruction-lowering time from the emitter's static
    sharding model; ``__call__`` does no planning — the destination
    devices, sharding, and path choice are already resolved:

    * fast path: when the edge is a 1:1 shard-structure move (see
      :func:`shard_structures_match`) the transfer goes straight to the
      runtime's batched C++ copy, skipping device_put's sharding
      resolution (~3x cheaper on the 8-device CPU mesh);
    * fallback: ``jax.device_put`` with the pre-resolved dst sharding.

    A per-call guard (``is_equivalent_to``, ~2 us) confirms the runtime
    array still has the sharding the plan assumed; divergence silently
    takes the fallback, so the fast path can never assemble wrong values.
    """

    __slots__ = ("dst_sharding", "src_sharding", "ndim", "fast",
                 "nbytes", "_dst_devices", "_semantics")

    def __init__(self, aval, src_sharding, dst_sharding):
        self.dst_sharding = dst_sharding
        self.src_sharding = src_sharding
        self.ndim = len(getattr(aval, "shape", ()))
        shape = tuple(getattr(aval, "shape", ()))
        try:
            self.nbytes = int(np.prod(shape, dtype=np.int64) *
                              np.dtype(aval.dtype).itemsize)
        except Exception:  # pylint: disable=broad-except
            self.nbytes = 0
        self.fast = (src_sharding is not None and shard_structures_match(
            shape, src_sharding, dst_sharding))
        self._dst_devices = None
        self._semantics = None
        if self.fast:
            try:
                import jaxlib.xla_extension as xe
                self._dst_devices = list(
                    dst_sharding._addressable_device_assignment)
                self._semantics = xe.ArrayCopySemantics.ALWAYS_COPY
            except Exception:  # pylint: disable=broad-except
                self.fast = False

    def __call__(self, val):
        if _ttrace.enabled():
            # per-edge bytes + latency (the span's duration) on the
            # calling thread's track (driver or pool worker)
            with _ttrace.get_recorder().span(
                    "reshard.edge", "resharding",
                    {"bytes": self.nbytes, "fast": self.fast}):
                return self._transfer(val)
        return self._transfer(val)

    def _transfer(self, val):
        if self.fast:
            try:
                if val.sharding.is_equivalent_to(self.src_sharding,
                                                 self.ndim):
                    import jaxlib.xla_extension as xe
                    return xe.batched_copy_array_to_devices_with_sharding(
                        [val], [self._dst_devices], [self.dst_sharding],
                        [self._semantics])[0]
            except Exception:  # pylint: disable=broad-except
                pass
        import jax
        return jax.device_put(val, self.dst_sharding)


class DirectTransferGroup:
    """Several :class:`DirectTransfer` edges between the same mesh pair,
    coalesced into one call (adjacent same-edge transfers in the
    instruction stream).  All-fast groups go through one batched C++
    copy; mixed groups batch the fallback through a single
    ``jax.device_put`` call (one runtime round-trip instead of N)."""

    __slots__ = ("transfers", "all_fast")

    def __init__(self, transfers: Sequence[DirectTransfer]):
        self.transfers = list(transfers)
        self.all_fast = all(t.fast for t in self.transfers)

    def __len__(self):
        return len(self.transfers)

    def __call__(self, vals):
        if _ttrace.enabled():
            with _ttrace.get_recorder().span(
                    "reshard.edge-group", "resharding",
                    {"bytes": sum(t.nbytes for t in self.transfers),
                     "n": len(self.transfers),
                     "fast": self.all_fast}):
                return self._transfer(vals)
        return self._transfer(vals)

    def _transfer(self, vals):
        ts = self.transfers
        if self.all_fast:
            try:
                if all(v.sharding.is_equivalent_to(t.src_sharding, t.ndim)
                       for v, t in zip(vals, ts)):
                    import jaxlib.xla_extension as xe
                    return xe.batched_copy_array_to_devices_with_sharding(
                        list(vals), [t._dst_devices for t in ts],
                        [t.dst_sharding for t in ts],
                        [t._semantics for t in ts])
            except Exception:  # pylint: disable=broad-except
                pass
        import jax
        return jax.device_put(list(vals), [t.dst_sharding for t in ts])


class CollectiveTransfer:
    """Pre-resolved executor for one RESHARD edge lowered to two legs
    (ISSUE 7; "Memory-efficient array redistribution through portable
    collective communication", PAPERS.md):

    1. **wire leg** — ``jax.device_put`` from the source mesh to the
       destination mesh, in the *landing* layout (the 1/k scattered
       layout for ``slice_all_gather`` / ``reduce_scatter_gather``, the
       translated source layout for ``all_to_all`` and
       ``aligned_relayout``), so only the strategy's reduced byte volume
       crosses meshes, and, where the move is 1:1, nothing of it goes
       through the host;
    2. **relayout leg** — a cached identity ``jax.jit`` with
       ``out_shardings``: XLA emits the all-gather / all-to-all / local
       slice over one mesh's own links.

    The relayout runs on the destination mesh after the wire leg, or,
    with ``relayout_first``, on the source mesh before it (the landing
    is then the destination's layout written on the source mesh).  Both
    legs are pure data movement — no arithmetic — so every strategy here
    is bit-exact against ``direct_p2p``.

    A per-call guard (``is_equivalent_to``, as in
    :class:`DirectTransfer`) confirms the runtime array has the sharding
    the plan assumed; a divergent one takes plain ``jax.device_put`` and
    is counted (``alpa_reshard_runtime_fallback_total``), so a landing
    chosen for another layout can never assemble wrong values.

    ``on_driver``: the relayout is a program over a whole mesh, so the
    overlap replay calls this executor on the driver thread, which
    launches every other program of that mesh, and not on its transfer
    pool (programs with collectives must reach a mesh's chips in one
    order).  Both legs only enqueue.
    """

    __slots__ = ("strategy", "dst_sharding", "src_sharding",
                 "inter_sharding", "relayout_first", "ndim", "nbytes",
                 "fast", "_relayout")

    on_driver = True

    def __init__(self, aval, src_sharding, dst_sharding, strategy,
                 inter_sharding, relayout_first=False):
        self.strategy = strategy
        self.dst_sharding = dst_sharding
        self.src_sharding = src_sharding
        self.inter_sharding = inter_sharding
        self.relayout_first = relayout_first
        self.ndim = len(getattr(aval, "shape", ()))
        self.fast = False   # never the batched-copy fast path
        shape = tuple(getattr(aval, "shape", ()))
        try:
            self.nbytes = int(np.prod(shape, dtype=np.int64) *
                              np.dtype(aval.dtype).itemsize)
        except Exception:  # pylint: disable=broad-except
            self.nbytes = 0
        self._relayout = None

    def __call__(self, val):
        if _ttrace.enabled():
            args = {"bytes": self.nbytes, "strategy": self.strategy}
            with _ttrace.get_recorder().span("reshard.edge", "resharding",
                                             args):
                return self._transfer(val, args)
        return self._transfer(val)

    def _transfer(self, val, span_args=None):
        import jax
        if not val.sharding.is_equivalent_to(self.src_sharding, self.ndim):
            _RUNTIME_FALLBACKS.inc()
            if span_args is not None:
                span_args["fallback"] = True
            return jax.device_put(val, self.dst_sharding)
        if self._relayout is None:
            self._relayout = jax.jit(
                lambda x: x,
                out_shardings=(self.inter_sharding if self.relayout_first
                               else self.dst_sharding))
        if self.relayout_first:
            return jax.device_put(self._relayout(val), self.dst_sharding)
        return self._relayout(jax.device_put(val, self.inter_sharding))


def make_transfer(aval, src_sharding, dst_sharding, cross=False,
                  plan=None, weight=False):
    """Executor factory for one RESHARD edge: DirectTransfer,
    CollectiveTransfer, or (opt-in) the quantized codec transfer.

    Same-mesh relayouts always stay direct.  Cross-mesh edges take the
    plan's strategy decision when a :class:`ReshardingTaskSpec` is given
    and that strategy is one ``choose_strategy`` could pick for THESE
    two shardings (so the emitter replays what the planner chose and
    cached, unless the planner saw another source layout than the
    emitter tracks), else resolve it here.  The quantized codec
    (``global_config.reshard_quantize``) takes precedence for eligible
    activation edges but is NEVER applied when ``weight`` is True —
    microbatch-invariant values (parameters, optimizer state) must cross
    losslessly.  Any planning failure degrades to DirectTransfer."""
    if not cross or src_sharding is None:
        return DirectTransfer(aval, src_sharding, dst_sharding)
    from alpa_tpu.global_env import global_config
    shape = tuple(getattr(aval, "shape", ()))
    try:
        itemsize = int(np.dtype(aval.dtype).itemsize)
        qmode = getattr(global_config, "reshard_quantize", "off")
        if qmode != "off" and not weight:
            from alpa_tpu.pipeline_parallel import reshard_codec
            qt = reshard_codec.maybe_quantized_transfer(
                aval, src_sharding, dst_sharding, qmode)
            if qt is not None:
                return qt
        opts = collective_options(shape, itemsize, src_sharding,
                                  dst_sharding)
        strat = getattr(plan, "strategy", None)
        forced = getattr(global_config, "reshard_strategy", "auto")
        if strat not in opts or (forced == "auto" and
                                 not opts[strat]["chip_to_chip"]):
            strat, _costs, _cached = resolve_strategy(
                shape, itemsize, src_sharding, dst_sharding)
            if strat not in opts:
                strat = "direct_p2p"
        if strat == "direct_p2p":
            return DirectTransfer(aval, src_sharding, dst_sharding)
        return CollectiveTransfer(aval, src_sharding, dst_sharding,
                                  strat, opts[strat]["landing"],
                                  opts[strat]["relayout_first"])
    except Exception:  # pylint: disable=broad-except
        logger.warning("make_transfer: collective lowering failed; "
                       "using DirectTransfer", exc_info=True)
        return DirectTransfer(aval, src_sharding, dst_sharding)


def make_ingest_transfer(aval, dst_sharding):
    """Transfer executor landing a HOST-resident payload on the
    destination sharding — the arrival half of a cross-process edge
    whose source lives in another address space (the disaggregated
    KV handoff, serve.disagg: the prefill replica's payload arrives as
    numpy and must land exactly where the decode engine's resident
    caches live).  A plain :class:`DirectTransfer` with no source
    sharding: the fast copy path is off and ``device_put`` lands it."""
    return DirectTransfer(aval, None, dst_sharding)


@dataclasses.dataclass
class ExecutionReport:
    """Bytes actually moved by one ``ReshardingTask.run`` call.

    ``cross_mesh_bytes`` is the inter-mesh traffic in planned (payload
    dtype) bytes — the DCN-class hop the planner minimizes;
    ``intra_mesh_bytes`` is destination-internal movement (the ICI-class
    all-gather/broadcast leg).  Tests assert ``cross_mesh_bytes ==
    spec.transfer_bytes``.  ``wire_bytes`` is the planned bytes widened to
    the psum work dtype the multiprocess leg actually packs tiles in
    (bf16/fp16 -> f32, bool -> i32) — up to 4x the planned bytes for
    sub-word payloads.  It is per-process payload size, not a total-DCN
    measurement (the collective also carries each non-owner process's
    zero slots), and only ``run_multiprocess`` sets it.
    ``max_link_bytes`` (ISSUE 4) is the busiest single link this run
    loaded — max over per-source-device egress and per-destination-device
    ingress bytes of the cross-mesh leg."""
    mode: str = "device_put"
    cross_mesh_bytes: float = 0.0
    intra_mesh_bytes: float = 0.0
    wire_bytes: float = 0.0
    n_tiles: int = 0
    max_link_bytes: float = 0.0


class ReshardingTask:
    """Executable resharding (ref SymbolicReshardingTask :418).

    Three execution modes:

    - ``device_put`` (default fast path): one ``jax.device_put`` — the jax
      runtime carries shard transfers over ICI/DCN itself.  The spec is
      used for accounting only.
    - ``tiled`` (ref send/recv mode :418): drives the plan literally —
      each planned ``TileSlice`` is sliced out *on its source device*,
      transferred to its destination device, and the destination tiles are
      assembled in place.  When the plan carries the local-allgather
      rewrite, each replica-group member receives only its 1/k part
      cross-mesh and the full tile is completed by intra-destination
      transfers (the ICI gather leg).
    - ``broadcast`` (ref broadcast mode :935): each unique destination
      tile crosses the mesh boundary exactly once (to its first holder),
      then fans out to the other replica holders inside the destination
      mesh.

    ``last_report`` records the bytes each leg actually moved so tests can
    hold execution to the plan's accounting.
    """

    def __init__(self, spec: ReshardingTaskSpec, dst_sharding,
                 mode: str = "device_put"):
        self.spec = spec
        self.dst_sharding = dst_sharding
        self.mode = mode
        self.last_report: Optional[ExecutionReport] = None

    def run(self, src_array, mode: Optional[str] = None):
        import jax
        mode = mode or self.mode
        fault.fire("cross_mesh_recv", mode=mode,
                   n_requests=len(self.spec.requests))
        if mode == "device_put" or not self.spec.requests:
            self.last_report = ExecutionReport(mode="device_put")
            return jax.device_put(src_array, self.dst_sharding)
        if mode not in ("tiled", "broadcast"):
            raise ValueError(f"unknown resharding execution mode: {mode}")
        addressable_src = {s.device.id for s in src_array.addressable_shards}
        addressable_dst = {d.id
                           for d in self.dst_sharding.addressable_devices}
        if (not set(self.spec.src_device_ids) <= addressable_src or
                not set(self.spec.dst_device_ids) <= addressable_dst):
            # Planned modes drive transfers from the controller and need
            # every source/destination shard addressable; on a multi-host
            # run fall back to the runtime-carried transfer.
            return self._fallback(src_array,
                                  "needs all shards addressable from "
                                  "this process")
        if self.spec.src_tiles:
            # the array's ACTUAL layout must match the plan's source
            # sharding — the emit-model sharding can diverge from what a
            # stage executable really produced; slicing with the planned
            # offsets would then assemble wrong values
            actual = VirtualDistributedArray.from_sharding(
                self.spec.shape, src_array.sharding)
            if (tuple(actual.device_ids) != self.spec.src_device_ids or
                    tuple(actual.device_tiles) != self.spec.src_tiles):
                return self._fallback(src_array,
                                      "source layout diverged from plan")
        return self._run_planned(src_array, broadcast=(mode == "broadcast"))

    def run_multiprocess(self, src_array):
        """Cross-PROCESS tiled execution (multi-controller): only the
        packed unique planned tiles cross the process boundary — one
        global-device collective over a buffer of exactly the plan's
        bytes — and each process assembles its local destination shards
        from the packed buffer.

        This is the multi-controller analog of the reference's per-tile
        NCCL send/recv (ref SymbolicReshardingTask:418): DCN traffic is
        proportional to the PLANNED tiles instead of the full-array
        gather that ``put_global`` pays.

        COLLECTIVE: all processes execute the same instruction stream, so
        they reach this call in the same order with the same spec.
        """
        import jax
        import jax.numpy as jnp

        from alpa_tpu.distributed import (psum_work_dtype, put_global,
                                          sum_across_processes)

        # fires BEFORE the collective: every process injects (or not)
        # identically, so the lock-step instruction streams stay aligned
        fault.fire("cross_mesh_recv", mode="multiprocess",
                   n_requests=len(self.spec.requests))
        spec = self.spec
        if not spec.requests:
            self.last_report = ExecutionReport(mode="device_put")
            return put_global(src_array, self.dst_sharding)

        dtype = np.dtype(src_array.dtype)
        work = psum_work_dtype(dtype)
        report = ExecutionReport(mode="tiled")

        # unique planned tiles, packed in deterministic plan order
        order: List[TileSlice] = []
        offsets: Dict[Tuple, int] = {}
        total = 0
        for req in spec.requests:
            for ts in req.srcs:
                if ts.tile.slices in offsets:
                    continue
                offsets[ts.tile.slices] = total
                total += ts.tile.size
                order.append(ts)

        # cross-process leg: each tile is painted by the process owning
        # its (load-balanced, unique) planned source shard
        local_src = {s.device.id: np.asarray(s.data)
                     for s in src_array.addressable_shards}
        canvas = np.zeros(total, work)
        for ts in order:
            dev_id = spec.src_device_ids[ts.src_shard_index]
            shard = local_src.get(dev_id)
            if shard is not None:
                piece = shard[tuple(slice(a, b)
                                    for a, b in ts.offset_in_src)]
                off = offsets[ts.tile.slices]
                canvas[off:off + ts.tile.size] = \
                    piece.ravel().astype(work)
        packed = sum_across_processes(canvas)
        report.cross_mesh_bytes = float(total) * dtype.itemsize
        report.wire_bytes = float(total) * np.dtype(work).itemsize
        report.n_tiles = len(order)
        # busiest egress link: bytes painted per owning source device
        # (ingress is collective — every process receives the full pack)
        egress: Dict[int, float] = {}
        for ts in order:
            dev_id = spec.src_device_ids[ts.src_shard_index]
            egress[dev_id] = (egress.get(dev_id, 0.0) +
                              ts.tile.size * dtype.itemsize)
        report.max_link_bytes = max(egress.values()) if egress else 0.0

        # local assembly: every locally-addressable destination shard
        # fills its full tile from the intersecting packed tiles
        shard_of_dev = {d: i for i, d in enumerate(spec.dst_device_ids)}
        arrs = []
        for dev in sorted(self.dst_sharding.addressable_devices,
                          key=lambda d: d.id):
            full_tile = spec.dst_tiles[shard_of_dev[dev.id]]
            buf = np.zeros(full_tile.shape, work)
            for ts in order:
                inter = ts.tile.intersect(full_tile)
                if inter is None:
                    continue
                off = offsets[ts.tile.slices]
                tile_arr = packed[off:off + ts.tile.size].reshape(
                    ts.tile.shape)
                src_idx = tuple(slice(a, b)
                                for a, b in inter.offset_in(ts.tile))
                dst_idx = tuple(slice(a, b)
                                for a, b in inter.offset_in(full_tile))
                buf[dst_idx] = tile_arr[src_idx]
            arrs.append(jax.device_put(jnp.asarray(buf.astype(dtype)),
                                       dev))
        # no dtype kwarg: older jax rejects it; every arr already carries
        # the work dtype
        out = jax.make_array_from_single_device_arrays(
            spec.shape, self.dst_sharding, arrs)
        self.last_report = report
        return out

    def _fallback(self, src_array, why: str):
        import jax
        global _warned_fallback
        if not _warned_fallback:
            _warned_fallback = True
            logger.warning(
                "planned resharding execution %s; falling back to "
                "device_put (warned once)", why)
        self.last_report = ExecutionReport(mode="device_put")
        return jax.device_put(src_array, self.dst_sharding)

    # -- planned execution --------------------------------------------

    def _run_planned(self, src_array, broadcast: bool):
        import jax
        import jax.numpy as jnp
        from jax import lax

        spec = self.spec
        itemsize = src_array.dtype.itemsize
        report = ExecutionReport(mode="broadcast" if broadcast else "tiled")

        src_data = {s.device.id: s.data
                    for s in src_array.addressable_shards}
        dev_by_id = {d.id: d for d in self.dst_sharding.device_set}
        for d in getattr(src_array.sharding, "device_set", ()):
            dev_by_id.setdefault(d.id, d)

        # Replica groups: destination shards holding the same full tile.
        groups = VirtualDistributedArray(
            spec.shape, list(spec.dst_tiles),
            list(spec.dst_device_ids)).unique_tiles

        # 1) cross-mesh leg: move each planned TileSlice to one dst
        #    device, in the planner's balanced send order (ISSUE 4).
        #    Broadcast mode routes each replica group's unique tiles
        #    across the group members (route_broadcast) — naive routing
        #    piles the whole group's ingress on the first holder —
        #    and each unique piece still crosses exactly once; the other
        #    holders are served by intra-mesh fan-out below.
        #    landed[shard_index] = [(global_tile, piece_on_dst_device)]
        landed: Dict[int, List[Tuple[Tile, Any]]] = {}
        routes = route_broadcast(spec, spec.loadbalanced) \
            if broadcast else None
        seen: set = set()
        egress: Dict[int, float] = {}
        ingress: Dict[int, float] = {}
        order = spec.send_order or tuple(
            (ri, si) for ri, req in enumerate(spec.requests)
            for si in range(len(req.srcs)))
        for ri, si in order:
            req = spec.requests[ri]
            ts = req.srcs[si]
            if broadcast:
                gslices = spec.dst_tiles[req.dst_shard_index].slices
                key = (gslices, ts.tile.slices)
                if key in seen:
                    continue
                seen.add(key)
                target = routes[key]
            else:
                target = req.dst_shard_index
            dst_dev_id = spec.dst_device_ids[target]
            src_dev_id = spec.src_device_ids[ts.src_shard_index]
            shard = src_data[src_dev_id]
            piece = shard[tuple(slice(a, b)
                                for a, b in ts.offset_in_src)]
            moved = jax.device_put(piece, dev_by_id[dst_dev_id])
            nbytes = ts.tile.size * itemsize
            report.cross_mesh_bytes += nbytes
            egress[src_dev_id] = egress.get(src_dev_id, 0.0) + nbytes
            ingress[dst_dev_id] = ingress.get(dst_dev_id, 0.0) + nbytes
            report.n_tiles += 1
            landed.setdefault(target, []).append((ts.tile, moved))
        links = list(egress.values()) + list(ingress.values())
        report.max_link_bytes = max(links) if links else 0.0

        # 2) intra-mesh leg + assembly: every dst shard assembles its FULL
        #    tile; pieces that landed on a sibling replica are pulled over
        #    the destination mesh's own links (allgather / broadcast leg).
        out_arrays = []
        for shard_i, full_tile in enumerate(spec.dst_tiles):
            dst_dev = dev_by_id[spec.dst_device_ids[shard_i]]
            holders = groups[full_tile.slices]
            if spec.allgather_rewrite or broadcast:
                donors = holders          # union of the group's pieces
            else:
                donors = [shard_i]        # own fetches cover the tile
            pieces: List[Tuple[Tile, Any]] = []
            covered: Dict[Tuple, Any] = {}
            for d in donors:
                for tile, buf in landed.get(d, ()):
                    if tile.slices in covered:
                        continue
                    if d != shard_i:
                        buf = jax.device_put(buf, dst_dev)
                        report.intra_mesh_bytes += tile.size * itemsize
                    covered[tile.slices] = buf
                    pieces.append((tile, buf))
            if len(pieces) == 1 and pieces[0][0].slices == full_tile.slices:
                tile_arr = pieces[0][1]
            else:
                tile_arr = jax.device_put(
                    jnp.zeros(full_tile.shape, src_array.dtype), dst_dev)
                for tile, buf in pieces:
                    starts = tuple(a for a, _b in tile.offset_in(full_tile))
                    tile_arr = lax.dynamic_update_slice(
                        tile_arr, buf, starts)
            out_arrays.append(tile_arr)

        self.last_report = report
        return jax.make_array_from_single_device_arrays(
            spec.shape, self.dst_sharding, out_arrays)
