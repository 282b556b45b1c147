"""Sharding spec algebra for the auto-sharding planner.

A ``Spec`` is a tuple over tensor dims; each element is a tuple of logical
mesh axes (ints) that dim is sharded over (usually 0 or 1 axes, possibly 2
for fully-2D sharding of one dim).  Replicated = all elements empty.

This plays the role of the HloSharding/ShardingSpec conversions in ref
``alpa/shard_parallel/auto_sharding.py:490-588``, but stays in
jax-PartitionSpec land: ``spec_to_partition_spec`` maps a Spec to
``jax.sharding.PartitionSpec`` over named mesh axes.
"""
from typing import Optional, Sequence, Tuple

import numpy as np
from jax.sharding import PartitionSpec

Spec = Tuple[Tuple[int, ...], ...]


def replicated_spec(ndim: int) -> Spec:
    return tuple(() for _ in range(ndim))


def is_replicated(spec: Spec) -> bool:
    return all(not axes for axes in spec)


def used_axes(spec: Spec) -> Tuple[int, ...]:
    out = []
    for axes in spec:
        out.extend(axes)
    return tuple(sorted(out))


def make_spec(ndim: int, assignment: dict) -> Spec:
    """assignment: {tensor_dim: mesh_axis or tuple(mesh_axes)}"""
    spec = [() for _ in range(ndim)]
    for d, a in assignment.items():
        spec[d] = (a,) if isinstance(a, int) else tuple(a)
    return tuple(spec)


def num_shards(spec: Spec, mesh_shape: Sequence[int]) -> int:
    n = 1
    for a in used_axes(spec):
        n *= mesh_shape[a]
    return n


def sharded_bytes(aval, spec: Spec, mesh_shape: Sequence[int]) -> float:
    size = float(np.prod(aval.shape)) if aval.shape else 1.0
    return size * aval.dtype.itemsize / num_shards(spec, mesh_shape)


def spec_valid(aval, spec: Spec, mesh_shape: Sequence[int]) -> bool:
    """Every sharded dim must be divisible by its axis product."""
    if len(spec) != len(aval.shape):
        return False
    for d, axes in enumerate(spec):
        if not axes:
            continue
        p = int(np.prod([mesh_shape[a] for a in axes]))
        if p > 1 and (aval.shape[d] % p != 0 or aval.shape[d] < p):
            return False
    return True


def spec_to_partition_spec(spec: Spec,
                           axis_names: Sequence[str]) -> PartitionSpec:
    parts = []
    for axes in spec:
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axis_names[axes[0]])
        else:
            parts.append(tuple(axis_names[a] for a in axes))
    # Trim trailing Nones for canonical form.
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def enumerate_var_specs(aval, mesh_shape: Sequence[int],
                        max_axes: int = 2) -> Tuple[Spec, ...]:
    """All valid specs for a tensor: replicated, one dim on one axis, and
    two dims on the two axes (or one dim on both axes)."""
    ndim = len(aval.shape)
    nontrivial = [a for a, s in enumerate(mesh_shape) if s > 1]
    out = [replicated_spec(ndim)]
    # one axis on one dim
    for a in nontrivial:
        for d in range(ndim):
            s = make_spec(ndim, {d: a})
            if spec_valid(aval, s, mesh_shape):
                out.append(s)
    if len(nontrivial) >= 2 and max_axes >= 2:
        a0, a1 = nontrivial[0], nontrivial[1]
        for d0 in range(ndim):
            for d1 in range(ndim):
                if d0 == d1:
                    s = make_spec(ndim, {d0: (a0, a1)})
                else:
                    s = make_spec(ndim, {d0: a0, d1: a1})
                if spec_valid(aval, s, mesh_shape):
                    out.append(s)
    # dedup, keep order
    seen, uniq = set(), []
    for s in out:
        if s not in seen:
            seen.add(s)
            uniq.append(s)
    return tuple(uniq)


def _resharding_steps(src: Spec, dst: Spec):
    """(collective, mesh axis) for every axis of ``src`` that ``dst`` does
    not keep on the same dim: gathered where ``dst`` drops it, moved by an
    all-to-all where ``dst`` has it on another dim.  Axes new in ``dst``
    are a local slice: free, no step."""
    dst_axis_dim = {a: d for d, axes in enumerate(dst) for a in axes}
    for d, axes in enumerate(src):
        for a in axes:
            if a not in dst_axis_dim:
                yield "all_gather", a
            elif dst_axis_dim[a] != d:
                yield "all_to_all", a


def resharding_cost(aval, src: Spec, dst: Spec, logical_mesh) -> float:
    """Alpha-beta cost of transforming src-sharded tensor to dst sharding.

    Coarse model mirroring the role of the reference's resharding cost
    entries in the ILP (ref auto_sharding.py edge costs): per mesh axis,
    gathering pays all-gather; slicing is free; moving an axis between dims
    pays an all-to-all.
    """
    size_bytes = float(np.prod(aval.shape) if aval.shape else 1) * \
        aval.dtype.itemsize
    cost = 0.0
    for kind, a in _resharding_steps(src, dst):
        if kind == "all_gather":
            # bytes gathered = full size / shards kept
            cost += logical_mesh.all_gather_cost(size_bytes, a)
        else:
            cost += logical_mesh.all_to_all_cost(size_bytes, a)
    return cost


def resharding_bytes(aval, src: Spec, dst: Spec,
                     mesh_shape: Sequence[int],
                     dropped: Sequence[int] = ()) -> float:
    """Bytes each device receives for the same transformation, counted as
    ``resharding_cost`` counts them: (n-1)/n of the tensor for a gather
    over an axis of n, (n-1)/n^2 for an all-to-all.  ``dropped``: axes
    gathered before it (``strategy.map_spec``'s, which an edge charges
    the same way)."""
    size_bytes = float(np.prod(aval.shape) if aval.shape else 1) * \
        aval.dtype.itemsize
    steps = [("all_gather", a) for a in dropped]
    steps += _resharding_steps(src, dst)
    total = 0.0
    for kind, a in steps:
        n = mesh_shape[a]
        total += size_bytes * (n - 1) / (n if kind == "all_gather"
                                         else n * n)
    return total
