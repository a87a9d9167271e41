"""Device-mesh and collective helpers — the NCCL/process-group layer, TPU-native.

ref: the reference's communication substrate is torch.distributed with NCCL
(apex/parallel/distributed.py:181-191), process groups created with
dist.new_group (create_syncbn_process_group, apex/parallel/__init__.py:58-95),
and CUDA streams for overlap.  The TPU equivalents (SURVEY.md §5.8):

- process group            -> named axis of a jax.sharding.Mesh
- dist.new_group(subset)   -> axis_index_groups on a collective, or a
                              factored mesh axis (outer x group)
- NCCL allreduce           -> jax.lax.psum / pmean over ICI
- reduce_scatter           -> jax.lax.psum_scatter
- all_gather               -> jax.lax.all_gather
- send/recv                -> jax.lax.ppermute
- streams/events           -> nothing: XLA's latency-hiding scheduler
                              overlaps collectives with compute
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map_compat(f, mesh: Mesh, *, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with this library's argument order (the name is
    kept for its callers: library code and the fused train driver all
    map through here)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name: str):
    """Size of a mesh axis from inside a mapped region."""
    return jax.lax.axis_size(axis_name)


def data_parallel_mesh(
    n_devices: Optional[int] = None, axis_name: str = "data"
) -> Mesh:
    """1-D mesh over all (or the first n) local devices."""
    devices = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return Mesh(np.array(devices), axis_names=(axis_name,))


def make_mesh(axes: Sequence[Tuple[str, int]]) -> Mesh:
    """Mesh from ordered (axis_name, size) pairs, e.g.
    ``make_mesh([("data", 4), ("model", 2)])``.  Axis order follows device
    order: earlier axes vary slowest (put the bandwidth-hungry axis last so
    it maps to the tightest ICI ring)."""
    sizes = [s for _, s in axes]
    names = tuple(n for n, _ in axes)
    n = int(np.prod(sizes))
    devices = np.array(jax.devices()[:n]).reshape(sizes)
    return Mesh(devices, axis_names=names)


def syncbn_groups(world_size: int, group_size: int) -> List[List[int]]:
    """axis_index_groups for BN stat-sync over subgroups of the data axis.

    The TPU translation of create_syncbn_process_group
    (apex/parallel/__init__.py:58-95): same constraint, world_size must be
    divisible by group_size; returns contiguous groups
    [[0..g-1], [g..2g-1], ...] for lax.psum(axis_index_groups=...).
    """
    if group_size <= 0:
        raise ValueError("group_size must be positive")
    if world_size % group_size != 0:
        raise ValueError(
            f"world_size ({world_size}) must be divisible by group_size "
            f"({group_size})"  # ref asserts the same, __init__.py:83
        )
    return [
        list(range(i * group_size, (i + 1) * group_size))
        for i in range(world_size // group_size)
    ]


def grouped_psum(x, axis_name: str, groups: Sequence[Sequence[int]]):
    """psum restricted to subgroups of a mesh axis (process-group semantics).

    jax.lax.psum's ``axis_index_groups`` is not supported under shard_map
    (as of jax 0.9), so this implements the grouped reduction directly:
    all_gather over the axis, then a static 0/1 group-mask contraction picks
    each device's group sum.  For the small per-channel stat vectors this is
    built for (SyncBN, metric reduction) the extra gather traffic is noise;
    for giant gradient trees prefer a factored mesh
    (``make_mesh([("outer", n//g), ("group", g)])``) and psum over the inner
    axis, which lowers to a true subgroup collective.
    """
    world = sum(len(g) for g in groups)
    mask = np.zeros((world, world), np.float32)
    for g in groups:
        for i in g:
            for j in g:
                mask[i, j] = 1.0
    gathered = jax.lax.all_gather(x, axis_name)  # (world, ...)
    idx = jax.lax.axis_index(axis_name)
    row = jnp.asarray(mask)[idx]  # (world,)
    out = jnp.tensordot(row, gathered.astype(jnp.float32), axes=1)
    return out.astype(x.dtype)


def replicate(tree, mesh: Mesh):
    """Place a pytree fully-replicated on the mesh — the TPU equivalent of
    DDP's rank-0 parameter broadcast (ref distributed.py:253)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_batch(tree, mesh: Mesh, axis_name: str = "data"):
    """Shard leading (batch) axis of every leaf over the data axis."""
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.device_put(tree, sharding)
