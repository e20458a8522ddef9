"""Explicit collective operations over the device mesh.

The TPU-native replacement for the reference's RPC layer (SURVEY.md §2.7):
the data plane is XLA collectives over ICI. These wrappers are used inside
``shard_map`` kernels (ring attention, explicit GEMMs, user map2 kernels)
and at the host level for resharding. Names follow the reference's
conceptual ops: reduce -> all_reduce, shuffle -> all_to_all, tile fetch ->
all_gather, rotation -> ring_permute.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..array.tiling import Tiling
from . import mesh as mesh_mod

# -- in-kernel collectives (call inside shard_map) ----------------------


def all_reduce(x: Any, axis: str = mesh_mod.AXIS_ROW, op: str = "add"):
    """The lowering of the reference's reducer-merge (SURVEY.md §3.2)."""
    if op == "add":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    raise ValueError(f"unknown all_reduce op {op!r}")


def all_gather(x: Any, axis: str = mesh_mod.AXIS_ROW, *,
               gather_axis: int = 0, tiled: bool = True):
    """The lowering of the reference's remote tile fetch (SURVEY.md §3.5)."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x: Any, axis: str = mesh_mod.AXIS_ROW, *,
                   scatter_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def all_to_all(x: Any, axis: str = mesh_mod.AXIS_ROW, *,
               split_axis: int, concat_axis: int):
    """The lowering of the reference's shuffle (SURVEY.md §2.6)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ring_permute(x: Any, axis: str = mesh_mod.AXIS_ROW, shift: int = 1):
    """Rotate shards around the ring (the substrate of ring attention and
    pipeline stages). shift=+1 sends to the next device."""
    n = mesh_mod.get_mesh().shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str = mesh_mod.AXIS_ROW):
    return lax.axis_index(axis)


def axis_size(axis: str = mesh_mod.AXIS_ROW) -> int:
    return mesh_mod.get_mesh().shape[axis]


# -- host-level resharding ---------------------------------------------


def reshard(arr: jax.Array, tiling: Tiling) -> jax.Array:
    """General redistribution: XLA emits the minimal collective
    (cf. the redistribution paper, PAPERS.md:5)."""
    return jax.device_put(arr, tiling.sharding(mesh_mod.get_mesh()))


def ulysses_swap(arr: jax.Array, seq_axis: int, head_axis: int,
                 mesh_axis: str = mesh_mod.AXIS_ROW) -> jax.Array:
    """Ulysses-style axis swap: move the mesh shard from ``seq_axis`` to
    ``head_axis`` with one all-to-all (SURVEY.md §2.6 SP row)."""
    from jax import shard_map

    mesh = mesh_mod.get_mesh()
    ndim = arr.ndim
    in_axes = [None] * ndim
    in_axes[seq_axis] = mesh_axis
    out_axes = [None] * ndim
    out_axes[head_axis] = mesh_axis
    in_t, out_t = Tiling(in_axes), Tiling(out_axes)

    def kern(x):
        return all_to_all(x, mesh_axis, split_axis=head_axis,
                          concat_axis=seq_axis)

    arr = jax.device_put(arr, in_t.sharding(mesh))
    return jax.jit(shard_map(kern, mesh=mesh, in_specs=(in_t.spec(),),
                             out_specs=out_t.spec()))(arr)
