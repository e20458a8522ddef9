"""Histogram / bincount kernel: per-shard one-hot count + psum reduce.

The histogram's bincount reduction is a scatter-add of ones — XLA's
generic scatter on TPU. Here each shard streams its id blocks through
VMEM, reduces the ``(block_e, k)`` one-hot over its entry axis (VPU)
into a resident ``(1, k)`` counts row, and the per-shard rows merge
with one ``psum`` over the mesh row axis. Matches ``jnp.bincount``:
negative ids clip to bucket 0, ids >= length are dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod
from . import registry

_BLOCK_E = 512


def bincount_block(ids: jax.Array, length: int,
                   interpret: bool = False,
                   block_e: int = _BLOCK_E) -> jax.Array:
    """One shard's bincount: f32 counts of ``ids`` in [0, length)."""
    from jax.experimental import pallas as pl

    e = ids.shape[0]
    e_pad = -e % block_e
    if e_pad:
        # out-of-range sentinel: padded slots count nowhere
        ids = jnp.pad(ids, (0, e_pad), constant_values=length)
    # jnp.bincount parity: negatives land in bucket 0
    ids = jnp.maximum(ids.astype(jnp.int32), 0)
    n_blocks = ids.shape[0] // block_e
    k_total = -(-length // 128) * 128
    ids2d = ids.reshape(n_blocks, block_e)

    def kernel(ids_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        seg = jax.lax.broadcasted_iota(jnp.int32, (block_e, k_total), 1)
        onehot = (ids_ref[step, :][:, None] == seg).astype(jnp.float32)
        out_ref[:] += jnp.sum(onehot, axis=0)[None, :]

    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((n_blocks, block_e), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, k_total), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, k_total), jnp.float32),
        interpret=interpret,
        name="bincount_block",
    )(ids2d)
    return out[0, :length]


def bincount_sharded(ids: jax.Array, length: int,
                     sel: registry.Selection, mesh=None) -> jax.Array:
    """Distributed bincount: row-shard the id stream, count per shard
    with :func:`bincount_block`, ``psum`` the count rows. Returns
    int32 (jnp.bincount parity; counts are exact in f32 to 2**24 and
    each shard holds far fewer entries than that)."""
    from jax import shard_map

    mesh = mesh or mesh_mod.get_mesh()
    axis = tiling_mod.AXIS_ROW
    p = int(mesh.shape.get(axis, 1))
    interpret = sel.interpret
    if p <= 1:
        return bincount_block(ids, length,
                              interpret=interpret).astype(jnp.int32)
    e = ids.shape[0]
    e_pad = -e % p
    if e_pad:
        ids = jnp.pad(ids, (0, e_pad), constant_values=length)
    ids = ids.astype(jnp.int32)
    t = tiling_mod.row(1)
    ids = redist_mod.constrain(ids, t, mesh)

    def shard_fn(i):
        part = bincount_block(i, length, interpret=interpret)
        return jax.lax.psum(part, axis)

    mapped = shard_map(shard_fn, mesh=mesh, in_specs=(t.spec(),),
                       out_specs=tiling_mod.replicated(1).spec(),
                       check_vma=False)
    return mapped(ids).astype(jnp.int32)
