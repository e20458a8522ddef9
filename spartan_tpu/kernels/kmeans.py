"""Fused k-means iteration kernel — now partitionable.

The seed's ``ops/kmeans.py`` Pallas kernel was explicitly single-TPU
("the pallas_call is not partitionable"). Migrated onto the kernel
layer: the SAME per-block kernel (Gram matrix vs VMEM-resident
centers on the MXU, lane-wise argmin, one-hot accumulate of sums and
counts) now runs per shard under ``shard_map`` over the row tiling
the planner commits for the point matrix, and the per-shard ``(k, d)``
sums / ``(k,)`` counts merge with one ``psum`` over the mesh row
axis. Row-validity masking is per shard (each shard masks global rows
``>= valid_rows``), so driver padding behaves identically to the
single-device kernel.

Constraints (selection falls back to the expr/XLA path otherwise):
f32 points, d a multiple of 128, k <= 128, per-shard rows a multiple
of the 1024-point block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod
from . import registry

_BLOCK = 1024
_KPAD = 128


def supports(n: int, d: int, k: int, mesh=None) -> bool:
    """Can the Pallas path run this problem here? Multi-chip meshes
    are supported now — the kernel shard_maps over the row tiling."""
    mesh = mesh or mesh_mod.get_mesh()
    sel = registry.select("kmeans", (n, d), np.float32,
                          tiling_mod.row(2), mesh, k=k, block=_BLOCK)
    return sel.pallas


def _block_kernel(points: jax.Array, cpad: jax.Array, cnorm: jax.Array,
                  limit: jax.Array, interpret: bool
                  ) -> tuple:
    """One shard's fused pass: (kpad, d) sums and (1, kpad) counts.

    ``points`` (m, d) f32 with m % 1024 == 0; ``cpad`` (kpad, d)
    zero-padded centers whose padding rows carry +inf norms in
    ``cnorm`` so the argmin never selects them; local rows at index
    >= ``limit`` (driver padding) are masked out of the accumulation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = points.shape
    kpad = _KPAD
    nsteps = m // _BLOCK
    lim2 = jnp.full((1, kpad), limit, jnp.int32)

    def kernel(p_ref, c_ref, cn_ref, lim_ref, sums_ref, cnt_ref,
               acc, cacc):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            cacc[:] = jnp.zeros_like(cacc)

        p = p_ref[:]                                   # (B, d)
        gram = jax.lax.dot_general(
            p, c_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)       # (B, kpad)
        score = cn_ref[0, :][None, :] - 2.0 * gram
        assign = jnp.argmin(score, axis=1)             # (B,)
        oh = (assign[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (_BLOCK, kpad), 1)).astype(jnp.float32)
        row = (b * _BLOCK
               + jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, kpad), 0))
        oh = oh * (row < lim_ref[0, 0]).astype(jnp.float32)
        acc[:] += jax.lax.dot_general(
            oh, p, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)       # (kpad, d)
        cacc[0, :] += jnp.sum(oh, axis=0)

        @pl.when(b == pl.num_programs(0) - 1)
        def _flush():
            sums_ref[:] = acc[:]
            cnt_ref[:] = cacc[:]

    return pl.pallas_call(
        kernel,
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((_BLOCK, d), lambda b: (b, 0)),
            pl.BlockSpec((kpad, d), lambda b: (0, 0)),
            pl.BlockSpec((1, kpad), lambda b: (0, 0)),
            pl.BlockSpec((1, kpad), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kpad, d), lambda b: (0, 0)),
            pl.BlockSpec((1, kpad), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kpad, d), jnp.float32),
            jax.ShapeDtypeStruct((1, kpad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kpad, d), jnp.float32),
            pltpu.VMEM((1, kpad), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_lloyd",
    )(points, cpad, cnorm, lim2)


def assign_accumulate(points: jax.Array, centers: jax.Array, k: int,
                      valid_rows=None, mesh=None) -> tuple:
    """One fused pass over the whole (sharded) point matrix: (k, d)
    cluster sums and (k,) counts. Traceable — the k-means drivers run
    all iterations as one dispatch with this inside ``fori_loop``."""
    from jax import shard_map

    mesh = mesh or mesh_mod.get_mesh()
    n, d = points.shape
    kpad = _KPAD
    interpret = registry.interpret_mode()
    cpad = jnp.zeros((kpad, d), jnp.float32).at[:k].set(centers)
    cnorm = jnp.full((kpad,), jnp.inf, jnp.float32).at[:k].set(
        jnp.sum(centers * centers, axis=1))[None, :]
    valid = n if valid_rows is None else int(valid_rows)
    axis = tiling_mod.AXIS_ROW
    p = int(mesh.shape.get(axis, 1))
    if p <= 1 or n % p or (n // p) % _BLOCK:
        # single-kernel path (the seed's semantics): whole point
        # matrix through one grid — direct callers with shard-
        # indivisible row counts keep working; the DRIVERS pad to
        # p * _BLOCK so they always take the shard_map path below
        sums, cnt = _block_kernel(points, cpad, cnorm,
                                  jnp.int32(valid), interpret)
        return sums[:k], cnt[0, :k]

    t = tiling_mod.row(2)
    points = redist_mod.constrain(points, t, mesh)
    ms = n // p

    def shard_fn(pts_l, cp, cn):
        me = jax.lax.axis_index(axis)
        limit = jnp.clip(valid - me.astype(jnp.int32) * ms, 0, ms)
        sums, cnt = _block_kernel(pts_l, cp, cn, limit, interpret)
        return jax.lax.psum(sums, axis), jax.lax.psum(cnt, axis)

    rep = tiling_mod.replicated(2)
    mapped = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(t.spec(), rep.spec(), rep.spec()),
        out_specs=(rep.spec(), rep.spec()), check_vma=False)
    sums, cnt = mapped(points, cpad, cnorm)
    return sums[:k], cnt[0, :k]


@functools.partial(jax.jit, static_argnames=("k", "valid_rows"))
def step(points: jax.Array, centers: jax.Array, k: int,
         valid_rows=None) -> jax.Array:
    """One k-means update: new centers from one fused pass."""
    sums, cnt = assign_accumulate(points, centers, k, valid_rows)
    return sums / jnp.maximum(cnt, 1.0)[:, None]


@functools.partial(jax.jit, static_argnames=("k", "valid_rows"))
def run(points: jax.Array, centers: jax.Array, k: int,
        iters: jax.Array, valid_rows=None) -> jax.Array:
    """All iterations in one dispatch (traced loop bound)."""
    def body(_, c):
        sums, cnt = assign_accumulate(points, c, k, valid_rows)
        return sums / jnp.maximum(cnt, 1.0)[:, None]

    return jax.lax.fori_loop(0, iters, body, centers)
