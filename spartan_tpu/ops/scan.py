"""Distributed blocked prefix scan.

The reference family's prefix-scan (``scan`` builtin, exercised by SSVD
per BASELINE.json:11) over a SHARDED axis. A traced ``jnp.cumsum`` on a
row-sharded operand makes GSPMD all-gather the axis (3 all-gathers in
the compiled HLO) and run the whole scan replicated — measured minutes
at 4M elements on the 8-device CPU mesh. The classic blocked
decomposition is one shard_map program with static shapes:

1. local inclusive scan per shard;
2. ``all_gather`` of the per-shard totals (p scalars per scanned
   column — tiny);
3. exclusive scan of the totals on every device (p elements);
4. combine my shard's local scan with my exclusive offset.

Supports add / mul / max / min (the combine in step 4 uses the same
associative op), scanning axis 0 of row-sharded arrays of any rank
(trailing-axis sharding is preserved through the shard_map specs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod

_LOCAL = {
    "add": jnp.cumsum,
    "mul": jnp.cumprod,
    "max": lambda v, axis: jax.lax.cummax(v, axis=axis),
    "min": lambda v, axis: jax.lax.cummin(v, axis=axis),
}
_COMBINE = {
    "add": jnp.add,
    "mul": jnp.multiply,
    "max": jnp.maximum,
    "min": jnp.minimum,
}
_IDENTITY = {"add": 0.0, "mul": 1.0, "max": -jnp.inf, "min": jnp.inf}


def _identity_for(op: str, dtype):
    if op in ("max", "min") and jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.min if op == "max" else info.max
    return _IDENTITY[op]


def _kernel(xs: jax.Array, axis_name, p: int, op: str) -> jax.Array:
    local = _LOCAL[op](xs, axis=0)
    tot = local[-1][None]                              # (1, ...) totals
    alls = jax.lax.all_gather(tot, axis_name, tiled=True)   # (p, ...)
    # exclusive scan of totals: shift the inclusive scan by identity
    incl = _LOCAL[op](alls, axis=0)
    ident = jnp.full_like(alls[:1], _identity_for(op, xs.dtype))
    excl = jnp.concatenate([ident, incl[:-1]], axis=0)
    me = jax.lax.axis_index(axis_name)
    return _COMBINE[op](local, excl[me])


def scan_axes(in_axes, ndim: int):
    """The sharding the blocked scan runs under: scan axis on the mesh
    row axis, trailing axes KEEPING their existing mesh assignment
    (the kernel is independent per trailing-axis shard — de-sharding
    columns of a block-tiled operand would all-gather them for
    nothing). A trailing axis that conflicts with the row axis is
    dropped to replicated."""
    row = tiling_mod.AXIS_ROW
    trailing = list(tuple(in_axes or ())[1:]) + [None] * ndim
    axes = [row]
    for a in trailing[:ndim - 1]:
        conflict = a == row or (isinstance(a, tuple) and row in a)
        axes.append(None if conflict else a)
    return tiling_mod.Tiling(axes)


def blocked_scan(x: jax.Array, op: str = "add", mesh=None,
                 in_axes=None) -> jax.Array:
    """Inclusive prefix scan along axis 0, distributed over the mesh
    row axis. ``in_axes`` (the operand's tiling axes, when known)
    keeps trailing-axis sharding intact. Traceable; falls back to the
    local cumulative op when the axis does not shard evenly (same
    contract as sample_sort)."""
    from jax import shard_map

    if op not in _LOCAL:
        raise ValueError(f"unknown scan op {op!r}")
    mesh = mesh or mesh_mod.get_mesh()
    axis = tiling_mod.AXIS_ROW
    p = int(mesh.shape[axis])
    n = int(x.shape[0])
    if p <= 1 or n == 0 or n % p != 0:
        return _LOCAL[op](x, axis=0)
    t = scan_axes(in_axes, x.ndim)
    t = tiling_mod.sanitize(t, x.shape, mesh)
    if t.mesh_axis_of(0) is None:  # sanitize dropped the scan axis
        return _LOCAL[op](x, axis=0)
    x = redist_mod.constrain(x, t, mesh)
    mapped = shard_map(lambda v: _kernel(v, axis, p, op), mesh=mesh,
                       in_specs=(t.spec(),), out_specs=t.spec())
    return mapped(x)
