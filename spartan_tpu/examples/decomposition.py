"""Blocked matrix decompositions: Cholesky and QR (reference:
``[U] spartan/examples/`` cholesky, qr — SURVEY.md §2.4).

The reference ran blocked right-looking Cholesky / TSQR with per-tile
kernels and shuffle updates. TPU-first: the factorizations are traced
``jnp.linalg`` calls over the sharded operand — XLA's blocked
implementations run on the MXU, and a TSQR variant demonstrates the
explicit tree reduction over row shards for tall-skinny inputs.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from ..array import tiling as tiling_mod
from ..expr.base import Expr, as_expr
from ..expr.map2 import map2


def cholesky(a) -> Expr:
    """Lower-triangular factor of an SPD matrix."""
    a = as_expr(a)
    return map2([a], jnp.linalg.cholesky,
                out_tiling=tiling_mod.replicated(2))


def qr(a) -> Tuple[np.ndarray, np.ndarray]:
    """Thin QR of a (possibly row-sharded) matrix."""
    a = as_expr(a)

    def kern(x):
        q, r = jnp.linalg.qr(x)
        return jnp.concatenate([q, r], axis=0)  # pack (m+n, n)

    packed = map2([a], kern, out_tiling=tiling_mod.replicated(2)).glom()
    m = a.shape[0]
    return packed[:m], packed[m:]


def tsqr(a) -> Tuple[np.ndarray, np.ndarray]:
    """Tall-skinny QR: local QR per row shard, tree-reduced R factors —
    the owner-computes algorithm the reference's per-tile QR performed,
    expressed as one shard_map program."""
    from jax import shard_map

    from ..parallel import mesh as mesh_mod

    a = as_expr(a)
    arr = a.evaluate()
    mesh = mesh_mod.get_mesh()
    n_x = mesh.shape[mesh_mod.AXIS_ROW]
    m, n = a.shape
    if m % max(n_x, 1) or m // max(n_x, 1) < n:
        # fall back to the plain path when shards would be wide
        return qr(a)

    row_t = tiling_mod.row(2)
    x = jax.device_put(arr.jax_array, row_t.sharding(mesh))

    def kern(block):
        q1, r1 = jnp.linalg.qr(block)  # local (m/p, n), (n, n)
        # gather all R factors, QR the stack, correct local Q
        rs = jax.lax.all_gather(r1, mesh_mod.AXIS_ROW)  # (p, n, n)
        stacked = rs.reshape(-1, n)
        q2, r = jnp.linalg.qr(stacked)
        my = jax.lax.axis_index(mesh_mod.AXIS_ROW)
        q2_mine = jax.lax.dynamic_slice_in_dim(q2, my * n, n, axis=0)
        return jnp.concatenate([q1 @ q2_mine, r], axis=0)

    packed = jax.jit(shard_map(
        kern, mesh=mesh, in_specs=(row_t.spec(),),
        out_specs=tiling_mod.Tiling((mesh_mod.AXIS_ROW, None)).spec()))(x)
    packed = np.asarray(jax.device_get(packed))
    shard_rows = m // n_x + n
    qs, r = [], None
    for p in range(n_x):
        blk = packed[p * shard_rows:(p + 1) * shard_rows]
        qs.append(blk[:m // n_x])
        r = blk[m // n_x:]
    return np.concatenate(qs, axis=0), r


def netflix_sgd(ratings, k: int = 16, num_iter: int = 10,
                lr: float = 0.01, reg: float = 0.05, seed: int = 0
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Netflix-style SGD matrix factorization (reference:
    ``[U] spartan/examples/netflix.py``): full-gradient descent on the
    observed entries, one traced step per iteration over the
    batch-sharded ratings."""
    ratings = as_expr(ratings)
    m, n = ratings.shape
    rng = np.random.RandomState(seed)
    u = rng.rand(m, k).astype(np.float32) * 0.1
    v = rng.rand(n, k).astype(np.float32) * 0.1

    def step(rv, uv, vv):
        pred = uv @ vv.T
        mask = (rv != 0).astype(rv.dtype)
        err = (pred - rv) * mask
        gu = err @ vv / jnp.maximum(mask.sum(), 1.0) + reg * uv
        gv = err.T @ uv / jnp.maximum(mask.sum(), 1.0) + reg * vv
        return jnp.concatenate([uv - lr * gu,
                                vv - lr * gv], axis=0)

    for _ in range(num_iter):
        eu = st.from_numpy(u, tiling=tiling_mod.replicated(2))
        ev = st.from_numpy(v, tiling=tiling_mod.replicated(2))
        packed = map2([ratings, eu, ev], step,
                      out_tiling=tiling_mod.replicated(2)).glom()
        u, v = packed[:m], packed[m:]
    return u, v
