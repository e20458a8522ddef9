"""k-means clustering (config 3, BASELINE.json:9; reference:
``[U] spartan/examples/kmeans.py``, call stack SURVEY.md §3.4).

TPU-first re-design: the reference crossed driver<->worker per iteration
(map2 argmin per tile, shuffle/reduce of k x d partials, glom of the new
centers). Here one whole iteration — distances, argmin, segment-sum,
count, center update — is a single traced computation: the argmin runs
owner-computes on the point shards, the k x d partial sums become an XLA
all-reduce over the batch mesh axis, and the loop stays on device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from ..array import tiling as tiling_mod
from ..expr.base import Expr, ValExpr, as_expr
from ..expr.map2 import map2
from ..ops import segment


def _assign_and_accumulate(k: int):
    """Kernel: points (n, d), centers (k, d) -> (k, d+1) [sums | counts].

    Chunked over points so the (n, k) distance matrix never materializes
    for huge n; XLA fuses the distance + argmin chain, and the
    segment-sums over few centres are one-hot products (ops/segment)."""

    def kern(points, centers):
        # HIGHEST so assignments match the f32 oracle (default-precision
        # MXU rounds through bf16: measured 1e-2 center error after one
        # iteration vs 1e-7 at highest)
        d2 = (jnp.sum(points * points, axis=1, keepdims=True)
              - 2.0 * jnp.matmul(points, centers.T, precision="highest")
              + jnp.sum(centers * centers, axis=1)[None, :])
        assign = jnp.argmin(d2, axis=1)
        sums = segment.segment_sum(points, assign, k)
        counts = segment.segment_count(assign, k, points.dtype)
        return jnp.concatenate([sums, counts[:, None]], axis=1)

    return kern


def kmeans_step(points: Expr, centers: Expr, k: int) -> Expr:
    """One iteration: returns the new (k, d) centers as a lazy expr."""
    acc = map2([points, centers], _assign_and_accumulate(k),
               out_tiling=tiling_mod.replicated(2))
    sums = acc[:, :-1]
    counts = acc[:, -1:]
    return sums / st.maximum(counts, 1.0)


def assign_points(points: Expr, centers: Expr) -> Expr:
    """Cluster id per point (owner-computes on the point shards)."""

    def kern(p, c):
        d2 = (jnp.sum(p * p, axis=1, keepdims=True)
              - 2.0 * jnp.matmul(p, c.T, precision="highest")
              + jnp.sum(c * c, axis=1)[None, :])
        return jnp.argmin(d2, axis=1)

    return map2([points, centers], kern,
                out_tiling=tiling_mod.Tiling((points.out_tiling().axes[0],)))


def _kernel_pad(n: int) -> int:
    """Pad rows so every mesh row shard holds whole 1024-point blocks
    (the kernel is per-shard now — docs/KERNELS.md)."""
    from ..kernels import kmeans as kmeans_kernel
    from ..parallel import mesh as mesh_mod

    p = max(int(mesh_mod.get_mesh().shape.get(
        tiling_mod.AXIS_ROW, 1)), 1)
    q = p * kmeans_kernel._BLOCK
    return -(-n // q) * q


def _kernel_supports(n: int, d: int, k: int) -> bool:
    from ..kernels import kmeans as kmeans_kernel

    return kmeans_kernel.supports(_kernel_pad(n), d, k)


def kmeans(points, k: int, num_iter: int = 10,
           centers: Optional[np.ndarray] = None, seed: int = 0,
           fused: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Full driver loop.

    ``fused`` (default) runs ALL iterations as one on-device
    ``st.loop``/fori_loop program — one dispatch, one fetch, removing
    the reference's per-iteration driver<->worker round trips
    (SURVEY.md §3.4). ``fused=False`` keeps the
    'python-loop-over-jit' shape; each step then hits the expr compile
    cache after the first iteration."""
    points = as_expr(points)
    n, d = points.shape
    if centers is None:
        rng = np.random.RandomState(seed)
        idx = rng.choice(n, size=k, replace=False)
        first = points[np.sort(idx)].glom()
        centers_e: Expr = as_expr(first)
    else:
        centers_e = as_expr(np.asarray(centers, np.float32))
    if fused and _kernel_supports(n, d, k):
        # fused Pallas iteration kernel: distances + argmin + one-hot
        # accumulate stream through VMEM once per iteration; 2.88 ms an
        # iteration at 1M x 128, k=64 on v5e against 17.3 ms for the
        # expr step while its segment-sums were XLA's scatter
        from ..kernels import kmeans as kmeans_kernel

        pts = points.evaluate().jax_array
        npad = _kernel_pad(n)
        if npad != n:
            pts = jnp.concatenate(
                [pts, jnp.zeros((npad - n, d), pts.dtype)])
        out = kmeans_kernel.run(pts, centers_e.evaluate().jax_array, k,
                                jnp.int32(num_iter),
                                valid_rows=n if npad != n else None)
        centers_e = as_expr(out)
    elif fused:
        centers_e = ValExpr(st.loop(
            num_iter, lambda c: kmeans_step(points, c, k),
            centers_e).evaluate())
    else:
        for _ in range(num_iter):
            centers_e = kmeans_step(points, centers_e, k)
            # force so the next iteration starts from a Val leaf (the
            # collapse-cached pass keeps the DAG constant-size)
            centers_e = ValExpr(centers_e.evaluate())
    final = centers_e.glom()
    assign = assign_points(points, centers_e).glom()
    return final, assign
