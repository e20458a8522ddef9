"""Streaming drivers over the delta-aware incremental engine
(docs/INCREMENTAL.md; ISSUE 16's consumer layer).

Each driver keeps its state in :class:`DistArray` handles and applies
new data through the mutation seam — ``DistArray.update()`` /
``st.assign`` — so the per-step DAGs keep hitting the plan cache AND
the incremental engine (``FLAGS.incremental``) can serve warm steps
from the per-plan result cache, recomputing only the tiles each batch
actually dirtied. The drivers compose with the rest of the stack:
multi-step refinements run through ``st.loop`` (one on-device program,
checkpoint/resume), and every ``*_async`` entry point submits through
``serve/`` (``evaluate_async``: admission control, coalescing, flight
recording; solo serve dispatches route through ``evaluate()`` and so
stay incremental).

What is (and is not) delta-scaled — the honest contract:

* :class:`IncrementalPageRank` — the per-batch correction step after
  ``insert_edges`` IS delta-scaled: the base rank vector is held fixed
  for a rebase window, so only the transition matrix's dirty columns
  changed since the cached step and the engine restricts the matvec to
  them (the acceptance benchmark's ≥5x warm-step speedup at ≤1% dirty).
  Every ``rebase_every`` batches the driver folds the estimate into a
  new base (a full recompute) — a standard streaming rebase window.
* :class:`OnlineKMeans` — every batch is new data (whole-batch dirty),
  so assignment steps are full dispatches; the wins here are the plan
  cache (fixed batch shape -> zero recompiles) and ``st.loop`` refine.
* :class:`SlidingWindowStats` — a windowed reduction needs every
  element, so ``stats()`` after a push is a full (cheap, small-output)
  dispatch; but repeated ``stats()``/``normalized()`` calls BETWEEN
  pushes are all-clean result-cache hits (zero dispatch), which is the
  common read-heavy monitoring pattern.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

from ..array import distarray as da_mod
from ..array.distarray import DistArray
from ..expr import base as expr_base
from ..expr.base import lazify


def _dist(x: Any) -> DistArray:
    if isinstance(x, DistArray):
        return x
    return da_mod.from_numpy(np.asarray(x))


class IncrementalPageRank:
    """Dense-transition PageRank over edge-insert batches.

    Holds a column-stochastic transition matrix ``A`` (n, n) where
    ``A[i, j]`` is the probability of moving from page i to page j,
    and a rank estimate. ``insert_edges(col, new_column)`` replaces one
    or more pages' in-link columns through ``DistArray.update`` — the
    lineage-recorded delta. ``step()`` evaluates one damped power-
    iteration correction ``r' = d * (r0 @ A) + (1-d)/n`` against the
    FIXED base vector ``r0``: with ``FLAGS.incremental`` on, the warm
    step recomputes only ``r0 @ A[:, dirty]`` and splices it into the
    cached product, so per-batch cost scales with the edge delta, not
    the graph. (For the sparse/Pallas batch path see
    examples/pagerank.py — this driver is the dense streaming
    counterpart the incremental engine can see through.)
    """

    def __init__(self, transition: Any, damping: float = 0.85,
                 rebase_every: int = 8):
        self.A = _dist(transition)
        n = self.A.shape[0]
        if self.A.shape != (n, n):
            raise ValueError(f"transition must be square, got "
                             f"{self.A.shape}")
        self.n = n
        self.damping = float(damping)
        self.rebase_every = int(rebase_every)
        self._base = da_mod.from_numpy(
            np.full((n,), 1.0 / n, self.A.dtype))  # r0, held fixed
        self.ranks: DistArray = self._base
        self._batches_since_rebase = 0

    def _step_expr(self):
        from ..expr.dot import DotExpr

        prod = DotExpr(lazify(self._base), lazify(self.A))
        return prod * self.damping + (1.0 - self.damping) / self.n

    def insert_edges(self, cols: slice, values: Any) -> None:
        """Replace the in-link columns ``A[:, cols]`` (already
        column-stochastic) — one lineage-logged region write."""
        self.A = self.A.update((slice(0, self.n), cols),
                               np.asarray(values, self.A.dtype))
        self._batches_since_rebase += 1

    def step(self) -> DistArray:
        """One damped correction against the fixed base vector —
        the delta-scaled warm step. Rebases when the window is up."""
        if self._batches_since_rebase >= self.rebase_every:
            self.rebase()
        self.ranks = expr_base.evaluate(self._step_expr())
        return self.ranks

    def step_async(self, tenant: Optional[str] = None):
        """The serve/ route: submit the correction step to the
        concurrent engine (admission, flight recording); solo serve
        dispatches evaluate() underneath and stay incremental."""
        return self._step_expr().evaluate_async(tenant=tenant)

    def rebase(self, iters: int = 4) -> DistArray:
        """Fold the current estimate into a new base with ``iters``
        full power iterations in ONE on-device program (st.loop) —
        the full-recompute end of the streaming window."""
        from ..expr.loop import loop as st_loop

        A = lazify(self.A)
        d, n = self.damping, self.n
        out = st_loop(
            iters, lambda r: r.dot(A) * d + (1.0 - d) / n,
            lazify(self.ranks))
        self._base = expr_base.evaluate(out)
        self.ranks = self._base
        self._batches_since_rebase = 0
        return self._base


class OnlineKMeans:
    """Mini-batch k-means (Sculley 2010 style) over streaming batches.

    ``partial_fit(batch)`` assigns the batch to the current centers and
    moves each center toward its batch mean with a per-center learning
    rate 1/count — one dispatched program per batch, plan-cached across
    batches of the same shape. ``refine`` runs full Lloyd iterations
    over a reference point set through ``st.loop``.
    """

    def __init__(self, centers: Any):
        self.centers = _dist(centers)
        self.k, self.d = self.centers.shape
        self._counts = da_mod.from_numpy(
            np.ones((self.k,), self.centers.dtype))

    def partial_fit(self, batch: Any) -> DistArray:
        import jax.numpy as jnp

        from ..expr.map2 import map2
        from ..ops import segment

        pts = _dist(np.asarray(batch, self.centers.dtype))
        k = self.k

        def kern(points, centers, counts):
            d2 = (jnp.sum(points * points, axis=1, keepdims=True)
                  - 2.0 * jnp.matmul(points, centers.T,
                                     precision="highest")
                  + jnp.sum(centers * centers, axis=1)[None, :])
            assign = jnp.argmin(d2, axis=1)
            sums = segment.segment_sum(points, assign, k)
            cnt = segment.segment_count(assign, k, points.dtype)
            new_counts = counts + cnt
            lr = (cnt / new_counts)[:, None]
            mean = sums / jnp.maximum(cnt, 1.0)[:, None]
            moved = jnp.where(cnt[:, None] > 0,
                              centers * (1.0 - lr) + mean * lr,
                              centers)
            return jnp.concatenate([moved, new_counts[:, None]], axis=1)

        from ..array import tiling as tiling_mod

        packed = expr_base.evaluate(map2(
            [lazify(pts), lazify(self.centers), lazify(self._counts)],
            kern, out_tiling=tiling_mod.replicated(2)))
        host = np.asarray(packed.jax_array)
        self.centers = da_mod.from_numpy(host[:, :-1])
        self._counts = da_mod.from_numpy(host[:, -1])
        return self.centers

    def refine(self, points: Any, iters: int = 5) -> DistArray:
        """Full Lloyd iterations over ``points`` as ONE on-device
        st.loop program (checkpoint/resume-capable)."""
        from ..examples.kmeans import kmeans_step
        from ..expr.loop import loop as st_loop

        pts = lazify(_dist(points))
        out = st_loop(
            iters, lambda c: kmeans_step(pts, c, self.k),
            lazify(self.centers))
        self.centers = expr_base.evaluate(out)
        return self.centers


class SlidingWindowStats:
    """Per-feature mean/std over a ring-buffer window (w, d).

    ``push(rows)`` overwrites the oldest slots through
    ``DistArray.update`` (lineage-logged); ``stats()`` reduces the
    window. A windowed reduction touches every element, so the
    post-push ``stats()`` is a full (small) dispatch — but every
    read between pushes is an all-clean result-cache hit with zero
    dispatch, and ``normalized()`` (elementwise over the window) IS
    delta-scaled to the rows the last push dirtied.
    """

    def __init__(self, window: int, dim: int, dtype: Any = np.float32):
        self.window = int(window)
        self.dim = int(dim)
        self.buf = da_mod.from_numpy(
            np.zeros((self.window, self.dim), dtype))
        self._head = 0
        self._filled = 0

    def push(self, rows: Any) -> None:
        rows = np.asarray(rows, self.buf.dtype)
        if rows.ndim == 1:
            rows = rows[None, :]
        r = 0
        while r < len(rows):
            take = min(len(rows) - r, self.window - self._head)
            self.buf = self.buf.update(
                (slice(self._head, self._head + take),
                 slice(0, self.dim)),
                rows[r:r + take])
            self._head = (self._head + take) % self.window
            r += take
        self._filled = min(self.window, self._filled + len(rows))

    def stats(self) -> Tuple[DistArray, DistArray]:
        """(mean, std) per feature over the window — one plan-cached
        dispatch after a push, a zero-dispatch cache hit otherwise."""
        x = lazify(self.buf)
        mean = expr_base.evaluate(x.mean(axis=0))
        var = expr_base.evaluate(((x - lazify(mean)) ** 2).mean(axis=0))
        std = expr_base.evaluate(lazify(var) ** 0.5)
        return mean, std

    def stats_async(self, tenant: Optional[str] = None):
        """serve/ route for read-heavy monitors: mean through the
        concurrent engine (coalesces identical concurrent readers)."""
        return lazify(self.buf).mean(axis=0).evaluate_async(
            tenant=tenant)

    def normalized(self, mean: DistArray, std: DistArray) -> DistArray:
        """(window - mean) / std — elementwise over the big buffer, so
        a warm call after a push recomputes only the pushed rows."""
        x = lazify(self.buf)
        return expr_base.evaluate(
            (x - lazify(mean)) / (lazify(std) + 1e-12))
