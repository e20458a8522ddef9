"""Sparse PageRank (config 5, BASELINE.json:11; reference:
``[U] spartan/examples/pagerank.py``).

The reference iterated rank = d * A^T rank + (1-d)/n with per-tile sparse
kernels and shuffle merges. Here A^T is a :class:`SparseDistArray`; each
power iteration is one jitted SpMV (gather on the entry shards +
segment-merge) plus the teleport term.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..array.distarray import fetch_to_host
from ..array.sparse import SparseDistArray
from ..utils import profiling as prof


def _teleport_body(y, damping, n):
    new = damping * y + (1.0 - damping) / n
    dangling = 1.0 - jnp.sum(new)
    return new + dangling / n


@functools.partial(jax.jit, static_argnames=("n",))
def _teleport(y, damping, *, n):
    """Teleport + dangling-mass correction. Kept in a SEPARATE jit from
    the SpMV on the BCOO fallback path: fusing elementwise ops into the
    BCOO matvec program makes XLA drop the fast sparse lowering
    (measured 294 -> 1705 ms at 16M entries on v5e)."""
    return _teleport_body(y, damping, n)


def pagerank(links: SparseDistArray, damping: float = 0.85,
             num_iter: int = 20, tol: float = 0.0,
             transition: Optional[SparseDistArray] = None) -> np.ndarray:
    """links[i, j] != 0 means page i links to page j. Returns ranks.

    On TPU (windowed spmv available, no convergence checks) the whole
    power iteration runs as ONE dispatched program: a ``lax.fori_loop``
    of windowed-spmv + teleport steps. This is only possible because the
    windowed kernel keeps its speed inside ``fori_loop`` — XLA's own
    sparse lowerings degrade ~10x there — and it removes the per-
    iteration dispatch and fetch.

    ``transition`` lets callers pass a precomputed column-stochastic
    matrix; by default ``links.transition()`` builds it once and caches
    it on ``links`` (host-side restructuring — the transpose re-sorts
    all entries; see SparseDistArray.transition / clear_cache)."""
    n = links.shape[0]
    T = transition if transition is not None else links.transition()

    rank = jnp.full((n,), 1.0 / n, jnp.float32)
    damp = jnp.float32(damping)
    if tol == 0 and T._default_windowed():
        with prof.phase("dispatch"):
            out = _pagerank_fused(T, rank, damp, num_iter)
        return fetch_to_host(out)[0]
    for _ in range(num_iter):
        new = _teleport(T.spmv(rank), damp, n=n)
        if tol > 0:
            # convergence check costs one host fetch per iteration
            delta = float(jnp.abs(new - rank).sum())
            rank = new
            if delta < tol:
                break
        else:
            rank = new
    return fetch_to_host(rank)[0]


@functools.partial(jax.jit, static_argnames=("n", "dims"))
def _pagerank_loop(bufs, rank, damp, iters, *, n, dims):
    """Module-level jit: plan buffers are traced arguments, so matrices
    with the same plan dimensions share one compile and nothing pins
    device memory."""
    from ..ops.segment import windowed_spmv

    def body(_, r):
        return _teleport_body(windowed_spmv(*bufs, r, dims), damp, n)

    return jax.lax.fori_loop(0, iters, body, rank)


def _pagerank_fused(T: SparseDistArray, rank, damp, num_iter: int):
    """One dispatch for the whole power iteration; the iteration count
    is a traced loop bound so every num_iter shares one compile."""
    bufs, dims = T._windowed_plan()
    return _pagerank_loop(bufs, rank, damp, jnp.int32(num_iter),
                          n=T.shape[0], dims=dims)
