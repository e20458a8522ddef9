"""Plain PageRank: the graph generator and the float64 reference.

After ``chip_smoke.py``'s config-5 PageRank and ``_np_pagerank``, so
that a change there cannot move this yardstick. Imports nothing of
spartan_tpu. PageRank as the GAP Benchmark Suite and LDBC Graphalytics
define it: damping 0.85, a fixed number of iterations, the rank of
dangling nodes spread evenly.

Graph: GAP's uniform random graph (``Urand``; the gapbs generator's
``-u <scale> -k <edge factor>``): ``edge_factor * 2**scale`` edges,
each with both endpoints uniform over the nodes, undirected, so every
edge is an entry in each direction. The multiset of the endpoints, and
so every node's degree, is drawn once from a fixed key; the seed
orders it, and consecutive endpoints make the edges. Conditioned on
its degrees, GAP's graph is exactly such a random pairing, so a seed
gives GAP's graph with the degrees of one fixed draw. The program's
windowed plan pads each 1,024-node window of in-edges to whole blocks,
so its sizes follow the in-degrees: with degrees drawn per seed, its
time per iteration fell into two classes by seed, 127 and 151 ms on an
earlier graph of this size (my chip run, PR 22), and a new class would
compile in set-up. Self-loops and repeated edges are kept (gapbs's
builder drops them: some 16 and 256 of 16.8M edges), so every seed has
the same number of entries; the program's COO constructor sums a
repeated entry and the reference counts each, which is one operator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def graph_size(cfg: dict) -> tuple:
    """(nodes, entries) of the configuration's graph."""
    n = 1 << cfg["scale"]
    return n, cfg["edge_factor"] * n * (2 if cfg["undirected"] else 1)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor",
                                             "undirected"))
def edges(key, *, scale: int, edge_factor: int, undirected: bool):
    """(rows, cols) of every entry: entry ``(i, j)`` is an edge from
    node ``i`` to node ``j``. On the device, one call."""
    n = 1 << scale
    m = edge_factor * n
    ends = jax.random.randint(jax.random.key(0), (2 * m,), 0, n, jnp.int32)
    ends = jax.random.permutation(key, ends)
    u, v = ends[0::2], ends[1::2]
    if undirected:
        return jnp.concatenate([u, v]), jnp.concatenate([v, u])
    return u, v


def pagerank(rows: np.ndarray, cols: np.ndarray, n: int, damping: float,
             iters: int) -> np.ndarray:
    """float64 power iteration over the entry list."""
    out_deg = np.bincount(rows, minlength=n).astype(np.float64)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1.0), 0.0)[rows]
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        y = np.bincount(cols, weights=r[rows] * inv, minlength=n)
        new = damping * y + (1.0 - damping) / n
        r = new + (1.0 - new.sum()) / n
    return r


def rank_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest error of any rank, as a share of the largest rank."""
    return float(np.max(np.abs(got.astype(np.float64) - ref))
                 / np.max(ref))


def pagerank_lowp(rows, cols, n: int, damping: float, iters: int, dtype):
    """The control: the reference put in the program's place with the
    ranks held in ``dtype`` (bfloat16 for this float32 configuration)
    between iterations; each iteration's sums accumulate in float32.
    Runs on the device."""
    out_deg = jax.ops.segment_sum(jnp.ones(rows.shape, jnp.float32), rows, n)
    inv = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1.0), 0.0)[rows]

    def body(_, r):
        y = jax.ops.segment_sum(r.astype(jnp.float32)[rows] * inv, cols, n)
        new = damping * y + (1.0 - damping) / n
        return (new + (1.0 - jnp.sum(new)) / n).astype(dtype)

    r = jax.lax.fori_loop(0, iters, body, jnp.full((n,), 1.0 / n, dtype))
    return r.astype(jnp.float32)
