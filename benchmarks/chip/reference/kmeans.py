"""Plain k-means: the data generator and the float64 reference.

After ``chip_smoke.py``'s ``_blobs`` and ``_np_kmeans``, copied here so
that a change there cannot move this yardstick. Imports nothing of
spartan_tpu: the generator makes the data from the seed with
``jax.random`` on whatever device JAX has, and the reference reads
only that data and the host-side centres.

Data: ``k`` Gaussian blobs, centre spread 4, unit noise, in ``d``
dimensions. A fit job starts from ``k`` points of the data drawn from
the seed (Forgy's start, as ``examples/kmeans.py`` does with no
centres given): about a third of the blobs get no starting centre and
others two, so Lloyd spends the job's iterations merging and splitting
blobs and, on the seeds tried, had not reached a fixed point after 20
(my CPU runs at 100k-200k points, PR 22). Where two centres split one
blob, the boundary runs through it, so a few points lie within
rounding of it: float32 and float64 assign them differently, and the
centres of those blobs drift apart over the iterations. The
comparison therefore takes the median centre's error (the blobs with
one centre agree to about 1e-5), and checks the assignment against the
program's own centres, where a near-tie costs only its rounding.

Boundary queries (serve traffic only): a share of each query batch is
drawn between two blobs at a margin, in squared distance, that is
log-uniform in ``[margin_lo, margin_hi]``. float32 resolves every such
margin (its error on a squared distance here is about 1e-3); a bfloat16
rounding of the inputs (error about 1) does not. That is what lets the
comparison see a lower precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def blob_centres(seed: int, k: int, d: int) -> np.ndarray:
    """The ``k`` true centres (host, float32), from the seed alone."""
    rng = np.random.default_rng([seed, 0])
    return (rng.standard_normal((k, d), dtype=np.float32) * 4.0)


def make_points(key, true: np.ndarray, n: int, pool: int, sharding):
    """``n`` blob points, made on the device in one jitted call and
    laid out as ``sharding`` says, and ``pool`` distinct ones of them
    drawn from the key (host, float32): the rows job starts come from."""
    def make(key, true):
        k1, k2, k3 = jax.random.split(key, 3)
        lab = jax.random.randint(k1, (n,), 0, true.shape[0])
        pts = true[lab] + jax.random.normal(k2, (n, true.shape[1]),
                                            jnp.float32)
        return pts, pts[jax.random.choice(k3, n, (pool,), replace=False)]

    pts, rows = jax.jit(make, out_shardings=(sharding, None))(
        key, jnp.asarray(true))
    return pts, np.asarray(rows)


def job_init(seed: int, pool: np.ndarray, job: int, k: int) -> np.ndarray:
    """Job ``job``'s starting centres: ``k`` distinct pool rows drawn
    for that job, so no two jobs are the same computation."""
    rng = np.random.default_rng([seed, 1, job])
    return pool[np.sort(rng.choice(len(pool), k, replace=False))]


def query_pool(seed: int, true: np.ndarray, batches: int, rows: int,
               boundary_share: float, margin_lo: float,
               margin_hi: float) -> np.ndarray:
    """``batches`` query batches of ``rows`` x d float32 (host): blob
    points, and a ``boundary_share`` of rows between two blobs."""
    rng = np.random.default_rng([seed, 2])
    k, d = true.shape
    n = batches * rows
    lab = rng.integers(0, k, n)
    pts = true[lab] + rng.standard_normal((n, d), dtype=np.float32)
    nb = int(round(n * boundary_share))
    idx = rng.choice(n, nb, replace=False)
    a = rng.integers(0, k, nb)
    b = (a + rng.integers(1, k, nb)) % k
    u = (true[b] - true[a]).astype(np.float64)
    norm = np.linalg.norm(u, axis=1, keepdims=True)
    uhat = u / norm
    w = rng.standard_normal((nb, d))
    w -= np.sum(w * uhat, axis=1, keepdims=True) * uhat
    margin = np.exp(rng.uniform(np.log(margin_lo), np.log(margin_hi), nb))
    # |p - b|^2 - |p - a|^2 = margin: a is nearer than b by ``margin``
    mid = 0.5 * (true[a] + true[b])
    pts[idx] = (mid - (margin[:, None] / (2.0 * norm)) * uhat + w
                ).astype(np.float32)
    return pts.reshape(batches, rows, d)


# -- the float64 reference ------------------------------------------------


BLOCK = 1 << 16  # rows at a time: the (rows, k) scores stay small


def scores(pts64: np.ndarray, c64: np.ndarray) -> np.ndarray:
    """(n, k) squared distances less each point's own squared norm,
    float64: the same per row, so argmins and gaps are the distances'."""
    out = pts64 @ c64.T
    out *= -2.0
    out += np.sum(c64 * c64, axis=1)
    return out


def lloyd(pts64: np.ndarray, c64: np.ndarray, iters: int):
    """``iters`` Lloyd iterations in float64: (centres, the iteration at
    which the assignment stopped changing, or None).

    Stops early only at an exact fixed point: when an iteration leaves
    the assignment unchanged, every later iteration returns the same
    centres, so the result is the same as running all ``iters``."""
    k = c64.shape[0]
    prev = None
    for it in range(iters):
        a = np.concatenate([np.argmin(scores(pts64[i:i + BLOCK], c64), 1)
                            for i in range(0, len(pts64), BLOCK)])
        if prev is not None and np.array_equal(a, prev):
            return c64, it
        sums = np.zeros((k, pts64.shape[1]))
        for i in range(0, len(pts64), BLOCK):
            blk = a[i:i + BLOCK]
            onehot = (blk[:, None] == np.arange(k)).astype(np.float64)
            sums += onehot.T @ pts64[i:i + BLOCK]
        # an empty cluster's centre is 0, as in the program
        c64 = sums / np.maximum(np.bincount(a, minlength=k), 1)[:, None]
        prev = a
    return c64, None


def centre_err(got: np.ndarray, ref: np.ndarray) -> float:
    """The median over centres of each centre's largest error."""
    return float(np.median(np.max(np.abs(got.astype(np.float64) - ref),
                                  axis=1)))


def served_gap(q64: np.ndarray, c64: np.ndarray,
               served: np.ndarray) -> float:
    """Widest gap, in squared distance, by which a served centre lies
    further from its row than the nearest centre (0 when every served
    id is a nearest one)."""
    gap = 0.0
    served = served.reshape(-1).astype(np.int64)
    for i in range(0, len(q64), BLOCK):
        d2 = scores(q64[i:i + BLOCK], c64)
        got = np.take_along_axis(d2, served[i:i + BLOCK, None], axis=1)
        gap = max(gap, float(np.max(got[:, 0] - d2.min(axis=1))))
    return gap


# -- the control: the reference at the next precision down ----------------


def lloyd_lowp(points, init, iters: int, dtype):
    """The reference put in the program's place, computed in ``dtype``
    (bfloat16 for this float32 configuration): points and centres are
    rounded to ``dtype``, products and sums accumulate in float32, as
    a bfloat16 copy of the data would. Runs on the device."""
    k = init.shape[0]
    p = points.astype(dtype)

    def nearest(c):
        cl = c.astype(dtype)
        g = jnp.dot(p, cl.T, preferred_element_type=jnp.float32)
        cn = jnp.sum(cl.astype(jnp.float32) ** 2, axis=1)
        return jnp.argmin(cn[None, :] - 2.0 * g, axis=1)

    def body(_, c):
        a = nearest(c)
        sums = jax.ops.segment_sum(p.astype(jnp.float32), a, k)
        cnt = jax.ops.segment_sum(jnp.ones(a.shape, jnp.float32), a, k)
        return (sums / jnp.maximum(cnt, 1.0)[:, None]).astype(
            dtype).astype(jnp.float32)

    c = jax.lax.fori_loop(0, iters, body, jnp.asarray(init, jnp.float32))
    return c, nearest(c)


def nearest_lowp(q, c, dtype):
    """Nearest-centre ids computed in ``dtype`` (the serve control)."""
    ql, cl = q.astype(dtype), c.astype(dtype)
    g = jnp.dot(ql, cl.T, preferred_element_type=jnp.float32)
    cn = jnp.sum(cl.astype(jnp.float32) ** 2, axis=1)
    return jnp.argmin(cn[None, :] - 2.0 * g, axis=1)
