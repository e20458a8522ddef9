"""Stochastic (randomized) SVD (config 5, BASELINE.json:11; reference:
``[U] spartan/examples/ssvd.py``): Halko, Martinsson and Tropp,
"Finding structure with randomness", SIAM Review 53(2), 2011,
randomized subspace iteration (Alg. 4.4) and the direct SVD (Alg. 5.1).

One call is one plan. The sketch Y = A Omega, the ``n_power_iter``
power iterations (Z = A^T Q, then Y = A Q_Z, each panel
re-orthonormalised), the projection B = Q^T A, B's SVD and U = Q U_B
form one DAG, evaluated as one program whose three results come back
in one fetch: A is read 2q + 2 times, and no result visits the host
before the end. A^T is never formed: ``a.T`` feeds a dot, and XLA
folds the transpose into the product's contraction. The panel QRs and
the small SVD are ``map2`` kernels over whole panels in float32, and
U = Q U_B is a float32 product; the products with A run at the default
matmul precision.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from ..array import tiling as tiling_mod
from ..expr.base import TupleExpr, as_expr, tuple_of
from ..expr.map2 import map2
from ..utils import profiling as prof


def _qr_q(x):
    return jnp.linalg.qr(x)[0]


def _orth(x):
    """An orthonormal basis of the panel ``x``'s columns."""
    return map2([x], _qr_q, out_tiling=tiling_mod.row(2))


def _small_svd(b):
    """B's SVD in one array: U_B (k rows), s (one row), then V."""
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    return jnp.concatenate([u_b, s[None, :], vt.T], axis=0)


def ssvd_expr(a, omega, rank: int, n_power_iter: int) -> TupleExpr:
    """The DAG of one call, from A and the (n, k) sketch ``omega``:
    (U, s, V) with U (m, rank), s (rank,) and V (n, rank)."""
    a = as_expr(a)
    k = omega.shape[1]
    q = _orth(st.dot(a, omega))
    for _ in range(n_power_iter):
        q = _orth(st.dot(a, _orth(st.dot(a.T, q))))
    packed = map2([st.dot(q.T, a)], _small_svd,
                  out_tiling=tiling_mod.replicated(2))
    u = st.dot(q, packed[:k, :rank], precision="highest")
    return tuple_of(u, packed[k, :rank], packed[k + 1:, :rank])


def ssvd(a, rank: int, n_oversample: int = 10, n_power_iter: int = 2,
         seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Approximate truncated SVD: returns (U, s, Vt) with U (m, rank),
    s (rank,) and Vt (rank, n), from a sketch of ``k = rank +
    n_oversample`` columns drawn as ``RandomState(seed).randn(n, k)``."""
    m, n = a.shape
    k = min(rank + n_oversample, m, n)
    with prof.span("ssvd", m=m, n=n, l=k, q=n_power_iter):
        omega = st.from_numpy(
            np.random.RandomState(seed).randn(n, k).astype(np.float32),
            tiling=tiling_mod.replicated(2))
        u, s, v = ssvd_expr(a, omega, rank, n_power_iter).glom()
    return u, s, v.T
