"""Plain randomized SVD: the matrix generator and the float64 reference.

Halko, Martinsson and Tropp, "Finding structure with randomness", SIAM
Review 53(2), 2011: randomized subspace iteration (Alg. 4.4) for an
orthonormal basis Q of A's range, then the direct SVD (Alg. 5.1) of
B = Q^T A. Written here from the paper, so that a change to the
program cannot move this yardstick; imports nothing of spartan_tpu.

Matrix: HMT's eigenfaces matrix (Sec. 7.3) is FERET's face images,
which are not here. It stands in as A = G diag(sigma), G standard
Gaussian, sigma_j = j^-1/2: a spectrum that decays slowly, as HMT
report of the faces (the reason they take power iterations), made on
the device from the seed. Its singular values are about
sqrt(m) sigma_j, with a gap of 5% between the 10th and the 11th.

The sketch Omega of a call is ``RandomState(seed).randn(n, k)`` in
float32, as the program draws it, and the reference is the same
algorithm on the same Omega: what is compared is rounding, not the
randomized approximation, which both share.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8192  # rows of A in float64 at a time (a 475 MB block)


def make_matrix(key, m: int, n: int, sharding):
    """A = G diag(sigma), made on the device in one jitted call and
    laid out as ``sharding`` says."""
    def make(key):
        sigma = jnp.arange(1, n + 1, dtype=jnp.float32) ** -0.5
        return jax.random.normal(key, (m, n), jnp.float32) * sigma

    return jax.jit(make, out_shardings=sharding)(key)


def omega_seed(seed: int, job: int) -> int:
    """Job ``job``'s sketch seed (``RandomState`` takes 32 bits), drawn
    from the run's seed, so no two jobs share a sketch."""
    return int(np.random.default_rng([seed, 6, job]).integers(1 << 32))


def omega(seed: int, n: int, k: int) -> np.ndarray:
    """The (n, k) float32 sketch the program draws for ``seed``."""
    return np.random.RandomState(seed).randn(n, k).astype(np.float32)


# -- the float64 reference ------------------------------------------------


def _times(a32: np.ndarray, x: np.ndarray) -> np.ndarray:
    """A @ x in float64, A converted a block of rows at a time."""
    return np.concatenate([a32[i:i + BLOCK].astype(np.float64) @ x
                           for i in range(0, len(a32), BLOCK)])


def _t_times(a32: np.ndarray, y: np.ndarray) -> np.ndarray:
    """A^T @ y in float64, by blocks of rows."""
    out = np.zeros((a32.shape[1], y.shape[1]))
    for i in range(0, len(a32), BLOCK):
        out += a32[i:i + BLOCK].astype(np.float64).T @ y[i:i + BLOCK]
    return out


def _orth(x: np.ndarray) -> np.ndarray:
    return np.linalg.qr(x)[0]


def hmt(a32: np.ndarray, omega32: np.ndarray, rank: int, power_iter: int):
    """(U, s, Vt) of HMT Alg. 4.4 + 5.1 in float64: U (m, rank), s
    (rank,), Vt (rank, n)."""
    q = _orth(_times(a32, omega32.astype(np.float64)))
    for _ in range(power_iter):
        q = _orth(_times(a32, _orth(_t_times(a32, q))))
    u_b, s, vt = np.linalg.svd(_t_times(a32, q).T, full_matrices=False)
    return q @ u_b[:, :rank], s[:rank], vt[:rank]


def sv_rel_err(s: np.ndarray, s_ref: np.ndarray) -> float:
    """Largest singular value error over the largest singular value."""
    return float(np.max(np.abs(s.astype(np.float64) - s_ref)) / s_ref[0])


def orth_err(u: np.ndarray) -> float:
    """Largest entry of |U^T U - I|."""
    u64 = u.astype(np.float64)
    return float(np.max(np.abs(u64.T @ u64 - np.eye(u.shape[1]))))


def triplet_err(a32: np.ndarray, u, s, vt, s1: float) -> float:
    """Spectral norm of U^T A V - diag(s) over ``s1``: every triplet at
    once, against A itself. HMT's U = Q U_B and V = V_B give U^T A V =
    U_B^T B V_B = diag(s) exactly, whatever Q is, so what is left is
    rounding; a column of U or V paired with another triplet, or turned
    off its direction, puts a singular value off the diagonal. No gap
    between singular values enters."""
    av = _times(a32, vt.astype(np.float64).T)
    return float(np.linalg.norm(u.astype(np.float64).T @ av
                                - np.diag(s.astype(np.float64)), 2) / s1)


def subspace_err(x: np.ndarray, x_ref: np.ndarray) -> float:
    """Spectral norm of the part of ``x``'s columns outside the span of
    ``x_ref``'s (orthonormal) columns: the sine of the largest angle
    between the two spans when ``x`` is orthonormal too."""
    x64 = x.astype(np.float64)
    return float(np.linalg.norm(x64 - x_ref @ (x_ref.T @ x64), 2))


# -- the control: the reference at the next precision down ----------------


def hmt_lowp(a, omega32, rank: int, power_iter: int, dtype):
    """The reference put in the program's place, computed in ``dtype``
    (bfloat16 for this float32 configuration): A and every panel are
    rounded to ``dtype``, products accumulate in float32 and leave in
    ``dtype``, and each QR and the small SVD run in float32 on the
    rounded panel and are rounded again. Runs on the device."""
    al = a.astype(dtype)

    def dot(x, y):
        return jnp.dot(x.astype(dtype), y.astype(dtype),
                       preferred_element_type=dtype)

    def orth(x):
        return jnp.linalg.qr(x.astype(jnp.float32))[0].astype(dtype)

    q = orth(dot(al, omega32))
    for _ in range(power_iter):
        q = orth(dot(al, orth(dot(al.T, q))))
    u_b, s, vt = jnp.linalg.svd(dot(q.T, al).astype(jnp.float32),
                                full_matrices=False)
    return (dot(q, u_b[:, :rank]), s[:rank].astype(dtype),
            vt[:rank].astype(dtype))
