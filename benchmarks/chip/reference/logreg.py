"""Plain logistic regression: the data generator and the float64
reference.

Spartan's logistic regression example (Huang et al., USENIX ATC 2015;
``spartan/examples``) takes full-batch gradient steps from a random
start: ``p = sigmoid(X w)``, ``w -= lr * Xᵀ(p - y) / n``. Written here
from that description, so that a change to the program cannot move
this yardstick; imports nothing of spartan_tpu.

Data (as ``chip_smoke.config4``): X ~ N(0,1)^(n x d), w_true ~
N(0,1)^d, y = 1[X w_true + eps > 0] with eps ~ N(0,1), made on the
device from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 20  # rows at a time in the float64 steps (8 MB of margins)


def make_data(key, n: int, d: int, x_sharding, y_sharding):
    """X and y, made on the device in one jitted call and laid out as
    the shardings say."""
    def make(key):
        kx, kw, ke = jax.random.split(key, 3)
        # the barrier keeps X's draw out of the margin's fusion, where
        # XLA would hold a second X-sized buffer (1.28 GB at 10M x 32)
        x = jax.lax.optimization_barrier(
            jax.random.normal(kx, (n, d), jnp.float32))
        w_true = jax.random.normal(kw, (d,), jnp.float32)
        z = jnp.sum(x * w_true, axis=1) + jax.random.normal(
            ke, (n,), jnp.float32)
        return x, (z > 0).astype(jnp.float32)

    return jax.jit(make, out_shardings=(x_sharding, y_sharding))(key)


def job_start(seed: int, job: int, d: int) -> np.ndarray:
    """Job ``job``'s first iterate, U[0,1)^d in float32 (Spartan's
    ``rand`` start), drawn from the run's seed."""
    return np.random.default_rng([seed, 7, job]).random(d).astype(
        np.float32)


def descend(x32: np.ndarray, y: np.ndarray, w0: np.ndarray, iters: int,
            lr: float) -> np.ndarray:
    """``iters`` full-batch steps from ``w0`` in float64, each summed
    over blocks of ``BLOCK`` rows."""
    n = len(x32)
    x = np.asarray(x32, np.float64)
    y = np.asarray(y, np.float64)
    w = np.asarray(w0, np.float64)
    for _ in range(iters):
        g = np.zeros_like(w)
        for i in range(0, n, BLOCK):
            xb = x[i:i + BLOCK]
            g += (1.0 / (1.0 + np.exp(-(xb @ w))) - y[i:i + BLOCK]) @ xb
        w = w - lr * g / n
    return w


def w_rel_err(w: np.ndarray, w_ref: np.ndarray) -> float:
    """The largest coordinate error over the largest coordinate."""
    return float(np.max(np.abs(np.asarray(w, np.float64) - w_ref))
                 / np.max(np.abs(w_ref)))


def descend_lowp(x, y, w0, iters: int, lr: float, dtype):
    """The same steps with X, y, w and every product and sum in
    ``dtype`` (the control); returns float32."""
    n = x.shape[0]
    x, y = x.astype(dtype), y.astype(dtype)

    def step(_, w):
        p = jax.nn.sigmoid(x @ w)
        return w - (lr / n) * ((p - y) @ x)

    w = jax.lax.fori_loop(0, iters, step, jnp.asarray(w0, dtype))
    return w.astype(jnp.float32)
