"""Plain dense product: the operand generator and the float64
reference, with the sampling that covers every shard of the product.

The operands are uniform in [0, 1), so every entry of the product is
positive and its relative error is well defined. Copied in spirit from
``chip_smoke.py`` (``config2`` / ``mesh_dot``): rows drawn from the
seed, compared in float64. Imports nothing of spartan_tpu.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def operands(key, n: int, sharding):
    """A and B, made on the device in one jitted call and laid out as
    ``sharding`` says."""
    def make(key):
        ka, kb = jax.random.split(key)
        return (jax.random.uniform(ka, (n, n), jnp.float32),
                jax.random.uniform(kb, (n, n), jnp.float32))

    return jax.jit(make, out_shardings=(sharding, sharding))(key)


def sample_rows(seed: int, n: int, rows: int, row_blocks: int
                ) -> np.ndarray:
    """``rows`` sorted row ids, the same number from each of the
    ``row_blocks`` row shards; every row spans all column shards, so
    the sample touches every shard of the product."""
    rng = np.random.default_rng([seed, 3])
    per = rows // row_blocks
    blk = n // row_blocks
    return np.sort(np.concatenate([
        i * blk + rng.choice(blk, per, replace=False)
        for i in range(row_blocks)]))


def rel_err(got_rows: np.ndarray, a_rows: np.ndarray,
            b: np.ndarray) -> float:
    """Largest relative error of any sampled entry against float64."""
    ref = a_rows.astype(np.float64) @ b.astype(np.float64)
    return float(np.max(np.abs(got_rows.astype(np.float64) - ref) / ref))


def dot_lowp(a, b, dtype):
    """The control: the product computed in ``dtype`` (bfloat16 for
    this float32 configuration), operands and result alike. The result
    leaves in ``dtype``: converted back to float32 inside the program,
    XLA:TPU folds the conversion into the product and keeps float32
    (my chip run, PR 22: the control then read as the program did)."""
    return jnp.dot(a.astype(dtype), b.astype(dtype),
                   preferred_element_type=dtype)
