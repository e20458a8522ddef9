"""Per-shard top-k selection kernel (distributed_topk's local stage).

XLA's ``lax.top_k`` on TPU lowers through a full sort of the operand;
this kernel streams the shard through VMEM once and keeps a running
sorted candidate row instead. Per grid step it merges one block into
the running best-k by iterated extraction: take the max of
``block ∪ best`` (ties toward the LOWEST index — ``lax.top_k``'s
documented tie-break, which the sample-sort sentinel invariant in
ops/sort.py depends on), emit it into the next candidate slot, remove
exactly that element, repeat k times. Winners come out sorted
best-first by construction.

Keys are the caller's RANKING keys (ops/sort.py flips them for
smallest-k and masks ragged tails with the sentinel before calling);
the index payload is the LOCAL slot index, so the caller's gather /
global-offset bookkeeping is identical to the lax.top_k path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import registry

_IDX_INF = np.int32(2 ** 30)  # index sentinel for lifted padding slots


def shard_topk(key: jax.Array, k: int, sentinel,
               sel: registry.Selection) -> tuple:
    """(keys (k,), local indices (k,) i32) of one shard's top-k.

    ``key`` is 1-D; slots the caller already invalidated carry
    ``sentinel`` (they keep their real index — the tail-position
    invariant orders them behind every valid tie). Rows are lifted to
    ``(rows, 128)`` and padded per the derived schedule; lifted
    padding carries ``(sentinel, _IDX_INF)`` and can never displace a
    real candidate."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = key.shape[0]
    dt = key.dtype
    sched = sel.schedule
    brows = sched.block[0]
    rows = sched.padded[0]
    grid = sched.grid[0]
    total = rows * 128
    kpad = 128

    keyp = jnp.full((total,), sentinel, dt).at[:m].set(key)
    idxp = jnp.where(jnp.arange(total, dtype=jnp.int32) < m,
                     jnp.arange(total, dtype=jnp.int32), _IDX_INF)
    key2 = keyp.reshape(rows, 128)
    idx2 = idxp.reshape(rows, 128)

    def kernel(k_ref, i_ref, outv_ref, outi_ref, work, widx, newv, newi):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _init():
            outv_ref[:] = jnp.full_like(outv_ref, sentinel)
            outi_ref[:] = jnp.full_like(outi_ref, _IDX_INF)

        work[:] = k_ref[:]
        widx[:] = i_ref[:]
        newv[:] = jnp.full_like(newv, sentinel)
        newi[:] = jnp.full_like(newi, _IDX_INF)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, kpad), 1)

        def extract(j, _):
            m1 = jnp.maximum(jnp.max(work[:]), jnp.max(outv_ref[:]))
            c1 = jnp.min(jnp.where(work[:] == m1, widx[:], _IDX_INF))
            c2 = jnp.min(jnp.where(outv_ref[:] == m1, outi_ref[:],
                                   _IDX_INF))
            mi = jnp.minimum(c1, c2)
            newv[:] = jnp.where(lane == j, m1, newv[:])
            newi[:] = jnp.where(lane == j, mi, newi[:])
            hit_w = (work[:] == m1) & (widx[:] == mi)
            work[:] = jnp.where(hit_w, sentinel, work[:])
            widx[:] = jnp.where(hit_w, _IDX_INF, widx[:])
            hit_b = (outv_ref[:] == m1) & (outi_ref[:] == mi)
            outv_ref[:] = jnp.where(hit_b, sentinel, outv_ref[:])
            outi_ref[:] = jnp.where(hit_b, _IDX_INF, outi_ref[:])
            return 0

        jax.lax.fori_loop(0, k, extract, 0)
        outv_ref[:] = newv[:]
        outi_ref[:] = newi[:]

    outv, outi = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((brows, 128), lambda b: (b, 0)),
            pl.BlockSpec((brows, 128), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kpad), lambda b: (0, 0)),
            pl.BlockSpec((1, kpad), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, kpad), dt),
            jax.ShapeDtypeStruct((1, kpad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((brows, 128), dt),
            pltpu.VMEM((brows, 128), jnp.int32),
            pltpu.VMEM((1, kpad), dt),
            pltpu.VMEM((1, kpad), jnp.int32),
        ],
        interpret=sel.interpret,
        name="shard_topk",
    )(key2, idx2)
    # clamp the index payload so downstream gathers stay in bounds even
    # for sentinel candidates (they never win a slot)
    return outv[0, :k], jnp.minimum(outi[0, :k], m - 1)
