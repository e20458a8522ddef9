"""Sample-sort partition exchange: the send-buffer pack kernel.

The sample sort's padded transport (ops/sort.py) builds a ``(p, m)``
send buffer where bucket run j — a contiguous slice
``xs[starts[j] : starts[j] + counts[j]]`` of the locally-sorted shard
— lands in row j at positions ``[0, counts[j])``. The seed lowered
that as an XLA scatter (``.at[dst, pos].set``), the slowest lowering
class on TPU. Because runs are contiguous, the scatter is exactly a
batch of dynamic slices; this kernel does it with one VMEM-resident
pass per destination:

* the sublane part of each dynamic start is a ``pl.ds`` row slice;
* the lane part is a one-hot permutation matmul on the MXU — exact
  for EVERY 32-bit pattern (NaN payloads included) because the value
  is split into two 16-bit halves, rolled as exact f32 integers, and
  reassembled (a float matmul on raw bits would launder NaNs). The
  bits travel as int32: Mosaic has no uint32 <-> float32 cast.

Validity needs no kernel: ``t < counts[j]`` is an iota compare.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import registry

LANE = registry.LANE


def partition_pack(xs: jax.Array, starts: jax.Array,
                   counts: jax.Array, p: int,
                   sel: registry.Selection) -> jax.Array:
    """(p, m) send buffer from one shard's sorted stream ``xs`` (m,).

    ``starts``/``counts`` (p,) i32 name each destination's contiguous
    run. Slots past a run's count are zeroed (the validity channel —
    built outside — governs them). Any 4-byte dtype, bit-exact."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = xs.shape[0]
    dt = xs.dtype
    mr = -(-m // LANE)                       # destination row blocks
    src_rows = -(-m // LANE) + mr + 1        # slice reach: start + m
    xs_i = jax.lax.bitcast_convert_type(
        jnp.zeros((src_rows * LANE,), dt).at[:m].set(xs), jnp.int32)
    xs2 = xs_i.reshape(src_rows, LANE)

    def kernel(s_ref, c_ref, x_ref, out_ref):
        j = pl.program_id(0)
        s = s_ref[j]
        a = s // LANE
        b = s % LANE
        x = x_ref[pl.ds(a, mr + 1), :]
        hi = ((x >> 16) & 0xFFFF).astype(jnp.float32)
        lo = (x & 0xFFFF).astype(jnp.float32)
        # P[c, l] = 1 iff c == (b + l) % 128: Y = X @ P rolls lanes
        # left by b; both halves are < 2**16, exact in f32 at HIGHEST
        row = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
        perm = ((col + b) % LANE == row).astype(jnp.float32)
        yhi = jax.lax.dot_general(
            hi, perm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        ylo = jax.lax.dot_general(
            lo, perm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        y = (yhi.astype(jnp.int32) << 16) | ylo.astype(jnp.int32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (mr, LANE), 1)
        # element (r, l) of row j is xs[s + r*128 + l]: lane l came
        # from source row a+r when b+l < 128, else a+r+1 (the carry)
        yv = jnp.where(b + lane < LANE, y[:mr, :], y[1:mr + 1, :])
        t = (jax.lax.broadcasted_iota(jnp.int32, (mr, LANE), 0) * LANE
             + lane)
        out_ref[:] = jnp.where(t < c_ref[j], yv, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(p,),
            in_specs=[
                pl.BlockSpec((src_rows, LANE), lambda j, s, c: (0, 0)),
            ],
            out_specs=pl.BlockSpec((mr, LANE), lambda j, s, c: (j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((p * mr, LANE), jnp.int32),
        interpret=sel.interpret,
        name="partition_pack",
    )(starts.astype(jnp.int32), counts.astype(jnp.int32), xs2)
    out = jax.lax.bitcast_convert_type(out.reshape(p, mr * LANE), dt)
    return out[:, :m]
