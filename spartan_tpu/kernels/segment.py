"""Windowed segment kernels for SpMV on one chip (ops/segment.py's
SegmentPlan lays out their host-planned stream):

* :func:`windowed_segsum` — the windowed sorted-segment merge.
* :func:`windowed_gather` — its mirror image for an SpMV's ``x[cols]``:
  ``x`` resident in VMEM, read per 128-entry group through one-hot
  products on the MXU over the group's column window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import registry


def windowed_segsum(vals: jax.Array, ids2d: jax.Array, wb: jax.Array,
                    *, rows_pad: int, nsteps: int, outblk: int,
                    sub: int) -> jax.Array:
    """SegmentPlan's windowed sorted-segment kernel (ops/segment.py
    docstring has the algorithm); always Pallas — interpret mode off
    TPU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nout = rows_pad // outblk
    vals2d = vals.astype(jnp.float32).reshape(-1, 128)
    # flush runs on dedicated trailing grid steps AFTER all accumulation
    # steps: every output block is flushed (including a trailing partial
    # one — rows_pad is padded to outblk), and no entry can arrive after
    # its block was written out, regardless of id skew
    grid = nsteps + nout

    def kernel(wb_ref, ids_ref, vals_ref, out_ref, scratch):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _init():
            scratch[:] = jnp.zeros_like(scratch)

        @pl.when(b < nsteps)
        def _accumulate():
            lane_iota = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
            sub_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
            for j in range(sub):
                acc = jnp.zeros((8, 128), jnp.float32)
                for s in range(8):
                    ids_s = ids_ref[j * 8 + s, :]
                    lo = ids_s & 127
                    hi = ids_s >> 7
                    # entries live on lanes in both one-hots: no relayouts
                    a = (jnp.broadcast_to(lo[None, :], (128, 128))
                         == lane_iota).astype(jnp.float32)   # (lane, entry)
                    bmat = (jnp.broadcast_to(hi[None, :], (8, 128))
                            == sub_iota).astype(jnp.float32)  # (subrow, e)
                    bmat = bmat * vals_ref[j * 8 + s, :][None, :]
                    acc = acc + jax.lax.dot_general(
                        bmat, a, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
                w = wb_ref[b * sub + j]
                scratch[pl.ds(w * 8, 8), :] += acc

        @pl.when(b >= nsteps)
        def _flush():
            k = jnp.maximum(b - nsteps, 0)
            out_ref[:] = scratch[pl.ds(k * outblk, outblk), :]

    def in_map(b, wb_ref):
        return (jnp.minimum(b, nsteps - 1), 0)

    f = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((sub * 8, 128), in_map),
                pl.BlockSpec((sub * 8, 128), in_map),
            ],
            out_specs=pl.BlockSpec(
                (outblk, 128),
                lambda b, wb_ref: (jnp.maximum(b - nsteps, 0), 0)),
            scratch_shapes=[pltpu.VMEM((rows_pad, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows_pad, 128), jnp.float32),
        interpret=registry.interpret_mode(),
        name="windowed_segsum",
    )
    return f(wb, ids2d, vals2d)


# windowed_gather: x is read in column windows of CW = 128 sublane rows
# x 128 lanes; a group of 128 entries whose columns share one window
# reads it through one-hot products on the MXU
CW = 128 * 128
_PARTS = 3   # bf16 parts: hi + mid + lo == x exactly in f32
_GATHER_VMEM = 64 << 20
# x's parts take 6 bytes a column of the VMEM limit: 8M columns take
# 48 MiB (10.5M compile for a described v5e, 10.6M run out of VMEM)
GATHER_MAX_COLS = 8 << 20


def _gather_parts(x: jax.Array) -> jax.Array:
    """``x`` padded to whole column windows, each laid out (lane,
    subrow), as three bf16 parts (3, windows, 128, 128) whose f32 sum is
    ``x`` bit for bit: each part keeps the top 8 significant bits of
    what the parts before it left. The bits are cut by masking, not by
    rounding: XLA may fold an f32 -> bf16 -> f32 round trip away (excess
    precision), which would leave the lower parts zero."""
    n = x.shape[0]
    nw = max(-(-n // CW), 1)
    rest = jnp.pad(x.astype(jnp.float32), (0, nw * CW - n))
    rest = rest.reshape(nw, 128, 128).transpose(0, 2, 1)
    parts = []
    for _ in range(_PARTS):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        parts.append(top.astype(jnp.bfloat16))   # exact: 8 bits
        rest = rest - top                         # exact: same binade
    return jnp.stack(parts)


def windowed_gather(x: jax.Array, lcols: jax.Array, gwin: jax.Array,
                    pdata: jax.Array) -> jax.Array:
    """``pdata * x[gwin * CW + lcols]`` over a plan-ordered stream.

    ``lcols`` (G, 128) int32 holds each entry's column within its
    group's window, ``gwin`` (G // R, 1, R) int32 each group's window,
    ``pdata`` (G * 128,) f32 the entry values. Per group: the one-hot
    of the column's subrow (``lc >> 7``) picks, in one MXU product per
    bf16 part, the 128 lanes of that subrow for every entry, and a
    select on the column's lane (``lc & 127``) plus a sublane sum keeps
    the one value. The one-hot side is exact in bf16 and the three
    parts sum to ``x`` exactly, so the gather is bit for bit ``x[cols]``
    for finite ``x``; a non-finite value spreads NaN over its window
    (0 * inf), as it does through ``windowed_segsum``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    parts = _gather_parts(x)
    g = lcols.shape[0]
    r = gwin.shape[-1]

    def kernel(win_ref, ids_ref, d_ref, x_ref, out_ref):
        sub = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        row8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)

        def body(i, carry):
            base = pl.multiple_of(i * 8, 8)
            ids8 = ids_ref[pl.ds(base, 8), :]
            acc = jnp.zeros((8, 128), jnp.float32)
            for j in range(8):
                c = win_ref[0, base + j]
                ids = ids8[j:j + 1, :]
                hi = ((ids >> 7) == sub).astype(jnp.bfloat16)
                p = jnp.zeros((128, 128), jnp.float32)
                for k in reversed(range(_PARTS)):   # lo + mid exact first
                    p = p + jnp.dot(x_ref[k, c], hi,
                                    preferred_element_type=jnp.float32)
                v = jnp.sum(jnp.where((ids & 127) == sub, p, 0.0),
                            axis=0, keepdims=True)
                acc = jnp.where(row8 == j, v, acc)
            out_ref[pl.ds(base, 8), :] = acc * d_ref[pl.ds(base, 8), :]
            return carry

        # unrolled (Mosaic unrolls all or nothing), the products of one
        # group overlap the vector work of the others: 20.4 against
        # 24.7 ms at 299K groups of 128 a step on v5e
        jax.lax.fori_loop(0, r // 8, body, 0, unroll=True)

    f = pl.pallas_call(
        kernel,
        grid=(g // r,),
        in_specs=[
            pl.BlockSpec((None, 1, r), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((r, 128), lambda b: (b, 0)),
            pl.BlockSpec((r, 128), lambda b: (b, 0)),
            # every window resident in VMEM for the whole grid: up to
            # GATHER_MAX_COLS columns
            pl.BlockSpec(parts.shape, lambda b: (0, 0, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec((r, 128), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_GATHER_VMEM),
        interpret=registry.interpret_mode(),
        name="windowed_gather",
    )
    return f(gwin, lcols, pdata.astype(jnp.float32).reshape(g, 128), parts)
