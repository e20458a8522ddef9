"""Halo-exchange stencil: manual ppermute halos + blocked conv kernel.

GSPMD partitions a spatially-sharded convolution with generic halo
collectives it re-derives per program. Here the exchange is explicit:
under ``shard_map`` over the H-sharded tiling, each shard ppermutes
its boundary rows to its neighbours (un-received edges come back zero
— exactly SAME padding's zeros), concatenates the halos, and runs a
VALID convolution over its own rows. The inner conv is a blocked
Pallas kernel — grid over (image, H row-block), each step contracting
the ``KH x KW`` shifted input slices against the filter taps on the
MXU — with a local ``lax.conv`` fallback for shapes the kernel's
constraints exclude (the two-level fallback contract, docs/KERNELS.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod
from . import registry


def _same_pad(k: int) -> tuple:
    """XLA SAME padding split for stride 1: total k-1, low half first."""
    lo = (k - 1) // 2
    return lo, k - 1 - lo


def conv_block(x: jax.Array, w: jax.Array, hb: int,
               interpret: bool) -> jax.Array:
    """VALID conv of ``x`` (N, Hp, Wp, C) against ``w`` (KH, KW, C, O)
    via shifted-slice MXU contractions, grid over (image, H block)."""
    from jax.experimental import pallas as pl

    n, hp, wp, c = x.shape
    kh, kw, _, o = w.shape
    ho = hp - kh + 1
    wo = wp - kw + 1
    nh = -(-ho // hb)
    # pad rows so the last block's input reach stays in bounds
    need = nh * hb + kh - 1
    if need > hp:
        x = jnp.pad(x, ((0, 0), (0, need - hp), (0, 0), (0, 0)))

    def kernel(x_ref, w_ref, out_ref):
        hbi = pl.program_id(1)
        acc = jnp.zeros((hb * wo, o), jnp.float32)
        for dh in range(kh):
            for dw in range(kw):
                patch = x_ref[0, pl.ds(hbi * hb + dh, hb),
                              dw:dw + wo, :]
                acc += jax.lax.dot_general(
                    patch.reshape(hb * wo, c), w_ref[dh, dw],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        out_ref[0] = acc.reshape(hb, wo, o)

    out = pl.pallas_call(
        kernel,
        grid=(n, nh),
        in_specs=[
            pl.BlockSpec((1, x.shape[1], wp, c), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c, o), lambda i, j: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, wo, o), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, nh * hb, wo, o), jnp.float32),
        interpret=interpret,
        name="conv_block",
    )(x, w)
    return out[:, :ho]


def halo_stencil(x: jax.Array, w: jax.Array, tiling,
                 sel: registry.Selection, mesh=None) -> jax.Array:
    """SAME-padded stride-1 NHWC conv with the H axis mesh-sharded:
    manual ppermute halo exchange feeding the blocked kernel."""
    from jax import shard_map

    mesh = mesh or mesh_mod.get_mesh()
    axis = tiling.axes[1]
    p = int(mesh.shape[axis])
    kh, kw = int(w.shape[0]), int(w.shape[1])
    hlo, hhi = _same_pad(kh)
    wlo, whi = _same_pad(kw)
    hb = sel.schedule.block[0]
    interpret = sel.interpret
    x = redist_mod.constrain(x, tiling, mesh)

    def shard_fn(xl, wl):
        hs = xl.shape[1]
        parts = []
        if hlo:
            # my top halo = the previous shard's last hlo rows; shard 0
            # receives nothing -> zeros, which IS the SAME zero pad
            parts.append(jax.lax.ppermute(
                xl[:, hs - hlo:], axis,
                perm=[(i, i + 1) for i in range(p - 1)]))
        parts.append(xl)
        if hhi:
            parts.append(jax.lax.ppermute(
                xl[:, :hhi], axis,
                perm=[(i + 1, i) for i in range(p - 1)]))
        xpad = jnp.concatenate(parts, axis=1)
        xpad = jnp.pad(xpad, ((0, 0), (0, 0), (wlo, whi), (0, 0)))
        return conv_block(xpad, wl, hb, interpret)

    out_t = tiling.with_axis(2, None).with_axis(3, None)
    mapped = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(tiling.spec(), tiling_mod.replicated(4).spec()),
        out_specs=out_t.spec(), check_vma=False)
    return mapped(x, w.astype(jnp.float32))
