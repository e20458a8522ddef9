"""2-D stencil / pooling ops (``[U] spartan/expr/stencil.py`` [LOW] —
SURVEY.md §2.3: convnet stencil/maxpool in some reference versions).

TPU-native: the stencil is ``lax.conv_general_dilated`` (MXU) and pooling
is ``lax.reduce_window`` (VPU), traced into the consuming jit like any
map. :func:`stencil` lowers through a dedicated :class:`StencilExpr`
node; when the committed tiling shards the H axis, GSPMD partitions the
conv with its own halo transfers.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple, Union

import jax
import jax.numpy as jnp

from ..array.tiling import Tiling
from ..expr.base import Expr, as_expr, eval_shape_of
from ..expr.map2 import map2

Stride = Union[int, Tuple[int, int]]


def _pair(v: Stride) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


class StencilExpr(Expr):
    """NHWC convolution: the traced ``lax.conv`` (GSPMD halos), with
    batch/H shardings carried through to the output tiling."""

    def __init__(self, x: Expr, w: Expr, stride: Tuple[int, int],
                 padding: str):
        self.x = x
        self.w = w
        self.stride = tuple(int(s) for s in stride)
        self.padding = str(padding)
        out = eval_shape_of(
            lambda xv, wv: self._conv(xv, wv), x, w,
            cache_key=("stencil", self.stride, self.padding))
        super().__init__(out.shape, out.dtype)

    def children(self) -> Tuple[Expr, ...]:
        return (self.x, self.w)

    def replace_children(self, new_children) -> "StencilExpr":
        return StencilExpr(new_children[0], new_children[1],
                           self.stride, self.padding)

    def _conv(self, xv: Any, wv: Any) -> Any:
        return jax.lax.conv_general_dilated(
            xv, wv, window_strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def _lower(self, env: Dict[int, Any]) -> Any:
        return self._conv(self.x.lower(env), self.w.lower(env))

    def _sig(self, ctx) -> Tuple:
        return ("stencil", self.stride, self.padding,
                ctx.of(self.x), ctx.of(self.w))

    def _default_tiling(self) -> Tiling:
        # batch/H shardings carry through (GSPMD's halo exchange keeps
        # them); the W window and output channels stay whole. The plan
        # sanitizes H away when the output height stops dividing.
        tx = self.x.out_tiling()
        return Tiling((tx.axes[0], tx.axes[1], None, None))


def stencil(images, filters, stride: Stride = 1,
            padding: str = "SAME") -> Expr:
    """images (N, H, W, C), filters (KH, KW, C, O) -> (N, H', W', O)."""
    return StencilExpr(as_expr(images), as_expr(filters),
                       _pair(stride), padding)


def maxpool(images, window: Stride = 2, stride: Stride = None,
            padding: str = "VALID") -> Expr:
    """images (N, H, W, C) max-pooled over spatial dims."""
    images = as_expr(images)
    w = _pair(window)
    s = _pair(stride) if stride is not None else w

    def kern(x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            window_dimensions=(1,) + w + (1,),
            window_strides=(1,) + s + (1,),
            padding=padding)

    return map2([images], kern)


def avgpool(images, window: Stride = 2, stride: Stride = None,
            padding: str = "VALID") -> Expr:
    images = as_expr(images)
    w = _pair(window)
    s = _pair(stride) if stride is not None else w
    denom = float(w[0] * w[1])

    def kern(x):
        summed = jax.lax.reduce_window(
            x, 0.0, jax.lax.add,
            window_dimensions=(1,) + w + (1,),
            window_strides=(1,) + s + (1,),
            padding=padding)
        return summed / denom

    return map2([images], kern)
