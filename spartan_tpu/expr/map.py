"""Elementwise map expressions — the workhorse (SURVEY.md §2.3: ``[U]
spartan/expr/map.py``; BASELINE.json:7 config 1 is "element-wise map +
global sum").

The reference picked the largest input and ran a fused NumPy kernel per
tile, fetching matching extents of other inputs over RPC. Here the whole
map (with broadcasting) is traced into the enclosing jit; GSPMD aligns the
operand shardings (resharding the small ones — the broadcast wrapper of
SURVEY.md §2.6) and XLA fuses the elementwise chain into the surrounding
computation.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..array import tiling as tiling_mod
from ..array.tiling import Tiling
from . import local as local_mod
from .base import Expr, ScalarExpr, as_expr, eval_shape_of
from .local import LocalCall, LocalExpr, LocalInput, LocalUfunc


class MapExpr(Expr):
    """Fused elementwise expression over broadcast-aligned inputs."""

    def __init__(self, inputs: Sequence[Expr], op: LocalExpr):
        self.inputs: Tuple[Expr, ...] = tuple(inputs)
        self.op = op
        out = eval_shape_of(lambda *xs: op.emit(xs), *self.inputs,
                            cache_key=("map", op.key()))
        super().__init__(out.shape, out.dtype)

    def children(self) -> Tuple[Expr, ...]:
        return self.inputs

    def replace_children(self, new_children: Tuple[Expr, ...]) -> "MapExpr":
        return MapExpr(new_children, self.op)

    def _lower(self, env: Dict[int, Any]) -> Any:
        vals = [c.lower(env) for c in self.inputs]
        return self.op.emit(vals)

    def _sig(self, ctx) -> Tuple:
        return (("map", self.op.key())
                + tuple(ctx.of(c) for c in self.inputs))

    def _default_tiling(self) -> Tiling:
        # the largest same-shaped input donates its tiling (the reference
        # evaluated on the owner of the largest input's tiles)
        best: Optional[Tiling] = None
        for c in self.inputs:
            if c.shape == self.shape:
                t = c.out_tiling()
                if t.sharded_axes():
                    return t
                best = best or t
        if best is not None:
            return best
        return tiling_mod.default_tiling(self.shape)


def build_binop(name: str, a: Any, b: Any, reverse: bool = False) -> MapExpr:
    a = as_expr(a)
    b = as_expr(b)
    if reverse:
        a, b = b, a
    return MapExpr((a, b), LocalUfunc(name, (LocalInput(0), LocalInput(1))))


def build_unop(name: str, a: Any) -> MapExpr:
    return MapExpr((as_expr(a),), LocalUfunc(name, (LocalInput(0),)))


def map(fn: Callable, *args: Any, fn_kw: Optional[dict] = None):
    """User map: ``fn`` is a jax-traceable function applied elementwise /
    blockwise to the broadcast-aligned inputs (the reference shipped it as
    a pickled closure per tile; here it is traced into the jit).

    Masked operands (MaskedDistArray) propagate: ``fn`` runs on the
    data and the result's mask is the OR of the operands' masks
    (numpy.ma's ufunc rule), broadcast to the output shape."""
    from ..array import masked as masked_mod

    if any(isinstance(a, masked_mod.MaskedDistArray) for a in args):
        import jax.numpy as jnp

        out = map(fn, *(masked_mod._data_of(a) for a in args),
                  fn_kw=fn_kw)
        masks = [a.mask for a in args
                 if isinstance(a, masked_mod.MaskedDistArray)]
        mask = masks[0]
        for m in masks[1:]:
            mask = mask | m
        if mask.shape != out.shape:
            mask = map(lambda o, m: jnp.broadcast_to(
                m.astype(bool), o.shape), out, mask)
        return masked_mod.MaskedDistArray(out, mask)
    inputs = tuple(as_expr(a) for a in args)
    kw = tuple(sorted((fn_kw or {}).items()))
    op = LocalCall(fn, tuple(LocalInput(i) for i in range(len(inputs))), kw)
    return MapExpr(inputs, op)


class MapWithLocationExpr(Expr):
    """Map where the kernel also receives the block's global offset
    (SURVEY.md §2.3 ``map_with_location``: index-dependent ops).

    ``fn(block, ul)`` runs per shard under shard_map; ``ul`` is the global
    upper-left coordinate of the shard (a tuple of traced scalars computed
    from mesh axis indices) — the TPU-native replacement for handing the
    kernel its TileExtent.
    """

    def __init__(self, input: Expr, fn: Callable,
                 fn_kw: Tuple[Tuple[str, Any], ...] = ()):
        self.input = input
        self.fn = fn
        self.fn_kw = fn_kw
        # fn must preserve the block shape; dtype may change
        out = eval_shape_of(
            lambda x: fn(x, tuple(0 for _ in input.shape),
                         **dict(fn_kw)), input)
        if out.shape != input.shape:
            raise ValueError(
                "map_with_location kernels must preserve shape; got "
                f"{out.shape} from {input.shape}")
        super().__init__(out.shape, out.dtype)

    def children(self) -> Tuple[Expr, ...]:
        return (self.input,)

    def replace_children(self, new_children: Tuple[Expr, ...]
                         ) -> "MapWithLocationExpr":
        return MapWithLocationExpr(new_children[0], self.fn, self.fn_kw)

    def _lower(self, env: Dict[int, Any]) -> Any:
        import jax
        from jax import shard_map

        from ..parallel import mesh as mesh_mod

        x = self.input.lower(env)
        mesh = mesh_mod.get_mesh()
        t = self.input.out_tiling()
        if not t.divisible(self.shape, mesh):
            # replicated / uneven fallback: single logical block at (0,..)
            return self.fn(x, tuple(0 for _ in self.shape),
                           **dict(self.fn_kw))
        tiles = t.tiles_per_dim(mesh)
        shard_shape = tuple(d // n for d, n in zip(self.shape, tiles))
        axes = t.axes

        def kernel(block):
            ul = []
            for d in range(len(axes)):
                a = axes[d]
                if a is None:
                    ul.append(0)
                else:
                    idx = jax.lax.axis_index(a)
                    ul.append(idx * shard_shape[d])
            return self.fn(block, tuple(ul), **dict(self.fn_kw))

        mapped = shard_map(kernel, mesh=mesh, in_specs=(t.spec(),),
                           out_specs=t.spec())
        return mapped(x)

    def _sig(self, ctx) -> Tuple:
        from .base import fn_key

        return ("maploc", fn_key(self.fn), self.fn_kw,
                self.input.out_tiling().axes, ctx.of(self.input))

    def _default_tiling(self) -> Tiling:
        return self.input.out_tiling()


def map_with_location(array: Any, fn: Callable,
                      fn_kw: Optional[dict] = None) -> MapWithLocationExpr:
    return MapWithLocationExpr(as_expr(array), fn,
                               tuple(sorted((fn_kw or {}).items())))
