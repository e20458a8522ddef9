"""map2: generalized map over arrays with different shapes / alignments.

Parity with ``[U] spartan/expr/map2.py`` (SURVEY.md §2.3: kernel over
blocks of multiple differently-shaped arrays, yielding data into a new
array — used by dot / k-means / convnet-style ops). Two lowering paths
(SURVEY.md §7 hard part 1):

* :func:`map2` — the traced fast path: the kernel is jax-traceable and
  receives the *global* (sharded) arrays; GSPMD owner-computes each shard
  and inserts collectives only where the kernel's data flow demands them.
  This is semantically the reference's map2 (its per-tile blocking was a
  runtime detail), with XLA doing the blocking.
* :func:`shard_map2` — the explicit per-tile path: the kernel receives
  the *local block* of each input (the reference's actual kernel calling
  convention) under ``jax.shard_map``, for owner-computes algorithms that
  need block identity (e.g. partial-sum GEMM, per-tile argmin).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from ..array import tiling as tiling_mod
from ..array.tiling import Tiling
from ..parallel import mesh as mesh_mod
from .base import Expr, as_expr, eval_shape_of


class Map2Expr(Expr):
    """Traced kernel over whole (sharded) arrays."""

    def __init__(self, inputs: Sequence[Expr], fn: Callable,
                 fn_kw: Tuple[Tuple[str, Any], ...] = (),
                 out_tiling: Optional[Tiling] = None):
        self.inputs = tuple(inputs)
        self.fn = fn
        self.fn_kw = fn_kw
        from .base import fn_key

        out = eval_shape_of(lambda *xs: fn(*xs, **dict(fn_kw)),
                            *self.inputs,
                            cache_key=("map2", fn_key(fn), fn_kw))
        super().__init__(out.shape, out.dtype)
        self._map2_tiling = out_tiling

    def children(self) -> Tuple[Expr, ...]:
        return self.inputs

    def replace_children(self, new_children) -> "Map2Expr":
        return Map2Expr(new_children, self.fn, self.fn_kw,
                        self._map2_tiling)

    def _lower(self, env: Dict[int, Any]) -> Any:
        vals = [c.lower(env) for c in self.inputs]
        return self.fn(*vals, **dict(self.fn_kw))

    def _sig(self, ctx) -> Tuple:
        from .base import fn_key

        return (("map2", fn_key(self.fn), self.fn_kw)
                + tuple(ctx.of(c) for c in self.inputs))

    def _default_tiling(self) -> Tiling:
        if self._map2_tiling is not None:
            return self._map2_tiling
        return tiling_mod.default_tiling(self.shape)


def map2(arrays: Sequence[Any], fn: Callable,
         fn_kw: Optional[dict] = None,
         out_tiling: Optional[Tiling] = None) -> Map2Expr:
    inputs = tuple(as_expr(a) for a in arrays)
    kw = tuple(sorted((fn_kw or {}).items()))
    return Map2Expr(inputs, fn, kw, out_tiling)


class ShardMap2Expr(Expr):
    """Per-block kernel under shard_map — the reference's true per-tile
    kernel convention. ``in_specs[i]`` names how input i is blocked;
    ``out_spec`` how the kernel's outputs tile the result. Inputs are
    resharded to their specs before the kernel runs (owner-computes with
    explicit data placement, like smart tiling chose placements)."""

    def __init__(self, inputs: Sequence[Expr], fn: Callable,
                 in_tilings: Sequence[Tiling], out_tiling: Tiling,
                 out_shape: Sequence[int], out_dtype: Any):
        self.inputs = tuple(inputs)
        self.fn = fn
        self.in_tilings = tuple(in_tilings)
        self._out_tiling = out_tiling
        super().__init__(tuple(int(s) for s in out_shape), out_dtype)

    def children(self) -> Tuple[Expr, ...]:
        return self.inputs

    def replace_children(self, new_children) -> "ShardMap2Expr":
        return ShardMap2Expr(new_children, self.fn, self.in_tilings,
                             self._out_tiling, self._shape, self._dtype)

    def _lower(self, env: Dict[int, Any]) -> Any:
        from ..parallel import redistribute as redist_mod
        from jax import shard_map

        mesh = mesh_mod.get_mesh()
        vals = []
        for c, t in zip(self.inputs, self.in_tilings):
            v = c.lower(env)
            # constrain operand layout so the kernel sees the blocks the
            # caller named (resharding collective if needed) — via the
            # redistribution seam, planned when the child layout is
            # known and the model predicts an explicit win
            v = redist_mod.constrain(v, t, mesh, src=c.out_tiling())
            vals.append(v)
        mapped = shard_map(
            self.fn, mesh=mesh,
            in_specs=tuple(t.spec() for t in self.in_tilings),
            out_specs=self._out_tiling.spec())
        return mapped(*vals)

    def _sig(self, ctx) -> Tuple:
        from .base import fn_key

        return (("smap2", fn_key(self.fn),
                 tuple(t.axes for t in self.in_tilings),
                 self._out_tiling.axes)
                + tuple(ctx.of(c) for c in self.inputs))

    def _default_tiling(self) -> Tiling:
        return self._out_tiling


def shard_map2(arrays: Sequence[Any], fn: Callable,
               in_tilings: Sequence[Tiling], out_tiling: Tiling,
               out_shape: Sequence[int], out_dtype: Any = np.float32
               ) -> ShardMap2Expr:
    inputs = tuple(as_expr(a) for a in arrays)
    if len(inputs) != len(in_tilings):
        raise ValueError("need one tiling per input")
    return ShardMap2Expr(inputs, fn, in_tilings, out_tiling, out_shape,
                         out_dtype)
