"""Distributed matmul.

Parity with ``[U] spartan/expr/dot.py`` (SURVEY.md §3.3: shuffle-based
tile GEMM — per A-tile kernel fetches matching B tiles over RPC, partial
``np.dot`` products reducer-merged into the target; O(#tile-pairs)
point-to-point transfers). TPU-native lowering per BASELINE.json:5/8: the
operands get 2-D mesh shardings and ``jnp.dot`` under GSPMD emits
all-gather / reduce-scatter over ICI; the MXU does the FLOPs in one fused
kernel per shard. An explicit shard_map variant (:func:`dot_shardmap`,
psum-based) exists for A/B benchmarking against GSPMD.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..array import tiling as tiling_mod
from ..array.tiling import Tiling
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod
from ..parallel.mesh import AXIS_COL, AXIS_ROW
from .base import Expr, as_expr


class DotExpr(Expr):
    """a @ b for 1-D/2-D operands (NumPy dot semantics)."""

    def __init__(self, a: Expr, b: Expr, precision: Optional[str] = None):
        if a.ndim > 2 or b.ndim > 2:
            raise ValueError("dot supports 1-D and 2-D operands")
        if a.shape[-1] != (b.shape[0] if b.ndim else 1):
            raise ValueError(f"dot shape mismatch {a.shape} x {b.shape}")
        self.a = a
        self.b = b
        self.precision = precision
        # smart-tiling plan (tiling_cost): (output Tiling, strategy)
        # where strategy None = gathered contraction and a mesh axis =
        # contraction sharded there, merged by an output psum.
        # Recorded even when the chosen grid equals the default, so the
        # operand placement always reaches _lower without a redundant
        # output constraint.
        self._dot_plan = None
        if a.ndim == 1 and b.ndim == 1:
            shape: Tuple[int, ...] = ()
        elif a.ndim == 1:
            shape = (b.shape[1],)
        elif b.ndim == 1:
            shape = (a.shape[0],)
        else:
            shape = (a.shape[0], b.shape[1])
        super().__init__(shape, np.result_type(a.dtype, b.dtype))

    @property
    def _dot_strategy(self):
        """Contraction placement from the plan (None = gathered)."""
        return self._dot_plan[1] if self._dot_plan is not None else None

    def children(self) -> Tuple[Expr, ...]:
        return (self.a, self.b)

    def replace_children(self, new_children) -> "DotExpr":
        return DotExpr(new_children[0], new_children[1], self.precision)

    def _lower(self, env: Dict[int, Any]) -> Any:
        av = self.a.lower(env)
        bv = self.b.lower(env)
        mesh = mesh_mod.get_mesh()
        if (self.a.ndim == 2 and self.b.ndim == 2
                and self._dot_plan is not None):
            # Smart tiling chose this GEMM's plan: output grid
            # (m_r, m_c) with the contraction on mesh axis k (or
            # gathered when k is None) — A sharded (m_r, k),
            # B (k, m_c); for sharded k GSPMD inserts the merging
            # all-reduce. The cost model prices operand resharding and
            # the psum with exactly this rule (tiling_cost.py). Without
            # a plan (pass off) GSPMD negotiates from the operands' own
            # shardings — the reference's no-smart-tiling behavior
            # (tiles computed where they live).
            plan_t, k = self._dot_plan
            m_r, m_c = plan_t.axes[:2]
            # operand reshard edges go through the redistribution seam
            # (src = the committed child tiling the DP priced this
            # edge from): explicit collective schedules where the
            # planner predicts a win, with_sharding_constraint else
            av = redist_mod.constrain(av, Tiling((m_r, k)), mesh,
                                      src=self.a.out_tiling())
            bv = redist_mod.constrain(bv, Tiling((k, m_c)), mesh,
                                      src=self.b.out_tiling())
        return jnp.dot(av, bv, precision=self.precision)

    def _sig(self, ctx) -> Tuple:
        # the plan changes the lowering, so it must key the cache
        plan = (None if self._dot_plan is None
                else (self._dot_plan[0].axes, self._dot_plan[1]))
        return ("dot", self.precision, plan,
                ctx.of(self.a), ctx.of(self.b))

    def _default_tiling(self) -> Tiling:
        if self.ndim == 2:
            return tiling_mod.block(2)
        if self.ndim == 1:
            return tiling_mod.row(1)
        return tiling_mod.replicated(0)


def dot(a: Any, b: Any, precision: Optional[str] = None):
    """``a @ b``; masked operands route through the mask-aware GEMM
    (numpy.ma.dot semantics — see array/masked.py masked_dot)."""
    from ..array.masked import MaskedDistArray, masked_dot

    if isinstance(a, MaskedDistArray) or isinstance(b, MaskedDistArray):
        return masked_dot(a, b, precision=precision)
    return DotExpr(as_expr(a), as_expr(b), precision)


class DotShardMapExpr(Expr):
    """Explicit blocked GEMM under shard_map: A sharded (x, y) on
    (rows, contraction), B sharded (y,) on rows; each device computes its
    partial product on the MXU and ``psum`` over y reduces — the literal
    all-reduce lowering of the reference's reducer-merge (SURVEY.md §3.3).
    """

    def __init__(self, a: Expr, b: Expr):
        if a.ndim != 2 or b.ndim != 2:
            raise ValueError("dot_shardmap requires 2-D operands")
        if a.shape[1] != b.shape[0]:
            raise ValueError(f"shape mismatch {a.shape} x {b.shape}")
        self.a = a
        self.b = b
        super().__init__((a.shape[0], b.shape[1]),
                         np.result_type(a.dtype, b.dtype))

    def children(self) -> Tuple[Expr, ...]:
        return (self.a, self.b)

    def replace_children(self, new_children) -> "DotShardMapExpr":
        return DotShardMapExpr(new_children[0], new_children[1])

    def _lower(self, env: Dict[int, Any]) -> Any:
        from jax import shard_map

        mesh = mesh_mod.get_mesh()
        av = self.a.lower(env)
        bv = self.b.lower(env)
        a_t = tiling_mod.Tiling((AXIS_ROW, AXIS_COL))
        b_t = tiling_mod.Tiling((AXIS_COL, None))
        av = redist_mod.constrain(av, a_t, mesh,
                                  src=self.a.out_tiling())
        bv = redist_mod.constrain(bv, b_t, mesh,
                                  src=self.b.out_tiling())

        def kernel(ab, bb):
            partial = jnp.dot(ab, bb)
            return jax.lax.psum(partial, AXIS_COL)

        mapped = shard_map(kernel, mesh=mesh,
                           in_specs=(a_t.spec(), b_t.spec()),
                           out_specs=tiling_mod.row(2).spec())
        return mapped(av, bv)

    def _sig(self, ctx) -> Tuple:
        return ("dot_smap", ctx.of(self.a), ctx.of(self.b))

    def _default_tiling(self) -> Tiling:
        return tiling_mod.row(2)


def dot_shardmap(a: Any, b: Any) -> DotShardMapExpr:
    return DotShardMapExpr(as_expr(a), as_expr(b))
