"""Linear / ridge / logistic regression (config 4, BASELINE.json:10;
reference: ``[U] spartan/examples/`` linear_regression, ridge_regression,
logistic_regression).

Full-batch gradient descent, each step written as array expressions as
the reference writes it (``g = exp(dot(x, w))``, ``diff = x * (yp - y)``,
``sum(diff, axis=0)``): the planner's tall-skinny ``dot`` for X·w, an
elementwise map for the link and the residual, and an axis-0 reduction
for Xᵀr, which the planner sums across chips when X is row-tiled. The
products and sums are float32 (``precision="highest"``), so the result
does not hang on XLA's choice of unit for the product. With
``fused=True`` a whole run of ``num_iter`` steps is one ``st.loop``
program: one dispatch and one fetch.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

import spartan_tpu as st
from ..array import tiling as tiling_mod
from ..array.distarray import DistArray
from ..expr.base import Expr, ValExpr, as_expr
from ..expr.map2 import map2

_REPL1 = tiling_mod.replicated(1)
_PREC = "highest"  # float32 products at any width (the MXU takes bf16)


def _xt_times(x: Expr, r: Expr) -> Expr:
    """Xᵀr / n for a residual ``r`` of one entry per row of ``x``."""
    n = x.shape[0]
    return st.sum(x * r.reshape((n, 1)), axis=0) / n


def linear_grad(x: Expr, y: Expr, w: Expr) -> Expr:
    """d/dw of 0.5*||Xw - y||^2 / n  (lazy)."""
    return _xt_times(x, st.dot(x, w, precision=_PREC) - y)


def logistic_grad(x: Expr, y: Expr, w: Expr) -> Expr:
    """Gradient of mean logistic loss, y in {0,1}."""
    p = 1.0 / (1.0 + st.exp(-st.dot(x, w, precision=_PREC)))
    return _xt_times(x, p - y)


def _start(w0: Any, d: int) -> Expr:
    """The first iterate: zeros for ``None``, else ``w0`` of shape (d,)
    (an expression or array as it is, host data replicated)."""
    if w0 is None:
        return st.zeros((d,), np.float32, tiling=_REPL1)
    if isinstance(w0, (Expr, DistArray)):
        w = as_expr(w0)
    else:
        w = st.from_numpy(np.asarray(w0, np.float32), tiling=_REPL1)
    if tuple(w.shape) != (d,):
        raise ValueError(f"w0 has shape {tuple(w.shape)}, not ({d},)")
    return w


def _descend(step, w: Expr, num_iter: int, fused: bool) -> np.ndarray:
    if fused:
        # the whole run is ONE program (st.loop -> fori_loop): no
        # per-iteration dispatch (contrast SURVEY.md §3.4)
        return st.loop(num_iter, step, w).glom()
    for _ in range(num_iter):
        w = ValExpr(step(w).evaluate())
    return w.glom()


def linear_regression(x, y, num_iter: int = 10, lr: float = 1e-2,
                      ridge: float = 0.0, fused: bool = True,
                      w0: Optional[Any] = None) -> np.ndarray:
    x, y = as_expr(x), as_expr(y)

    def step(w: Expr) -> Expr:
        g = linear_grad(x, y, w)
        if ridge:
            g = g + ridge * w
        return w - lr * g

    return _descend(step, _start(w0, x.shape[1]), num_iter, fused)


def ridge_regression(x, y, num_iter: int = 10, lr: float = 1e-2,
                     alpha: float = 1.0,
                     w0: Optional[Any] = None) -> np.ndarray:
    return linear_regression(x, y, num_iter, lr, ridge=alpha, w0=w0)


def logistic_regression(x, y, num_iter: int = 10, lr: float = 1e-1,
                        fused: bool = True,
                        w0: Optional[Any] = None) -> np.ndarray:
    x, y = as_expr(x), as_expr(y)
    step = lambda w: w - lr * logistic_grad(x, y, w)  # noqa: E731
    return _descend(step, _start(w0, x.shape[1]), num_iter, fused)


def predict_logistic(x, w) -> Expr:
    x, w = as_expr(x), as_expr(w)
    return map2([x, w], lambda xv, wv: jax.nn.sigmoid(xv @ wv),
                out_tiling=tiling_mod.Tiling((x.out_tiling().axes[0],)))
