"""Work one full-batch logistic-regression step needs, from the
configuration's shapes.

Counted from the algorithm, not from the program that runs it, so the
share stays valid when a one-pass kernel replaces today's two passes
over X: the margin X·w, the link and Xᵀr can all come from one read of
each block of rows.

- operations: X·w (2 n d) and Xᵀr (2 n d);
- bytes: one pass over the float32 X and y (4 n d + 4 n); w and the
  gradient (d floats) are left out.
"""

from __future__ import annotations


def iteration(cfg: dict) -> dict:
    n, d = cfg["n"], cfg["d"]
    return {"flops": 4.0 * n * d, "bytes": 4.0 * n * d + 4.0 * n}
