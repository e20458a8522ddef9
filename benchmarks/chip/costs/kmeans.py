"""Work one Lloyd iteration needs, from the configuration's shapes.

Counted from the algorithm, not from what a kernel happens to do, so
the share stays valid when a later PR replaces the kernel:

- operations: the point-centre products (2 n k d) and the sums of the
  points into their centres, written as a one-hot product (2 n k d);
- bytes: one pass over the float32 points (n d 4), plus the centres
  read and written (2 k d 4).
"""

from __future__ import annotations


def lloyd_iteration(cfg: dict) -> dict:
    n, d, k = cfg["n"], cfg["d"], cfg["k"]
    return {"flops": 4.0 * n * k * d,
            "bytes": 4.0 * n * d + 2 * 4.0 * k * d}
