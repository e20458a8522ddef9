"""Bytes one power iteration needs, from the configuration's shapes.

The iteration is a sparse product: every entry's weight (float32) and
column id (int32) is read once, the source rank is gathered for every
entry (float32), and the rank vector is read and written once. Its
operations (2 per entry) are far below any peak, so bytes bound it.
"""

from __future__ import annotations


def power_iteration(cfg: dict) -> dict:
    n = 1 << cfg["scale"]
    e = cfg["edge_factor"] * n * (2 if cfg["undirected"] else 1)
    return {"flops": 2.0 * e,
            "bytes": e * (4.0 + 4.0 + 4.0) + 2 * 4.0 * n}
