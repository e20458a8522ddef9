"""Work of one dense product per chip, from the configuration's shapes.

An n x n by n x n product takes 2 n^3 operations; on a mesh of c chips
each chip owns 1/c of them. Each chip reads its block row of A and
block column of B (n^2 / rows and n^2 / cols float32 entries, gathered
over the mesh) and writes its n^2 / c block of C.
"""

from __future__ import annotations


def product_per_chip(cfg: dict) -> dict:
    n = cfg["n"]
    rows, cols = cfg["mesh"]
    chips = rows * cols
    return {"flops": 2.0 * n ** 3 / chips,
            "bytes": 4.0 * (n * n / rows + n * n / cols + n * n / chips)}
