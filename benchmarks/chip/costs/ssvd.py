"""Work of one randomized SVD, from the configuration's shapes.

A call of HMT Alg. 4.4 + 5.1 with q power iterations multiplies A (or
its transpose) by an l-column panel 2q + 2 times: the sketch, two
products per power iteration and the projection. Each such pass takes
2 m n l operations and reads A once (4 m n bytes of float32); the
panels (4 m l bytes) are a few percent of that and left out, as are
the QRs and the small SVD, which the algorithm needs but no roofline
bounds.
"""

from __future__ import annotations


def passes(cfg: dict) -> dict:
    """The passes over A one call makes, and each one's work."""
    m, n = cfg["m"], cfg["n"]
    l = min(cfg["rank"] + cfg["n_oversample"], m, n)
    return {"passes": 2 * cfg["n_power_iter"] + 2,
            "flops": 2.0 * m * n * l, "bytes": 4.0 * m * n}
