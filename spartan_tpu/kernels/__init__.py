"""Pallas kernel layer (docs/KERNELS.md).

The three kernels that won their slot on the chip, each with the cell
that shows it: the fused k-means pass (``kmeans.py``, shard_map-wrapped
over the row tiling) and the windowed SpMV gather and segment-sum
(``segment.py``). ``registry.select`` runs Pallas on TPU only;
``registry.derive`` gives a kernel its grid/block schedule from the
committed Tiling.

Pallas imports live ONLY under this package (lint rule 12).
"""
