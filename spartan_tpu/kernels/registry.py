"""Kernel registry + selection policy for the Pallas kernel layer.

The one place that decides, per op / shape / tiling / platform, whether
an op lowers through a shard_map-wrapped Pallas TPU kernel or through
its portable XLA formulation (TileLoom's planning stance in PAPERS.md:
the kernel's grid/block schedule is *derived from the tiling the DP
already chose*, never re-derived per kernel).

Two pieces:

* :func:`derive` — the tiling->grid rule. The committed ``Tiling`` of
  the op's operand names the per-chip shard; the block shape is that
  shard quantized to TPU lane/sublane tiles (last dim to 128 lanes,
  leading rows to the dtype's sublane quantum), and the grid is the
  ceil-division of the shard by the block. One function, property-
  tested over the whole tiling vocabulary (tests/test_kernels.py).
* :func:`select` — the policy: Pallas on TPU only, the XLA lowering
  elsewhere. Per-op constraint checks fall back to XLA with the reason
  recorded.

The platform alone decides the backend, and the persistent plan
store's fingerprint already carries the platform, so no cache key
needs a kernel component.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod

LANE = 128
# min sublane tile by itemsize (f32/i32: 8, bf16: 16, i8/fp8: 32)
_SUBLANE = {8: 8, 4: 8, 2: 16, 1: 32}
# conservative per-kernel VMEM budget (16 MB parts; leave headroom for
# double buffering and the compiler's own scratch)
VMEM_BUDGET = 8 * 1024 * 1024


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def mode() -> str:
    """Resolved backend family: ``pallas`` on TPU, else ``gspmd``."""
    return "pallas" if _platform() == "tpu" else "gspmd"


def interpret_mode() -> bool:
    """Pallas interpret mode: required anywhere but a real TPU."""
    return _platform() != "tpu"


def sublane(dtype: Any) -> int:
    return _SUBLANE.get(np.dtype(dtype).itemsize, 8)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A derived grid/block schedule over ONE shard of the operand.

    ``shard`` is the per-chip shape the committed Tiling induces
    (1-D shards are lifted to ``(rows, 128)`` lane-major); ``block``
    is the per-grid-step tile (lane/sublane quantized); ``padded`` is
    the shard shape after quantization padding — kernels mask the
    padding, they never double-count it; ``grid`` is the ceil-division
    of the padded shard's rows by the block rows."""

    grid: Tuple[int, ...]
    block: Tuple[int, ...]
    shard: Tuple[int, ...]
    padded: Tuple[int, ...]
    lifted: bool


def derive(shape: Tuple[int, ...], tiling: tiling_mod.Tiling,
           dtype: Any, mesh=None, rows_per_block: int = 1024
           ) -> Tuple[Optional[Schedule], str]:
    """Tiling->grid derivation (the TileLoom move): block shape =
    per-chip shard shape quantized to TPU lane/sublane tiles, grid =
    blocks covering the shard exactly. Returns ``(None, reason)`` for
    shards the rule cannot cover (indivisible tilings, empty dims)."""
    mesh = mesh or mesh_mod.get_mesh()
    shape = tuple(int(s) for s in shape)
    if not shape or any(s == 0 for s in shape):
        return None, "empty operand"
    tiles = tiling.tiles_per_dim(mesh)
    for d, t in zip(shape, tiles):
        if t > 1 and d % t:
            return None, (f"tiling {tiling.axes} does not divide shape "
                          f"{shape} over mesh {dict(mesh.shape)}")
    shard = tuple(d // t for d, t in zip(shape, tiles))
    lifted = False
    if len(shard) == 1:
        shard = (-(-shard[0] // LANE), LANE)
        lifted = True
    q = sublane(dtype)
    rows = shard[0]
    brows = min(int(rows_per_block), rows)
    brows = -(-brows // q) * q
    grid = -(-rows // brows)
    last = -(-shard[-1] // LANE) * LANE
    block = (brows,) + shard[1:-1] + (last,)
    padded = (grid * brows,) + shard[1:-1] + (last,)
    return Schedule((grid,), block, shard, padded, lifted), ""


@dataclasses.dataclass(frozen=True)
class Selection:
    """One selection decision: which backend lowers this op here."""

    op: str
    backend: str                     # "pallas" | "gspmd"
    reason: str
    schedule: Optional[Schedule] = None

    @property
    def pallas(self) -> bool:
        return self.backend == "pallas"


def _fallback(op: str, reason: str) -> Selection:
    return Selection(op, "gspmd", reason)


def _sel_kmeans(shape, dtype, tiling, mesh, params) -> Selection:
    op = "kmeans"
    n, d = shape
    k = int(params["k"])
    if np.dtype(dtype) != np.float32:
        return _fallback(op, f"dtype {np.dtype(dtype)} != float32")
    if d % LANE:
        return _fallback(op, f"d {d} not a multiple of 128")
    if k > LANE:
        return _fallback(op, f"k {k} > 128 padded centers")
    p = max(int(mesh.shape.get(tiling_mod.AXIS_ROW, 1)), 1)
    if n % p:
        return _fallback(op, f"n {n} not divisible by {p} shards")
    block = int(params.get("block", 1024))
    if (n // p) % block:
        return _fallback(op, f"shard rows {n // p} not a multiple of "
                             f"the {block} point block")
    sched, why = derive(shape, tiling, dtype, mesh, rows_per_block=block)
    if sched is None:
        return _fallback(op, why)
    need = 4 * (block * d + 2 * LANE * d + 2 * LANE)
    if need > VMEM_BUDGET:
        return _fallback(op, f"point block working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched)


_CHECKS = {
    "kmeans": _sel_kmeans,
}


def select(op: str, shape, dtype, tiling: Optional[tiling_mod.Tiling],
           mesh=None, **params) -> Selection:
    """The per-op backend decision (pure: platform + static
    shapes/tilings only). A kernel that cannot cover the shard falls
    back with the reason recorded."""
    if op not in _CHECKS:
        raise KeyError(f"unknown kernel op {op!r}; known: "
                       f"{sorted(_CHECKS)}")
    mesh = mesh or mesh_mod.get_mesh()
    if mode() == "gspmd":
        return _fallback(op, "platform is not TPU")
    shape = tuple(int(s) for s in shape)
    return _CHECKS[op](shape, np.dtype(dtype), tiling, mesh, params)
