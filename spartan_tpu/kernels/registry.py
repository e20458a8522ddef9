"""Kernel registry + selection policy for the partitionable Pallas layer.

The one place that decides, per op / shape / tiling / platform, whether
an irregular op lowers through a shard_map-wrapped Pallas TPU kernel or
through the portable GSPMD formulation (ROADMAP open item 1; TileLoom's
planning stance in PAPERS.md: the kernel's grid/block schedule is
*derived from the tiling the DP already chose*, never re-derived per
kernel).

Three pieces:

* :func:`derive` — the tiling->grid rule. The committed ``Tiling`` of
  the op's operand names the per-chip shard; the block shape is that
  shard quantized to TPU lane/sublane tiles (last dim to 128 lanes,
  leading rows to the dtype's sublane quantum), and the grid is the
  ceil-division of the shard by the block. One function, property-
  tested over the whole tiling vocabulary (tests/test_kernels.py).
* :func:`select` — the policy. ``FLAGS.native_kernels`` gates the
  layer (``auto``: Pallas on TPU only, GSPMD elsewhere — CPU lowering
  is provably unchanged; ``on``: Pallas everywhere, ``interpret=True``
  off-TPU so CPU CI exercises every kernel; ``off``: GSPMD always).
  Per-op constraint checks fall back to GSPMD with the reason
  recorded, and ops whose Pallas form *measured worse* than XLA keep
  the portable lowering in ``auto`` (the measured-win contract —
  ``redistribution.py``'s schedule-gating pattern).
* :func:`policy_key` — what the plan- and compile-cache keys carry
  (the audit/redistribution pattern): a Pallas-lowered executable must
  never alias the GSPMD executable of the same expr structure.

``select`` is a pure function of (op, shapes, tilings, flags,
platform), so ``st.explain`` recomputes the exact decision the
lowering seam will take (:func:`node_selection` / :func:`plan_entries`)
without tracing anything.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..utils.config import FLAGS

FLAGS.define_str(
    "native_kernels", "auto",
    "Partitionable Pallas kernel layer (spartan_tpu/kernels): "
    "auto = Pallas on TPU only (CPU lowering unchanged), on = Pallas "
    "everywhere (interpret mode off-TPU: the CPU CI parity path), "
    "off = GSPMD lowerings always. Part of the plan/compile cache "
    "keys. See docs/KERNELS.md.")

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
    """Resolved backend family: ``pallas`` or ``gspmd``."""
    v = FLAGS.native_kernels
    if v == "off":
        return "gspmd"
    if v == "on":
        return "pallas"
    return "pallas" if _platform() == "tpu" else "gspmd"


def interpret_mode() -> bool:
    """Pallas interpret mode: required anywhere but a real TPU."""
    return _platform() != "tpu"


def policy_key() -> Tuple:
    """The kernel-policy component of the plan/compile cache keys: a
    Pallas-lowered plan must never alias its GSPMD twin (and an
    interpret-mode executable must never alias a Mosaic one)."""
    return (mode(), interpret_mode())


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

    def describe(self) -> str:
        return (f"grid={self.grid} block={self.block} "
                f"shard={self.shard}")


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
    interpret: bool = False

    @property
    def pallas(self) -> bool:
        return self.backend == "pallas"


def _fallback(op: str, reason: str) -> Selection:
    return Selection(op, "gspmd", reason)


# ops whose Pallas form measured WORSE than the XLA lowering on the
# real chip keep the portable path in auto mode — a kernel only wins
# its slot by measurement (redistribution.py's gating contract).
# FLAGS.native_kernels=on (and explicit impl= overrides) still select
# them: that is the ablation / parity-test path.
_MEASURED_OFF: Dict[str, str] = {
    "segment_sum": (
        "measured worse than XLA scatter on v5e (1M x 128, k=64: "
        "pallas 71ms vs xla 33ms — ops/segment.py r0 note); kept as "
        "ablation, select with segment_impl=pallas or "
        "native_kernels=on"),
}


def _sel_bincount(shape, dtype, tiling, mesh, params) -> Selection:
    op = "bincount"
    length = int(params["length"])
    if len(shape) != 1:
        return _fallback(op, "only 1-D operands (ravel falls back)")
    if not np.issubdtype(np.dtype(dtype), np.integer):
        return _fallback(op, f"ids dtype {np.dtype(dtype)} not integral")
    if length > 4096:
        return _fallback(op, f"length {length} > 4096 (one-hot block "
                             "exceeds the VMEM budget)")
    p = _collective_size(tiling, mesh)
    n_pad = -(-shape[0] // max(p, 1)) * max(p, 1)
    sched, why = derive((n_pad,), _row_tiling(tiling, mesh, 1), dtype,
                        mesh, rows_per_block=2)
    if sched is None:
        return _fallback(op, why)
    k_total = -(-length // LANE) * LANE
    # one-hot block (block_e, k_total) f32 + ids table + counts row
    be = sched.block[0] * LANE
    need = 4 * (be * k_total + sched.padded[0] * LANE + k_total)
    if need > VMEM_BUDGET:
        return _fallback(op, f"one-hot working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched, interpret_mode())


def _sel_segment(shape, dtype, tiling, mesh, params) -> Selection:
    op = "segment_sum"
    k = int(params["num_segments"])
    if np.dtype(dtype) != np.float32:
        return _fallback(op, f"vals dtype {np.dtype(dtype)} != float32")
    if len(shape) not in (1, 2):
        return _fallback(op, "only 1-D/2-D value streams")
    d = shape[1] if len(shape) == 2 else 1
    p = _collective_size(tiling, mesh)
    n_pad = -(-shape[0] // max(p, 1)) * max(p, 1)
    sched, why = derive((n_pad, d) if len(shape) == 2 else (n_pad,),
                        _row_tiling(tiling, mesh, len(shape)), dtype,
                        mesh, rows_per_block=512)
    if sched is None:
        return _fallback(op, why)
    k_pad = -(-k // 8) * 8
    d_pad = -(-d // LANE) * LANE
    be = sched.block[0] if not sched.lifted else sched.block[0] * LANE
    need = 4 * (be * k_pad + k_pad * d_pad + be * d_pad)
    if need > VMEM_BUDGET:
        return _fallback(op, f"one-hot working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched, interpret_mode())


def _sel_topk(shape, dtype, tiling, mesh, params) -> Selection:
    op = "topk"
    k = int(params["k"])
    if len(shape) != 1:
        return _fallback(op, "only 1-D operands")
    if np.dtype(dtype).itemsize != 4:
        return _fallback(op, f"dtype {np.dtype(dtype)} is not 4-byte "
                             "(extraction keys are f32/i32 lanes)")
    if k > LANE:
        return _fallback(op, f"k {k} > 128 (candidate row exceeds one "
                             "lane tile; the sample argsort handles it)")
    p = _collective_size(tiling, mesh)
    m = -(-shape[0] // max(p, 1))
    sched, why = derive((m * max(p, 1),),
                        _row_tiling(tiling, mesh, 1), dtype, mesh,
                        rows_per_block=512)
    if sched is None:
        return _fallback(op, why)
    return Selection(op, "pallas", "selected", sched, interpret_mode())


def _sel_exchange(shape, dtype, tiling, mesh, params) -> Selection:
    op = "sort_exchange"
    m = int(params["m"])
    p = int(params["p"])
    if p < 2:
        return _fallback(op, "single shard: no exchange to pack")
    if np.dtype(dtype).itemsize != 4:
        return _fallback(op, f"dtype {np.dtype(dtype)} is not 4-byte "
                             "(the exact lane-roll splits 16-bit halves)")
    sched, why = derive((m * p,), _row_tiling(tiling, mesh, 1), dtype,
                        mesh, rows_per_block=512)
    if sched is None:
        return _fallback(op, why)
    mr = -(-m // LANE)
    # resident source rows + one destination row block (+1 carry row)
    need = 4 * LANE * (sched.padded[0] + 2 * (mr + 1))
    if need > VMEM_BUDGET:
        return _fallback(op, f"shard working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched, interpret_mode())


def _sel_stencil(shape, dtype, tiling, mesh, params) -> Selection:
    op = "stencil"
    if len(shape) != 4:
        return _fallback(op, "only NHWC operands")
    if np.dtype(dtype) != np.float32:
        return _fallback(op, f"dtype {np.dtype(dtype)} != float32")
    if tuple(params.get("stride", (1, 1))) != (1, 1):
        return _fallback(op, "only stride 1 (strided shards misalign "
                             "with the halo rule)")
    if params.get("padding", "SAME") != "SAME":
        return _fallback(op, "only SAME padding (halo ppermute zeros "
                             "match SAME's zero pad)")
    h_axis = tiling.axes[1]
    if not isinstance(h_axis, str) or int(mesh.shape.get(h_axis, 1)) < 2:
        return _fallback(op, "H axis not mesh-sharded: GSPMD needs no "
                             "halo exchange here")
    if any(a is not None for a in (tiling.axes[2], tiling.axes[3])):
        return _fallback(op, "W/C axes must be unsharded")
    p = int(mesh.shape[h_axis])
    n, h, w, c = shape
    if h % p:
        return _fallback(op, f"H {h} not divisible by {p} shards")
    kh, kw = params["kshape"]
    hs = h // p
    if hs < kh:
        return _fallback(op, f"shard H {hs} smaller than filter {kh}")
    # grid over H row-blocks of the shard (the halo axis); the kernel
    # adds the image index as a leading grid dim
    sched, why = derive((h, w, c), tiling.drop_axis(0), dtype, mesh,
                        rows_per_block=max(8, min(64, hs)))
    if sched is None:
        return _fallback(op, why)
    wp = w + kw - 1
    need = 4 * ((hs + kh - 1) * wp * c + kh * kw * c *
                int(params["out_channels"]))
    if need > VMEM_BUDGET:
        return _fallback(op, f"per-image working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched, interpret_mode())


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
    p = _collective_size(tiling, mesh)
    if n % max(p, 1):
        return _fallback(op, f"n {n} not divisible by {p} shards")
    block = int(params.get("block", 1024))
    if (n // max(p, 1)) % block:
        return _fallback(op, f"shard rows {n // max(p, 1)} not a "
                             f"multiple of the {block} point block")
    sched, why = derive(shape, _row_tiling(tiling, mesh, 2), dtype,
                        mesh, rows_per_block=block)
    if sched is None:
        return _fallback(op, why)
    need = 4 * (block * d + 2 * LANE * d + 2 * LANE)
    if need > VMEM_BUDGET:
        return _fallback(op, f"point block working set {need}B > VMEM "
                             f"budget {VMEM_BUDGET}B")
    return Selection(op, "pallas", "selected", sched, interpret_mode())


_CHECKS = {
    "bincount": _sel_bincount,
    "segment_sum": _sel_segment,
    "topk": _sel_topk,
    "sort_exchange": _sel_exchange,
    "stencil": _sel_stencil,
    "kmeans": _sel_kmeans,
}


def _row_tiling(tiling: Optional[tiling_mod.Tiling], mesh,
                ndim: int) -> tiling_mod.Tiling:
    """The leading-axis row tiling every kernel shard_maps over (the
    collective axis); the operand's committed tiling when it already
    rides the mesh row axis, else the canonical row placement."""
    del tiling  # kernels always exchange over the row axis today
    del mesh
    return tiling_mod.row(ndim)


def _collective_size(tiling: Optional[tiling_mod.Tiling], mesh) -> int:
    return int(mesh.shape.get(tiling_mod.AXIS_ROW, 1))


def select(op: str, shape, dtype, tiling: Optional[tiling_mod.Tiling],
           mesh=None, force: bool = False, **params) -> Selection:
    """The per-op backend decision (pure: flags + platform + static
    shapes/tilings only — ``st.explain`` calls this with the same
    inputs the lowering does and prints the same answer).

    ``force=True`` skips the measured-off table (explicit ``impl=``
    overrides, ablation benchmarks) but never the constraint checks —
    a kernel that cannot cover the shard still falls back."""
    if op not in _CHECKS:
        raise KeyError(f"unknown kernel op {op!r}; known: "
                       f"{sorted(_CHECKS)}")
    mesh = mesh or mesh_mod.get_mesh()
    if not force:
        m = mode()
        if m == "gspmd":
            why = ("FLAGS.native_kernels=off" if FLAGS.native_kernels
                   == "off" else "platform is not TPU "
                                 "(native_kernels=auto)")
            return _fallback(op, why)
        if FLAGS.native_kernels == "auto" and op in _MEASURED_OFF:
            return _fallback(op, _MEASURED_OFF[op])
    shape = tuple(int(s) for s in shape)
    return _CHECKS[op](shape, np.dtype(dtype), tiling, mesh, params)


# -- explain integration ------------------------------------------------


def node_selection(node: Any) -> Optional[Selection]:
    """The Selection an expr node's lowering will ask for — None when
    the node type never routes through the kernel layer. Matched by
    class name so this module stays import-light (no expr imports)."""
    name = type(node).__name__
    mesh = mesh_mod.get_mesh()
    try:
        if name == "TopKExpr":
            return select("topk", node.x.shape, node.x.dtype,
                          tiling_mod.row(1), mesh, k=node.k)
        if name == "BincountExpr":
            return select("bincount", node.x.shape, node.x.dtype,
                          node.x.out_tiling(), mesh, length=node.length)
        if name == "SampleSortExpr":
            from ..ops import sort as sort_ops

            moved = (node._moved_in_tiling() if node.x.ndim > 1
                     else node.x.out_tiling())
            axis = sort_ops.collective_axis(moved, mesh)
            p = int(mesh.shape.get(axis, 1))
            n = node.x.shape[-1] if node.x.ndim else 0
            m = -(-n // p) if p else n
            return select("sort_exchange", (n,), node.x.dtype, moved,
                          mesh, p=p, m=m)
        if name == "StencilExpr":
            return select(
                "stencil", node.x.shape, node.x.dtype,
                node.x.out_tiling(), mesh,
                stride=node.stride, padding=node.padding,
                kshape=node.w.shape[:2], out_channels=node.w.shape[3])
    except Exception:  # noqa: BLE001 - advisory surface only
        return None
    return None


def plan_entries(dag: Any) -> list:
    """Kernel-selection entries for every kernel-eligible node of an
    optimized DAG — the ``kernels`` section of the plan report
    (obs/explain.py), mirroring the decisions lowering will make."""
    from ..expr.optimize import dag_nodes

    out = []
    for n in dag_nodes(dag):
        sel = node_selection(n)
        if sel is None:
            continue
        entry: Dict[str, Any] = {
            "node": f"{type(n).__name__}#{n._id}",
            "op": sel.op, "backend": sel.backend,
        }
        if sel.schedule is not None and sel.pallas:
            entry["grid"] = tuple(sel.schedule.grid)
            entry["block"] = tuple(sel.schedule.block)
        if not sel.pallas:
            entry["reason"] = sel.reason
        if sel.interpret and sel.pallas:
            entry["interpret"] = True
        out.append(entry)
    return out
