"""Segment-sum / scatter-add merges.

The TPU-native equivalent of the reference's Cython sparse-merge kernel
(SURVEY.md §2.5: ``spartan/sparse_update.pyx``). Two forms:

* :func:`segment_sum` — ``jax.ops.segment_sum`` (XLA scatter). It
  measured faster than a blocked one-hot Pallas kernel and a one-hot
  matmul on v5e (1M x 128, k=64: xla 33ms, onehot 67ms, pallas 71ms).
* :class:`SegmentPlan` — a host-planned layout for the windowed
  sorted-segment and gather kernels (spartan_tpu/kernels/segment.py),
  the SpMV path PageRank runs on one chip.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.segment import CW as _GATHER_WINDOW
from ..kernels.segment import GATHER_MAX_COLS as _GATHER_MAX_COLS


def segment_sum(vals: jax.Array, ids: jax.Array, num_segments: int,
                sorted_ids: bool = False) -> jax.Array:
    """Sum ``vals`` rows into ``num_segments`` buckets by ``ids``.

    ids outside [0, num_segments) are dropped (XLA segment_sum
    semantics), which the padding paths rely on. ``sorted_ids`` unlocks
    XLA's sorted-scatter fast path (the SparseDistArray invariant)."""
    return jax.ops.segment_sum(vals, ids, num_segments=num_segments,
                               indices_are_sorted=sorted_ids)


def segment_count(ids: jax.Array, num_segments: int,
                  dtype=jnp.float32) -> jax.Array:
    return segment_sum(jnp.ones(ids.shape, dtype), ids, num_segments)


def _upload(arr: np.ndarray) -> jax.Array:
    from ..array.distarray import upload_span

    with upload_span(arr):
        return jnp.asarray(arr)


class SegmentPlan:
    """Host-precomputed layout for the windowed sorted-segment kernel.

    XLA's scatter lowering is the TPU sparse bottleneck (measured 199 ms
    for a 16M->1M sorted segment-sum on v5e, and far worse inside
    ``fori_loop``). This plan turns the scatter into dense one-hot
    algebra: entries are grouped by aligned ``W``-wide output windows and
    padded to 1024-entry subblocks; the kernel keeps the whole output
    resident in a VMEM scratch and, per subblock, builds two small
    one-hots from each id's lane (``id & 127``) and sublane (``id >> 7``)
    halves, contracts them with one (8,128)x(128,128) MXU dot, and
    accumulates the (8,128) window block at a dynamic scratch offset.
    Measured 34 ms standalone (~20 ms fused) for the same 16M->1M merge —
    ~6x over XLA — and it does not degrade inside ``lax.fori_loop``.

    The plan lays the stream out for :func:`windowed_gather` too, the
    same move for an SpMV's ``x[cols]``: inside each output window the
    entries are ordered by column window (``col // CW``) and every
    (output window, column window) block is padded to whole
    ``GB``-entry groups, so each group reads one ``CW``-wide window of
    ``x`` held in VMEM. The group layout needs only that each
    1024-entry subblock stays in one output window, which the output
    windows' padding keeps. A plain segment-sum passes one column
    (``cols`` all 0, ``num_cols=1``).

    The plan is built once per static id structure (e.g. a sparse
    matrix's rows); runtime value streams must be produced in plan order
    (use :meth:`reorder` on the host-side companion arrays at build
    time). Scratch residency bounds ``num_segments`` to ~2M on a 16 MB
    VMEM part. The kernels live in spartan_tpu/kernels/segment.py
    (lint rule 12: Pallas only under the kernel layer).
    """

    W = 1024          # output window (one (8,128) f32 block)
    EB = 1024         # entries per subblock
    SUB = 8           # subblocks per grid step
    CW = _GATHER_WINDOW   # gather column window: 128 x 128 of x
    GB = 128          # entries per gather group
    GR = 128          # gather groups per grid step
    MAX_COLS = _GATHER_MAX_COLS   # columns whose x fits in VMEM

    def __init__(self, ids: np.ndarray, num_segments: int,
                 cols: np.ndarray, num_cols: int):
        ids = np.asarray(ids)
        if ids.ndim != 1:
            raise ValueError("SegmentPlan ids must be 1-D")
        if np.any(np.diff(ids) < 0):
            raise ValueError("SegmentPlan requires sorted ids")
        n = int(num_segments)
        W, EB, SUB = self.W, self.EB, self.SUB
        self.num_segments = n
        self.n_pad = -(-max(n, 1) // W) * W
        n_windows = self.n_pad // W
        # Out-of-range ids are dropped on both ends (matching
        # jax.ops.segment_sum): sorted => negatives are a prefix and
        # ids >= n a suffix, so the valid run is a contiguous slice.
        neg = int(np.searchsorted(ids, 0))
        e = int(np.searchsorted(ids, n))
        ids_v = ids[neg:e].astype(np.int32)
        e -= neg
        wb_all = ids_v // W
        cols_v = np.asarray(cols)[neg:neg + e].astype(np.int32)
        nc = int(num_cols)
        if e and (cols_v.min() < 0 or cols_v.max() >= nc):
            raise ValueError("SegmentPlan cols must lie in [0, num_cols)")
        ncw = max(-(-nc // self.CW), 1)
        key = wb_all * ncw + cols_v // self.CW
        if n_windows * ncw <= 1 << 16:
            key = key.astype(np.uint16)   # numpy radix-sorts it
        # blocks of whole groups follow each other inside their output
        # window, and each window is padded to whole subblocks
        counts = np.bincount(key, minlength=n_windows * ncw)
        gpad = -(-counts // self.GB) * self.GB
        per_win = gpad.reshape(n_windows, ncw)
        padded = -(-per_win.sum(1) // EB) * EB
        win_start = np.cumsum(padded) - padded
        blk_start = (win_start[:, None] + np.cumsum(per_win, 1)
                     - per_win).reshape(-1)
        total = int(padded.sum())
        # whole grid steps of both kernels, and no finer than 1/128 of
        # the stream (at most 1/64 more slots): the blocks' padding
        # varies with the draw, and each new stream length compiles the
        # loop anew. rank10 on v5e: warm set-up 28.9-30.9 s on a new
        # length, 24.0 s on a seen one, 26.0-26.4 s rounded; the
        # rounding's 0.47% more slots cost 1.2% of step time.
        step = max(self.GB * self.GR, 1 << max(total.bit_length() - 7, 0))
        rows_out = self.n_pad // 128
        self.outblk = min(1024, rows_out)
        self.rows_pad = -(-rows_out // self.outblk) * self.outblk
        total_steps = max(-(-total // step), 1)
        grand = total_steps * step
        # position of each source entry in the padded stream: its
        # block's start plus its rank among the block's entries
        shift = blk_start - (np.cumsum(counts) - counts)
        order = np.argsort(key, kind="stable")
        pos = np.empty(e, np.int64)
        pos[order] = np.arange(e) + shift[key[order]]
        ids_local = np.full(grand, W, np.int32)      # sentinel: no match
        ids_local[pos] = ids_v - wb_all * W
        self.perm = pos                     # valid entry -> padded slot
        self._lo = neg                      # first valid source index
        self.padded_size = grand
        self.nsteps = grand // (SUB * EB)
        wb = np.zeros(grand // EB, np.int32)
        wb[:total // EB] = np.repeat(
            np.arange(n_windows, dtype=np.int32), padded // EB)
        self._ids2d = _upload(ids_local.reshape(-1, 128))
        self._wb = _upload(wb)
        # padding slots read column 0 of window 0 (their data is 0)
        lcols = np.zeros(grand, np.int32)
        lcols[pos] = cols_v % self.CW
        nb = gpad // self.GB
        gwin = np.zeros(grand // self.GB, np.int32)
        first = np.repeat(blk_start // self.GB - (np.cumsum(nb) - nb), nb)
        gwin[first + np.arange(first.size)] = np.repeat(
            np.tile(np.arange(ncw, dtype=np.int32), n_windows), nb)
        self._lcols = _upload(lcols.reshape(-1, 128))
        self._gwin = _upload(gwin.reshape(-1, 1, self.GR))

    @property
    def groups(self) -> int:
        """Gather groups in the stream (padding groups included)."""
        return self.padded_size // self.GB

    @property
    def dims(self) -> "PlanDims":
        return PlanDims(self.num_segments, self.rows_pad, self.nsteps,
                        self.outblk, self.SUB)

    def reorder(self, arr: np.ndarray) -> np.ndarray:
        """Host-side: lay a per-entry companion array out in plan order,
        padding slots 0."""
        arr = np.asarray(arr)
        out = np.zeros((self.padded_size,) + arr.shape[1:], arr.dtype)
        out[self.perm] = arr[self._lo:self._lo + self.perm.size]
        return out


class PlanDims(NamedTuple):
    """A plan's static shape: the jit-static half of its buffers."""

    num_segments: int
    rows_pad: int
    nsteps: int
    outblk: int
    sub: int


def windowed_merge(vals: jax.Array, ids2d: jax.Array, wb: jax.Array,
                   dims: PlanDims) -> jax.Array:
    """Traceable: a plan-ordered value stream summed into its
    ``dims.num_segments`` segments by ``windowed_segsum``."""
    from ..kernels.segment import windowed_segsum

    out2d = windowed_segsum(vals, ids2d, wb, rows_pad=dims.rows_pad,
                            nsteps=dims.nsteps, outblk=dims.outblk,
                            sub=dims.sub)
    return out2d.reshape(-1)[:dims.num_segments]


def windowed_spmv(pdata: jax.Array, lcols: jax.Array, gwin: jax.Array,
                  ids2d: jax.Array, wb: jax.Array, x: jax.Array,
                  dims: PlanDims) -> jax.Array:
    """Traceable ``A @ x`` over a plan built with columns: the gather
    ``pdata * x[cols]`` by ``windowed_gather``, then the merge. The
    plan's buffers enter as arguments so callers can jit them as traced
    values (one compile per plan shape, no device memory pinned)."""
    from ..kernels.segment import windowed_gather

    return windowed_merge(windowed_gather(x, lcols, gwin, pdata),
                          ids2d, wb, dims)
