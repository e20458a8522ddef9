"""Compile the chip's kernels, and the XLA lowerings of the irregular
ops, at real widths for a described v5e:2x2.

Nothing runs: the TPU compiler, installed here, compiles for a chip
that is described and not attached, and refuses what Mosaic or XLA on
the chip would refuse (unaligned blocks, VMEM over budget, programs
that cannot be partitioned). Interpret mode cannot show those. The
only file that describes the topology; it does so inside the module
fixture, never at import, so every test worker collects the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from spartan_tpu.array import tiling as tiling_mod
from spartan_tpu.kernels import registry

F32 = jnp.float32
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs nowhere
    prev_cache = jax.config.jax_enable_compilation_cache
    # a described chip's executables are written but can never be read
    # back here: keep them out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture()
def mosaic(monkeypatch):
    """Kernel selection as on the chip: Pallas, interpret mode off."""
    monkeypatch.setattr(registry, "_platform", lambda: "tpu")


def _mesh(topo, shape):
    n = shape[0] * shape[1]
    return Mesh(np.array(topo.devices[:n]).reshape(shape),
                (tiling_mod.AXIS_ROW, tiling_mod.AXIS_COL))


def _sds(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _compiled_text(fn, *args, **static) -> str:
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_kmeans_kernel(topo, mosaic, chips):
    """config 3 (1M x 128, k=64): one chip runs one grid over the
    padded points; on the 2x2 mesh every row shard runs the kernel
    under shard_map and the partial sums meet in an all-reduce."""
    from spartan_tpu.kernels import kmeans as kk
    from spartan_tpu.parallel import mesh as mesh_mod

    mesh = _mesh(topo, (1, 1) if chips == 1 else (2, 2))
    p = mesh.shape[tiling_mod.AXIS_ROW]
    npad = -(-1_000_000 // (p * 1024)) * p * 1024
    with mesh_mod.use_mesh(mesh):
        txt = kk.run.lower(
            _sds((npad, 128), F32, mesh, P(tiling_mod.AXIS_ROW, None)),
            _sds((64, 128), F32, mesh), k=64,
            iters=_sds((), I32, mesh),
            valid_rows=1_000_000).compile().as_text()
    assert "tpu_custom_call" in txt
    assert ("all-reduce" in txt) == (chips == 4)


def _windowed_shapes(edges: int, nodes: int):
    """The SegmentPlan layout of ``edges`` sorted ids over ``nodes``
    segments, worst case padding (every window rounded up by a block)."""
    from spartan_tpu.ops.segment import SegmentPlan as SP

    n_pad = -(-nodes // SP.W) * SP.W
    rows_out = n_pad // 128
    outblk = min(1024, rows_out)
    step = SP.SUB * SP.EB
    nsteps = -(-(edges + (n_pad // SP.W) * SP.EB) // step)
    return dict(rows_pad=-(-rows_out // outblk) * outblk, nsteps=nsteps,
                outblk=outblk, sub=SP.SUB), nsteps * step


@pytest.mark.parametrize("nodes", [1 << 20, 2 << 20])
def test_windowed_segsum(topo, mosaic, nodes):
    """config 5's SpMV merge: 16M edges into 1M and 2M nodes (2M is
    the plan's VMEM-resident output bound)."""
    from spartan_tpu.kernels.segment import windowed_segsum

    mesh = _mesh(topo, (1, 1))
    static, grand = _windowed_shapes(16 << 20, nodes)
    txt = _compiled_text(
        windowed_segsum, _sds((grand,), F32, mesh),
        _sds((grand // 128, 128), I32, mesh),
        _sds((grand // 1024,), I32, mesh), **static)
    assert "tpu_custom_call" in txt


def _gather_plan(edges: int, nodes: int, cols: int = 0):
    """The gather layout of ``edges`` entries of a ``nodes`` x ``cols``
    matrix (square by default), worst case padding (every nonempty
    (output window, column window) block and every output window
    rounded up): the plan's dims and its group count."""
    from spartan_tpu.ops.segment import PlanDims
    from spartan_tpu.ops.segment import SegmentPlan as SP

    static, _ = _windowed_shapes(edges, nodes)
    n_win = -(-nodes // SP.W)
    blocks = min(n_win * -(-(cols or nodes) // SP.CW), edges)
    total = edges + blocks * (SP.GB - 1) + n_win * (SP.EB - 1)
    step = max(SP.GB * SP.GR, 1 << (total.bit_length() - 7))
    grand = -(-total // step) * step
    dims = PlanDims(nodes, static["rows_pad"], grand // (SP.SUB * SP.EB),
                    static["outblk"], SP.SUB)
    return dims, grand // SP.GB


def _gather_args(mesh, cols: int, groups: int):
    from spartan_tpu.ops.segment import SegmentPlan as SP

    return (_sds((cols,), F32, mesh), _sds((groups, 128), I32, mesh),
            _sds((groups // SP.GR, 1, SP.GR), I32, mesh),
            _sds((groups * 128,), F32, mesh))


@pytest.mark.parametrize("nodes,cols", [
    (1 << 20, 1 << 20), (2 << 20, 2 << 20), (2 << 20, 8 << 20)],
    ids=["1048576", "2097152", "2097152x8388608"])
def test_windowed_gather(topo, mosaic, nodes, cols):
    """config 5's SpMV gather: 33.5M entries (GAP Urand, scale 20) over
    1M and 2M nodes, and at the windowed path's bounds (2M rows,
    ``SegmentPlan.MAX_COLS`` columns, whose parts fill 48 of the
    kernel's 64 MiB of VMEM); x resident in VMEM as three bf16 parts,
    at the layout's worst-case group count."""
    from spartan_tpu.kernels.segment import windowed_gather
    from spartan_tpu.ops.segment import SegmentPlan as SP

    assert cols <= SP.MAX_COLS
    mesh = _mesh(topo, (1, 1))
    _, groups = _gather_plan(32 << 20, nodes, cols)
    txt = _compiled_text(windowed_gather,
                         *_gather_args(mesh, cols, groups))
    assert "tpu_custom_call" in txt


def test_pagerank_loop(topo, mosaic):
    """The rank10 cell's program: the fused power iteration, gather
    and merge kernels inside one fori_loop, at 1M nodes; no XLA gather
    is left in it."""
    from spartan_tpu.examples.pagerank import _pagerank_loop

    mesh = _mesh(topo, (1, 1))
    nodes = 1 << 20
    dims, groups = _gather_plan(32 << 20, nodes)
    x, lcols, gwin, pdata = _gather_args(mesh, nodes, groups)
    bufs = (pdata, lcols, gwin,
            _sds((groups, 128), I32, mesh),
            _sds((groups * 128 // 1024,), I32, mesh))
    txt = _pagerank_loop.lower(bufs, x, _sds((), F32, mesh),
                               _sds((), I32, mesh), n=nodes,
                               dims=dims).compile().as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          txt)) == 2
    assert " gather(" not in txt


def _no_kernel(txt: str) -> None:
    assert "tpu_custom_call" not in txt


@pytest.mark.parametrize("shape", [(1 << 20, 64), (1 << 20,)])
def test_segment_sum(topo, mosaic, shape):
    """The segment-sum merge (naive Bayes' class sums, the sparse
    paths' todense and row sums): XLA's scatter over 1M entries sharded
    across four chips into 64 segments."""
    from spartan_tpu.ops.segment import segment_sum

    mesh = _mesh(topo, (4, 1))
    rows = P(tiling_mod.AXIS_ROW, *([None] * (len(shape) - 1)))
    _no_kernel(_compiled_text(
        segment_sum, _sds(shape, F32, mesh, rows),
        _sds((shape[0],), I32, mesh, P(tiling_mod.AXIS_ROW)),
        num_segments=64))


@pytest.mark.parametrize("shape", [(2, 2), (4, 1)], ids=["2x2", "4x1"])
def test_sort_exchange_four_chips(topo, mosaic, shape):
    """A 1-D 16M-element sample sort on the two meshes the four-chip
    smoke run sorts on: the send buffers are packed by XLA's scatter,
    both exchanges are fixed-size all-to-alls, and the program fits the
    chip (the ragged_all_to_all transport it replaced needed 16 GB)."""
    from spartan_tpu.ops import sort as sort_ops

    mesh = _mesh(topo, shape)
    compiled = jax.jit(lambda v: sort_ops.sample_sort(v, mesh)).lower(
        _sds((16 << 20,), F32, mesh, P(tiling_mod.AXIS_ROW))).compile()
    txt = compiled.as_text()
    _no_kernel(txt)
    assert "all-to-all" in txt and "ragged-all-to-all" not in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_distributed_topk_four_chips(topo, mosaic):
    """k=64 of 16M elements over four chips: a per-shard lax.top_k,
    then a gather of the 4 x 64 candidates."""
    from spartan_tpu.ops import sort as sort_ops

    mesh = _mesh(topo, (4, 1))
    txt = _compiled_text(
        lambda v: sort_ops.distributed_topk(v, 64, mesh=mesh),
        _sds((16 << 20,), F32, mesh, P(tiling_mod.AXIS_ROW)))
    _no_kernel(txt)


def _val(shape, dtype, spec):
    """A leaf standing for an operand of this shape and placement."""
    from types import SimpleNamespace

    from spartan_tpu.expr.base import ValExpr

    return ValExpr(SimpleNamespace(shape=shape, dtype=np.dtype(dtype),
                                   tiling=tiling_mod.Tiling(tuple(spec))))


def test_bincount_four_chips(topo, mosaic):
    """``st.bincount`` over 1M ids sharded across four chips, lowered
    as BincountExpr lowers it."""
    from spartan_tpu.expr.builtins import BincountExpr
    from spartan_tpu.parallel import mesh as mesh_mod

    mesh = _mesh(topo, (4, 1))
    rows = P(tiling_mod.AXIS_ROW)
    ids = _val((1 << 20,), I32, rows)
    with mesh_mod.use_mesh(mesh):
        e = BincountExpr(ids, 4096)
        txt = _compiled_text(lambda v: e.lower({ids._id: v}),
                             _sds((1 << 20,), I32, mesh, rows))
    _no_kernel(txt)


def test_stencil_h_sharded(topo, mosaic):
    """A 3x3 SAME stencil over 8 x 224 x 224 x 64 images whose H axis
    is sharded across four chips: GSPMD's conv with its own halos."""
    from spartan_tpu.ops.stencil import StencilExpr
    from spartan_tpu.parallel import mesh as mesh_mod

    mesh = _mesh(topo, (4, 1))
    h = P(None, tiling_mod.AXIS_ROW, None, None)
    x = _val((8, 224, 224, 64), F32, h)
    w = _val((3, 3, 64, 64), F32, P(None, None, None, None))
    with mesh_mod.use_mesh(mesh):
        e = StencilExpr(x, w, (1, 1), "SAME")
        txt = _compiled_text(
            lambda xv, wv: e.lower({x._id: xv, w._id: wv}),
            _sds((8, 224, 224, 64), F32, mesh, h),
            _sds((3, 3, 64, 64), F32, mesh))
    _no_kernel(txt)


def _named_kernel_text(topo, kernel: str) -> str:
    """A small program around one Pallas kernel, compiled for one
    described chip."""
    mesh = _mesh(topo, (1, 1))
    if kernel == "kmeans_lloyd":
        from spartan_tpu.kernels import kmeans as kk
        from spartan_tpu.parallel import mesh as mesh_mod

        with mesh_mod.use_mesh(mesh):
            return kk.run.lower(_sds((8192, 128), F32, mesh),
                                _sds((16, 128), F32, mesh), k=16,
                                iters=_sds((), I32, mesh)
                                ).compile().as_text()
    if kernel == "windowed_segsum":
        from spartan_tpu.kernels.segment import windowed_segsum

        static, grand = _windowed_shapes(1 << 16, 1 << 14)
        return _compiled_text(
            windowed_segsum, _sds((grand,), F32, mesh),
            _sds((grand // 128, 128), I32, mesh),
            _sds((grand // 1024,), I32, mesh), **static)
    from spartan_tpu.kernels.segment import windowed_gather

    return _compiled_text(windowed_gather,
                          *_gather_args(mesh, 1 << 14, 256))


@pytest.mark.parametrize("kernel", ["kmeans_lloyd", "windowed_segsum",
                                    "windowed_gather"])
def test_kernel_carries_its_name(topo, mosaic, kernel):
    """The compiled custom call is named by its ``pallas_call``'s
    ``name``, which the device trace's op events carry."""
    txt = _named_kernel_text(topo, kernel)
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom_call_target="
                     r'"tpu_custom_call"', txt), kernel


def test_dot_8192_committed_plan(topo):
    """The dot_8192 cell's product — 8192^2 f32 tiled (x, y) on the
    2x2 mesh, default precision — lowered as the smart-tiling pass
    commits it for the described chip: the operands' panels gathered
    in bf16, no all-reduce of a partial product, the output (x, y)."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.tiling_cost import assign_tilings
    from spartan_tpu.parallel import mesh as mesh_mod

    n = 8192
    mesh = _mesh(topo, (2, 2))
    xy = P(tiling_mod.AXIS_ROW, tiling_mod.AXIS_COL)
    a, b = (_val((n, n), F32, xy) for _ in range(2))
    with mesh_mod.use_mesh(mesh):
        d = assign_tilings(DotExpr(a, b))
        compiled = jax.jit(lambda x, y: d.lower({a._id: x, b._id: y})
                           ).lower(_sds((n, n), F32, mesh, xy),
                                   _sds((n, n), F32, mesh, xy)).compile()
    txt = compiled.as_text()
    assert "all-reduce" not in txt
    gathers = sorted(m.group(1) for m in re.finditer(
        r"= (\w+\[[\d,]+\])\S* all-gather\(", txt))
    assert gathers == ["bf16[4096,8192]", "bf16[8192,4096]"], gathers
    assert compiled.output_shardings.spec == xy
