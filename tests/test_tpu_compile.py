"""Compile the chip's kernels at real widths for a described v5e:2x2.

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


@pytest.mark.parametrize("shape", [(1 << 20, 64), (1 << 20,)])
def test_segment_sum_block(topo, shape):
    from spartan_tpu.kernels.segment import segment_sum_block

    mesh = _mesh(topo, (1, 1))
    txt = _compiled_text(
        segment_sum_block, _sds(shape, F32, mesh),
        _sds((shape[0],), I32, mesh), num_segments=64, interpret=False)
    assert "tpu_custom_call" in txt


def test_shard_topk(topo, mosaic):
    """distributed top-k's local stage: k=64 of a 4M-element shard
    (16M over the four chips)."""
    from spartan_tpu.kernels.topk import shard_topk

    mesh = _mesh(topo, (4, 1))
    sel = registry.select("topk", (16 << 20,), np.float32,
                          tiling_mod.row(1), mesh, k=64)
    assert sel.pallas and not sel.interpret, sel.reason
    txt = jax.jit(lambda key: shard_topk(key, 64, -np.inf, sel)).lower(
        _sds((4 << 20,), F32, _mesh(topo, (1, 1)))).compile().as_text()
    assert "tpu_custom_call" in txt


def test_partition_pack(topo, mosaic):
    """The padded sample-sort exchange's send-buffer pack at the widest
    shard the selection admits (512K elements, 2M over 4 chips)."""
    from spartan_tpu.kernels.exchange import partition_pack

    mesh = _mesh(topo, (4, 1))
    m = 1 << 19
    sel = registry.select("sort_exchange", (4 * m,), np.float32,
                          tiling_mod.row(1), mesh, p=4, m=m)
    assert sel.pallas and not sel.interpret, sel.reason
    one = _mesh(topo, (1, 1))
    txt = jax.jit(lambda xs, s, c: partition_pack(xs, s, c, 4, sel)).lower(
        _sds((m,), F32, one), _sds((4,), I32, one),
        _sds((4,), I32, one)).compile().as_text()
    assert "tpu_custom_call" in txt


def test_sort_exchange_four_chips(topo, mosaic):
    """A 1-D 16M-element sample sort over four chips: both exchanges
    are fixed-size all-to-alls, and the program fits the chip (the
    ragged_all_to_all transport it replaced needed 16 GB here)."""
    from spartan_tpu.ops import sort as sort_ops

    mesh = _mesh(topo, (4, 1))
    compiled = jax.jit(lambda v: sort_ops.sample_sort(v, mesh)).lower(
        _sds((16 << 20,), F32, mesh, P(tiling_mod.AXIS_ROW))).compile()
    txt = compiled.as_text()
    assert "all-to-all" in txt and "ragged-all-to-all" not in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


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
    if kernel == "segment_sum_block":
        from spartan_tpu.kernels.segment import segment_sum_block

        return _compiled_text(segment_sum_block, _sds((8192,), F32, mesh),
                              _sds((8192,), I32, mesh), num_segments=64,
                              interpret=False)
    if kernel == "bincount_block":
        from spartan_tpu.kernels.histogram import bincount_block

        return _compiled_text(bincount_block, _sds((8192,), I32, mesh),
                              length=64, interpret=False)
    from spartan_tpu.kernels.stencil import conv_block

    return _compiled_text(conv_block, _sds((1, 18, 18, 128), F32, mesh),
                          _sds((3, 3, 128, 128), F32, mesh), hb=8,
                          interpret=False)


@pytest.mark.parametrize("kernel", ["kmeans_lloyd", "windowed_segsum",
                                    "segment_sum_block", "bincount_block",
                                    "conv_block"])
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
    from types import SimpleNamespace

    from spartan_tpu.expr.base import ValExpr
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.tiling_cost import assign_tilings
    from spartan_tpu.parallel import mesh as mesh_mod

    n = 8192
    mesh = _mesh(topo, (2, 2))
    xy = P(tiling_mod.AXIS_ROW, tiling_mod.AXIS_COL)
    a, b = (ValExpr(SimpleNamespace(shape=(n, n), dtype=np.dtype(F32),
                                    tiling=tiling_mod.Tiling(tuple(xy))))
            for _ in range(2))
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
