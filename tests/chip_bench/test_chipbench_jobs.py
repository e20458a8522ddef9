"""The harness's comparison, driven end to end on the CPU at tiny sizes:
a sound run is correct; the control (the plain reference at the next
precision down, in the program's place) and each fault planted in the
program underneath the timed path come out not correct. The limits
are the cells' own, from their configuration files.

Job cells: ``kmeans_1m.fit20``, ``pagerank_1m.rank10``,
``dot_8192.mesh2x2``. The serving cell is in test_chipbench_serve.py.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import harness  # noqa: E402

import spartan_tpu as st  # noqa: E402
from spartan_tpu.array import distarray as da  # noqa: E402
from spartan_tpu.examples import kmeans as km  # noqa: E402
from spartan_tpu.examples import pagerank as pr  # noqa: E402
from spartan_tpu.expr.base import as_expr  # noqa: E402

SEED = 2 ** 33 + 12345  # wider than 32 bits, as the driver's are
TINY = {"kmeans_1m": {"n": 8192, "k": 8},
        "pagerank_1m": {"scale": 12, "edge_factor": 4},
        "dot_8192": {"n": 256, "check_rows": 64}}


@pytest.fixture(autouse=True)
def _program_state(monkeypatch, tmp_path):
    """A run initializes the program as the benchmark does: keep what it
    sets (the persistent compile cache, span recording) out of the other
    tests this worker runs."""
    from spartan_tpu.utils.config import FLAGS

    # set: st.initialize() then leaves JAX's compilation cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = FLAGS.trace, FLAGS.trace_ring
    yield
    FLAGS.trace, FLAGS.trace_ring = saved


def tiny_cell(workload: str):
    cell = harness.load_cell(harness.load_bench(), workload)
    cell.config.update(TINY[cell.config["name"]])
    return cell


def run(workload: str, control: bool = False) -> dict:
    return harness.run_cell(tiny_cell(workload), SEED, 0.3, False,
                            jax.devices(), control=control)


@pytest.mark.parametrize("workload", ["kmeans_1m.fit20",
                                      "pagerank_1m.rank10",
                                      "dot_8192.mesh2x2"])
def test_sound_run_is_correct(workload):
    line = run(workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"step_ms", "setup_s"}


@pytest.mark.parametrize("workload", ["kmeans_1m.fit20",
                                      "pagerank_1m.rank10",
                                      "dot_8192.mesh2x2"])
def test_control_is_not_correct(workload):
    assert not run(workload, control=True)["correct"]


# -- faults planted in the program ---------------------------------------------


def _kmeans_unchanged(orig):
    def fit(points, k, num_iter=10, centers=None, **kw):
        c = np.asarray(centers, np.float32)
        return c, km.assign_points(points, as_expr(c)).glom()
    return fit


def _kmeans_half(orig):
    def fit(points, k, num_iter=10, centers=None, **kw):
        half = points[: points.shape[0] // 2]
        c, _ = orig(half, k, num_iter=num_iter, centers=centers)
        return c, km.assign_points(points, as_expr(c)).glom()
    return fit


def _kmeans_altered(orig):
    def fit(points, k, num_iter=10, centers=None, **kw):
        c, a = orig(points, k, num_iter=num_iter, centers=centers)
        a = np.array(a)
        a[0] = (a[0] + 1) % k
        return c, a
    return fit


@pytest.mark.parametrize("fault", [_kmeans_unchanged, _kmeans_half,
                                   _kmeans_altered])
def test_kmeans_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(km, "kmeans", fault(km.kmeans))
    assert not run("kmeans_1m.fit20")["correct"]


def _pagerank_unchanged(orig):
    def rank(links, damping=0.85, num_iter=20, **kw):
        n = links.shape[0]
        return np.full((n,), 1.0 / n, np.float32)
    return rank


def _pagerank_half(orig):
    def rank(links, damping=0.85, num_iter=20, **kw):
        keep = (jnp.arange(links.data.shape[0]) % 2 == 0)
        half = st.SparseDistArray(links.data * keep, links.rows,
                                  links.cols, links.shape, links.nnz,
                                  mesh=links.mesh)
        return orig(half, damping=damping, num_iter=num_iter)
    return rank


def _pagerank_altered(orig):
    def rank(links, damping=0.85, num_iter=20, **kw):
        r = np.array(orig(links, damping=damping, num_iter=num_iter))
        r[0] *= 1.01
        return r
    return rank


@pytest.mark.parametrize("fault", [_pagerank_unchanged, _pagerank_half,
                                   _pagerank_altered])
def test_pagerank_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(pr, "pagerank", fault(pr.pagerank))
    assert not run("pagerank_1m.rank10")["correct"]


def _dot_exchange_left_out(orig):
    """Each chip multiplies only the tiles it holds: no panel crosses
    a chip."""
    def dot(a, b, **kw):
        from jax.sharding import PartitionSpec as P

        from spartan_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.get_mesh()
        spec = P("x", "y")
        local = jax.jit(jax.shard_map(lambda x, y: x @ y, mesh=mesh,
                                      in_specs=(spec, spec),
                                      out_specs=spec))
        out = local(a.evaluate().jax_array, b.evaluate().jax_array)
        return as_expr(da.from_jax(out, tiling=st.Tiling(("x", "y")),
                                   mesh=mesh))
    return dot


def _dot_shard_altered(orig):
    """One chip's tile of the product is off by 1%."""
    def dot(a, b, **kw):
        n = a.shape[0]
        scale = np.ones((n, n), np.float32)
        scale[n // 2:, n // 2:] = 1.01
        return orig(a, b) * st.from_numpy(scale,
                                          tiling=st.Tiling(("x", "y")))
    return dot


@pytest.mark.parametrize("fault", [_dot_exchange_left_out,
                                   _dot_shard_altered])
def test_dot_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(st, "dot", fault(st.dot))
    assert not run("dot_8192.mesh2x2")["correct"]
