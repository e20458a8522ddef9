"""Public-API surface tests: multi-root exprs, file IO, status, the
driver entry points."""

import os
import tempfile

import numpy as np
import pytest

import spartan_tpu as st


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


def test_tuple_expr_single_jit():
    st.clear_compile_cache()
    x = st.from_numpy(np.ones((8, 8), np.float32))
    t = st.tuple_of(x + 1.0, (x * 2.0).sum(), x.T)
    a, b, c = t.glom()
    np.testing.assert_array_equal(a, np.full((8, 8), 2.0))
    np.testing.assert_allclose(b, 128.0)
    assert c.shape == (8, 8)
    assert st.compile_cache_size() == 1  # one program for all roots


def test_dict_expr():
    x = st.from_numpy(np.arange(16, dtype=np.float32).reshape(4, 4))
    d = st.dict_of(double=x * 2.0, total=x.sum())
    out = d.glom()
    assert set(out) == {"double", "total"}
    np.testing.assert_allclose(out["total"], 120.0)
    np.testing.assert_array_equal(out["double"][0], [0, 2, 4, 6])


def test_from_file_npy():
    x = np.random.RandomState(0).rand(8, 8).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npy")
        np.save(p, x)
        e = st.from_file(p)
        np.testing.assert_array_equal(e.glom(), x)


def test_save_load_roundtrip():
    x = np.random.RandomState(1).rand(8, 8).astype(np.float32)
    e = st.from_numpy(x) * 2.0
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "ckpt")
        st.save(p, e)
        back = st.load(p)
        np.testing.assert_allclose(back.glom(), x * 2, rtol=1e-6)


def test_status():
    s = st.status()
    assert s["num_devices"] == 8
    assert s["mesh"] == {"x": 4, "y": 2}
    assert s["process_count"] == 1


def test_initialize():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    leftover = st.initialize(["--log_level=1", "extra"])
    jax.config.update("jax_compilation_cache_dir", prev)
    assert leftover == ["extra"]
    assert st.FLAGS.log_level == 1
    st.FLAGS.reset_all()


def test_graft_entry_runs():
    import sys

    sys.path.insert(0, "/root/repo")
    try:
        import __graft_entry__ as g

        import jax

        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (16, 64)
        g.dryrun_multichip(8)
    finally:
        sys.path.pop(0)


def test_numpy_surface_complete():
    """The SURVEY §2.3 builtins list plus the round-5 additions are
    all reachable from the top-level namespace — the parity surface a
    reference user would reach for."""
    wanted = (
        # SURVEY's named list
        "zeros ones rand randn arange astype ravel sum mean max min "
        "argmin argmax diag diagonal norm concatenate bincount tril "
        "triu scan "
        # operators / order statistics / contraction family
        "sort argsort median percentile quantile histogram unique topk "
        "unique_counts einsum tensordot matmul inner trace dot "
        "cumsum cumprod var std ptp take where linspace "
        # structure
        "from_numpy shuffle loop map map2 outer filter reshape "
        "transpose tuple_of dict_of build_mesh use_mesh initialize "
        "Tiling"
    ).split()
    missing = [name for name in wanted if not hasattr(st, name)]
    assert not missing, f"missing from spartan_tpu namespace: {missing}"
