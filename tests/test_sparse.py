"""Sparse array + segment kernel tests (config 5 substrate)."""

import jax
import numpy as np
import pytest

from spartan_tpu.array.sparse import SparseDistArray
from spartan_tpu.ops import segment as segment_mod
from spartan_tpu.ops.segment import segment_count, segment_sum


@pytest.fixture(autouse=True)
def _mesh(mesh1d):
    yield


def _random_sparse(n=20, m=16, density=0.2, seed=0):
    rng = np.random.RandomState(seed)
    dense = rng.rand(n, m) * (rng.rand(n, m) < density)
    return dense.astype(np.float32)


def _uniform_stream(rng, k):
    return (rng.rand(500, 8).astype(np.float32),
            rng.randint(0, k, 500))


def _int_valued_oob_stream(rng, k):
    """Integer-valued floats (every sum exact in f32) with ids out of
    range at both ends, which the merge must drop."""
    return (rng.randint(-8, 9, (1000, 16)).astype(np.float32),
            rng.randint(-2, k + 4, 1000))


def _add_at(vals, ids, k):
    keep = (ids >= 0) & (ids < k)
    expect = np.zeros((k,) + vals.shape[1:], vals.dtype)
    np.add.at(expect, ids[keep], vals[keep])
    return expect


_ABOVE = segment_mod._ONEHOT_MAX_SEGMENTS + 1


def _impl_case(stream, k, ndim):
    name = "uniform" if stream is _uniform_stream else "int_valued_oob"
    return pytest.param(stream, k, ndim, id=(
        name if (k, ndim) == (16, 2) else
        f"{name}-{ndim}d-{'above' if k == _ABOVE else k}"))


@pytest.mark.parametrize("stream,k,ndim", [
    _impl_case(stream, k, ndim)
    for stream in (_uniform_stream, _int_valued_oob_stream)
    for k in (1, 16, segment_mod._ONEHOT_MAX_SEGMENTS, _ABOVE)
    for ndim in (2, 1)])
def test_segment_sum_impls(stream, k, ndim):
    """Both lowerings, the one-hot product (k up to the bound) and the
    scatter (above it), against np.add.at."""
    import jax.numpy as jnp

    vals, ids = stream(np.random.RandomState(0), k)
    if ndim == 1:
        vals = vals[:, 0]
    expect = _add_at(vals, ids, k)
    out = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), k))
    if stream is _int_valued_oob_stream:
        np.testing.assert_array_equal(out.view(np.uint32),
                                      expect.view(np.uint32))
    else:
        np.testing.assert_allclose(out, expect, rtol=1e-5)
    keep = (ids >= 0) & (ids < k)
    cnt = np.asarray(segment_count(jnp.asarray(ids), k))
    np.testing.assert_array_equal(cnt, np.bincount(ids[keep],
                                                   minlength=k))


def _nonfinite_rows():
    vals = np.arange(40, dtype=np.float32).reshape(10, 4)
    vals[1, 0], vals[4, 1], vals[6, 2] = np.inf, -np.inf, np.nan
    vals[7, 0] = np.inf            # +inf and -inf meet in segment 1
    vals[9, 1] = -np.inf
    return vals, np.array([0, 1, 2, 3, 0, 1, 2, 3, 0, 1]), 4


def _nonfinite_oob():
    vals = np.ones((6, 3), np.float32)
    vals[2, 1] = np.inf
    return vals, np.array([0, 1, 5, 2, 1, -1]), 3


def _nonfinite_one_segment():
    vals = np.arange(12, dtype=np.float32).reshape(6, 2)
    vals[3, 0] = np.inf
    return vals, np.zeros(6, np.int64), 1


@pytest.mark.parametrize("case", [_nonfinite_rows, _nonfinite_oob,
                                  _nonfinite_one_segment],
                         ids=["inf_nan_rows", "oob_inf", "k1_inf"])
def test_segment_sum_nonfinite_exact(case):
    """Non-finite values on the one-hot side give the scatter's answer
    bit for bit: infinities, NaNs, an infinity on a dropped id, and one
    segment."""
    import jax.numpy as jnp

    vals, ids, k = case()
    assert k <= segment_mod._ONEHOT_MAX_SEGMENTS
    out = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), k))
    np.testing.assert_array_equal(out.view(np.uint32),
                                  _add_at(vals, ids, k).view(np.uint32))


@pytest.mark.parametrize("k,dtype,onehot", [
    (16, np.float32, 1), (_ABOVE, np.float32, 0), (16, np.int32, 0)],
    ids=["f32_few", "f32_above", "int32"])
def test_segment_sum_lowering_by_shape(k, dtype, onehot):
    """float32 values over at most ``_ONEHOT_MAX_SEGMENTS`` segments
    lower to the one-hot product, counted once a lowering; more
    segments and integer values keep the scatter."""
    import jax
    import jax.numpy as jnp

    from spartan_tpu.utils import profiling as prof

    rng = np.random.RandomState(1)
    vals = rng.randint(-5, 6, (300, 4)).astype(dtype)
    ids = rng.randint(-1, k + 1, 300)
    fn = jax.jit(lambda v, i: segment_sum(v, i, k))
    prof.reset_counters()
    txt = fn.lower(jnp.asarray(vals), jnp.asarray(ids)).as_text()
    assert prof.counters().get("segment_sum_onehot", 0) == onehot
    assert ("dot_general" in txt) == bool(onehot)
    out = np.asarray(fn(jnp.asarray(vals), jnp.asarray(ids)))
    assert out.dtype == dtype
    np.testing.assert_array_equal(out, _add_at(vals, ids, k))


def test_segment_sum_out_of_range_dropped():
    import jax.numpy as jnp

    vals = np.ones((4,), np.float32)
    ids = np.array([0, 1, 7, 3])
    out = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), 3))
    np.testing.assert_array_equal(out, [1, 1, 0])


def test_sparse_roundtrip():
    dense = _random_sparse()
    sp = SparseDistArray.from_dense(dense)
    assert sp.nnz == np.count_nonzero(dense)
    assert sp.nse % 8 == 0  # padded to the mesh
    np.testing.assert_allclose(sp.glom(), dense, rtol=1e-6)


def test_sparse_from_coo_sorting():
    rows = np.array([3, 0, 2, 0])
    cols = np.array([1, 2, 0, 0])
    data = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    sp = SparseDistArray.from_coo(rows, cols, data, (4, 3))
    expect = np.zeros((4, 3), np.float32)
    expect[rows, cols] = data
    np.testing.assert_allclose(sp.glom(), expect)


def test_spmv():
    dense = _random_sparse(24, 16, seed=1)
    sp = SparseDistArray.from_dense(dense)
    x = np.random.RandomState(2).rand(16).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sp.spmv(x)), dense @ x,
                               rtol=1e-4, atol=1e-5)
    # matrix rhs
    xm = np.random.RandomState(3).rand(16, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sp.spmv(xm)), dense @ xm,
                               rtol=1e-4, atol=1e-5)


def test_spmv_unknown_impl_raises():
    sp = SparseDistArray.from_dense(_random_sparse(8, 8, seed=6))
    with pytest.raises(ValueError, match="unknown spmv impl"):
        sp.spmv(np.ones(8, np.float32), impl="xla")


def test_sparse_transpose_rsums_scale():
    dense = _random_sparse(12, 8, seed=4)
    sp = SparseDistArray.from_dense(dense)
    np.testing.assert_allclose(sp.T.glom(), dense.T, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sp.rsums()), dense.sum(1),
                               rtol=1e-5, atol=1e-6)
    scale = np.arange(12, dtype=np.float32)
    np.testing.assert_allclose(sp.scale_rows(scale).glom(),
                               dense * scale[:, None], rtol=1e-6)


def test_bcoo_bridge():
    import jax.experimental.sparse as jsparse

    dense = _random_sparse(10, 10, seed=5)
    sp = SparseDistArray.from_dense(dense)
    bcoo = sp.to_bcoo()
    np.testing.assert_allclose(np.asarray(bcoo.todense()), dense,
                               rtol=1e-6)


def test_from_coo_duplicate_entries_sum():
    """COO semantics: duplicate (row, col) entries sum (scipy-compatible);
    the BCOO bridge's unique_indices claim must therefore be true."""
    import scipy.sparse as sp

    rows = [0, 0, 1, 0]
    cols = [5, 2, 3, 5]   # (0,5) duplicated
    data = [1.0, 2.0, 3.0, 4.0]
    a = SparseDistArray.from_coo(rows, cols, data, (2, 8))
    want = sp.coo_matrix((data, (rows, cols)), shape=(2, 8)).toarray()
    np.testing.assert_allclose(a.glom(), want)
    # spmv agrees through both the BCOO and segment paths
    x = np.arange(8, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(a.spmv(x)), want @ x, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a.spmv(x, impl="bcoo")),
                               want @ x, rtol=1e-5)


def test_from_coo_lex_sorted_with_padding():
    rng = np.random.RandomState(0)
    n, m, k = 32, 16, 100
    rows = rng.randint(0, n, k)
    cols = rng.randint(0, m, k)
    data = rng.rand(k).astype(np.float32)
    a = SparseDistArray.from_coo(rows, cols, data, (n, m), pad_to=128)
    r = np.asarray(jax.device_get(a.rows)).astype(np.int64)
    c = np.asarray(jax.device_get(a.cols)).astype(np.int64)
    flat = r * m + c
    assert (np.diff(flat) > 0).all()  # strictly sorted incl. padding
    import scipy.sparse as sp
    want = sp.coo_matrix((data, (rows, cols)), shape=(n, m)).toarray()
    np.testing.assert_allclose(a.glom(), want, rtol=1e-5)


import jax.numpy as jnp


def _plan_segment_sum(ids, n, vals):
    """A plain segment-sum through a SegmentPlan (one column) and the
    windowed merge kernel: interpret mode on CPU, Mosaic on TPU."""
    from spartan_tpu.ops.segment import SegmentPlan, windowed_merge

    plan = SegmentPlan(ids, n, cols=np.zeros(len(ids), np.int32),
                       num_cols=1)
    return np.asarray(jax.device_get(windowed_merge(
        jnp.asarray(plan.reorder(vals)), plan._ids2d, plan._wb,
        plan.dims)))


def test_segment_plan_windowed():
    """Windowed sorted-segment kernel vs numpy oracle (interpret mode on
    CPU; the real Mosaic kernel on TPU)."""
    rng = np.random.RandomState(3)
    n, e = 3000, 20000
    ids = np.sort(rng.randint(0, n, size=e).astype(np.int32))
    vals = rng.rand(e).astype(np.float32)
    out = _plan_segment_sum(ids, n, vals)
    expect = np.zeros(n, np.float32)
    np.add.at(expect, ids, vals)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=1e-5)


def test_segment_plan_drops_out_of_range():
    ids = np.array([0, 1, 1, 5, 7, 9, 9], np.int32)
    vals = np.arange(1, 8, dtype=np.float32)
    out = _plan_segment_sum(ids, 6, vals)  # ids 7, 9, 9 out of range
    expect = np.zeros(6, np.float32)
    np.add.at(expect, ids[ids < 6], vals[ids < 6])
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_spmv_windowed_matches_oracle():
    import scipy.sparse as sp

    from spartan_tpu.parallel import mesh as mesh_mod

    rng = np.random.RandomState(4)
    n = 2 * 16384 + 700  # three column windows, the last partial
    k = 20_000
    mat = sp.coo_matrix((rng.rand(k), (rng.randint(0, n, k),
                                       rng.randint(0, n, k))), shape=(n, n))
    # the windowed kernel is single-device by design; build on a
    # 1-device mesh so the _can_window() guard passes honestly
    m1 = mesh_mod.build_mesh(jax.devices()[:1])
    with mesh_mod.use_mesh(m1):
        a = SparseDistArray.from_scipy(mat)
        x = rng.rand(n).astype(np.float32)
        y = np.asarray(jax.device_get(a.spmv(x, impl="windowed")))
    np.testing.assert_allclose(y, mat.tocsr() @ x, rtol=1e-4, atol=1e-6)


def _gather_graph(n_cols, seed):
    """Sorted rows over 4 output windows with window 1 empty, random
    columns over ``n_cols``, and one hot column that fills many groups."""
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, 4096, 3000)
    rows = rows[(rows // 1024) != 1]
    cols = rng.randint(0, n_cols, rows.size)
    hot_rows = rng.randint(2048, 3072, 1500)
    rows = np.concatenate([rows, hot_rows])
    cols = np.concatenate([cols, np.full(hot_rows.size, n_cols - 1)])
    order = np.argsort(rows, kind="stable")
    return rows[order].astype(np.int32), cols[order].astype(np.int32)


@pytest.mark.parametrize("n_cols", [700, 16384, 2 * 16384 + 1, 50_003])
def test_windowed_gather_bit_exact(n_cols):
    """The one-hot MXU gather returns ``data * x[cols]`` bit for bit
    (interpret mode here), at fewer columns than one window, exactly
    one, one past two, and a ragged count."""
    from spartan_tpu.kernels.segment import windowed_gather
    from spartan_tpu.ops.segment import SegmentPlan

    rows, cols = _gather_graph(n_cols, seed=n_cols)
    rng = np.random.RandomState(1)
    # values over many binades, so all three bf16 parts carry bits
    x = (rng.randn(n_cols) * 10.0 ** rng.uniform(-20, 20, n_cols)
         ).astype(np.float32)
    data = rng.rand(rows.size).astype(np.float32)
    plan = SegmentPlan(rows, 4096, cols=cols, num_cols=n_cols)
    got = windowed_gather(jnp.asarray(x), plan._lcols, plan._gwin,
                          jnp.asarray(plan.reorder(data)))
    want = plan.reorder(data * x[cols])
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), want)


def test_segment_plan_gather_layout():
    """Each 1024-entry subblock lies in one output window, each
    128-entry group in one column window, and the stream is a
    permutation of the valid entries with padding at data 0."""
    from spartan_tpu.ops.segment import SegmentPlan as SP

    n_cols = 3 * SP.CW + 5
    rows, cols = _gather_graph(n_cols, seed=7)
    rows = np.concatenate([[-2], rows, [4096, 5000]]).astype(np.int32)
    cols = np.concatenate([[0], cols, [1, 2]]).astype(np.int32)
    plan = SP(rows, 4096, cols=cols, num_cols=n_cols)
    valid = slice(1, rows.size - 2)
    e = rows[valid].size
    assert np.unique(plan.perm).size == e == plan.perm.size
    assert plan.padded_size % (SP.GB * SP.GR) == 0
    assert plan.groups * SP.GB == plan.padded_size
    slot_row = np.full(plan.padded_size, -1)
    slot_row[plan.perm] = rows[valid]
    slot_col = np.full(plan.padded_size, -1)
    slot_col[plan.perm] = cols[valid]
    wb = np.asarray(plan._wb)
    filled = slot_row >= 0
    assert (slot_row[filled] // SP.W
            == wb[np.flatnonzero(filled) // SP.EB]).all()
    gwin = np.asarray(plan._gwin).reshape(-1)
    lcols = np.asarray(plan._lcols).reshape(-1)
    assert (slot_col[filled] // SP.CW
            == gwin[np.flatnonzero(filled) // SP.GB]).all()
    np.testing.assert_array_equal(lcols[filled], slot_col[filled] % SP.CW)
    # padding slots: data 0, and a column inside the x windows
    pdata = plan.reorder(np.arange(1, rows.size + 1, dtype=np.float32))
    np.testing.assert_array_equal(pdata[plan.perm], np.arange(2, e + 2))
    assert (pdata[~filled] == 0).all()
    assert (gwin < -(-n_cols // SP.CW)).all()
    assert ((lcols >= 0) & (lcols < SP.CW)).all()
    assert (np.asarray(plan._ids2d).reshape(-1)[~filled] == SP.W).all()


def test_segment_plan_partial_trailing_block():
    """Regression: num_segments not a multiple of the flush block size
    (131072 elements) must still flush the trailing partial block."""
    n = 140000
    ids = np.array([5, 139999], np.int32)
    vals = np.array([1.5, 2.0], np.float32)
    out = _plan_segment_sum(ids, n, vals)
    assert out[5] == pytest.approx(1.5)
    assert out[139999] == pytest.approx(2.0)
    assert out.sum() == pytest.approx(3.5)


def test_segment_plan_skewed_ids_flush_after_accumulate():
    """Regression: heavily skewed ids (all entries in the first output
    block, more entry steps than output blocks) must not lose the
    contributions of late grid steps."""
    n = 256 * 1024
    e = 24576  # 3 subblock steps of entries, all into segment 0
    ids = np.zeros(e, np.int32)
    vals = np.ones(e, np.float32)
    out = _plan_segment_sum(ids, n, vals)
    assert out[0] == pytest.approx(e)
    assert out[1:].sum() == pytest.approx(0.0)


def test_segment_plan_drops_negative_ids():
    """Regression (ADVICE r1): negative ids are dropped like
    jax.ops.segment_sum drops them, not crashed on in bincount."""
    ids = np.array([-3, -1, 0, 2, 2, 5, 9], np.int32)
    vals = np.arange(1, 8, dtype=np.float32)
    out = _plan_segment_sum(ids, 6, vals)  # -3, -1 and 9 out of range
    keep = (ids >= 0) & (ids < 6)
    expect = np.zeros(6, np.float32)
    np.add.at(expect, ids[keep], vals[keep])
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_spmv_windowed_forced_unavailable_raises(mesh2d):
    """Regression (ADVICE r1): forcing impl='windowed' on a multi-device
    mesh must fail fast, not silently gather to host."""
    a = SparseDistArray.from_dense(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError, match="windowed"):
        a.spmv(np.ones(8, np.float32), impl="windowed")


@pytest.mark.parametrize("shape,ok", [
    ((2 << 20, 8 << 20), True),        # both bounds: rows 2M, columns 8M
    ((1024, (8 << 20) + 1), False),    # x's parts past the gather's VMEM
    (((2 << 20) + 1, 16), False),      # the merge's output past VMEM
])
def test_windowed_shape_bounds(shape, ok):
    """The windowed path's structural bounds on one device: rows by the
    merge's VMEM-resident output, columns by the gather's VMEM-resident
    x (``SegmentPlan.MAX_COLS``); past either, the default is BCOO and
    a forced impl='windowed' fails fast."""
    from spartan_tpu.parallel import mesh as mesh_mod

    m1 = mesh_mod.build_mesh(jax.devices()[:1])
    with mesh_mod.use_mesh(m1):
        a = SparseDistArray.from_coo(np.array([0, 5]), np.array([1, 7]),
                                     np.ones(2), shape)
        assert a._can_window() == ok
        if not ok:
            assert a.default_impl() == "bcoo"
            with pytest.raises(ValueError, match="columns"):
                a.spmv(jnp.ones(shape[1], jnp.float32), impl="windowed")


def test_transition_cached_and_clearable():
    """links.transition() caches; clear_cache() releases it."""
    links = SparseDistArray.from_dense(np.array(
        [[0, 1, 1], [1, 0, 0], [0, 0, 0]], np.float32))
    t1 = links.transition()
    assert links.transition() is t1
    # column-stochastic: each column with in-links sums to the source's
    # 1/outdegree contributions
    dense = np.asarray(t1.glom())
    np.testing.assert_allclose(dense.sum(axis=0), [1.0, 1.0, 0.0],
                               rtol=1e-6)
    links.clear_cache()
    assert links.transition() is not t1


# -- multi-chip sparse (VERDICT r1 #4) -----------------------------------


def test_sparse_entries_genuinely_sharded(mesh1d):
    """Entries must really live sharded over the mesh's entry axis —
    one distinct shard per device, together covering nse."""
    import scipy.sparse as sp

    rng = np.random.RandomState(7)
    mat = sp.random(64, 64, density=0.05, random_state=rng, format="coo")
    a = SparseDistArray.from_scipy(mat)
    shards = a.data.addressable_shards
    assert len({s.device for s in shards}) == 8
    sizes = [int(s.data.shape[0]) for s in shards]
    assert sum(sizes) == a.nse
    assert max(sizes) - min(sizes) == 0  # padded to an even split


@pytest.mark.parametrize("fixture", ["mesh1d", "mesh2d"])
def test_spmv_sharded_matches_oracle(fixture, request):
    """The explicit segment-sum+psum SpMV is the multi-device default
    and matches scipy on 8x1 and 4x2 meshes (the 4x2 case exercises
    entry replication over the unused y axis)."""
    import scipy.sparse as sp

    request.getfixturevalue(fixture)
    rng = np.random.RandomState(8)
    n = 96
    mat = sp.random(n, n, density=0.03, random_state=rng, format="coo")
    a = SparseDistArray.from_scipy(mat)
    x = rng.rand(n).astype(np.float32)
    y_default = np.asarray(jax.device_get(a.spmv(x)))
    y_forced = np.asarray(jax.device_get(a.spmv(x, impl="sharded")))
    expect = mat.tocsr() @ x
    np.testing.assert_allclose(y_default, expect, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(y_forced, expect, rtol=1e-4, atol=1e-6)
    # matrix operand (n, d)
    X = rng.rand(n, 3).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(a.spmv(X, impl="sharded"))),
        mat.tocsr() @ X, rtol=1e-4, atol=1e-6)


def test_rsums_sharded(mesh2d):
    import scipy.sparse as sp

    rng = np.random.RandomState(9)
    mat = sp.random(40, 30, density=0.1, random_state=rng, format="coo")
    a = SparseDistArray.from_scipy(mat)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(a.rsums())),
        np.asarray(mat.tocsr().sum(axis=1)).ravel(), rtol=1e-5)


def test_pagerank_multichip(mesh1d):
    """PageRank through the sharded SpMV path (no windowed kernel on a
    multi-device mesh) reproduces the star-graph structure."""
    from spartan_tpu.examples.pagerank import pagerank

    n = 8
    rows = np.concatenate([np.arange(1, n), [0]])
    cols = np.concatenate([np.zeros(n - 1, np.int64), [1]])
    links = SparseDistArray.from_coo(rows, cols,
                                     np.ones(n, np.float32), (n, n))
    ranks = pagerank(links, num_iter=40)
    assert ranks.argmax() == 0
    assert ranks[1] > ranks[2]
    np.testing.assert_allclose(ranks.sum(), 1.0, rtol=1e-3)


def test_transpose_no_host_roundtrip(monkeypatch):
    """Round-3 verdict Weak #4 done-criterion: transpose() performs no
    device_get — the re-sort runs entirely on device."""
    dense = _random_sparse(24, 16, seed=11)
    sp = SparseDistArray.from_dense(dense)
    calls = {"n": 0}
    real = jax.device_get

    def counting_get(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(jax, "device_get", counting_get)
    spt = sp.transpose()
    monkeypatch.undo()
    assert calls["n"] == 0, f"transpose did {calls['n']} device_gets"
    np.testing.assert_allclose(spt.glom(), dense.T, rtol=1e-6)


def test_transpose_scipy_oracle_padding_and_claims():
    """Transpose of a padded matrix: entries stay (row, col)-sorted,
    unique, with padding out of range — and match scipy exactly."""
    import scipy.sparse as ss

    rng = np.random.RandomState(12)
    n, m = 30, 17
    nnz = 60
    r = rng.randint(0, n, nnz)
    c = rng.randint(0, m, nnz)
    v = rng.rand(nnz).astype(np.float32)
    sp = SparseDistArray.from_coo(r, c, v, (n, m), pad_to=128)
    spt = sp.transpose()
    oracle = ss.coo_matrix((v, (r, c)), shape=(n, m)).toarray().T
    np.testing.assert_allclose(spt.glom(), oracle, rtol=1e-6)
    rows = np.asarray(jax.device_get(spt.rows)).astype(np.int64)
    cols = np.asarray(jax.device_get(spt.cols)).astype(np.int64)
    flat = rows * n + cols
    assert (np.diff(flat) > 0).all(), "entries not strictly sorted"
    assert (rows[spt.nnz:] >= m).all(), "padding rows in range"
    # double transpose round-trips
    np.testing.assert_allclose(spt.transpose().glom(),
                               oracle.T, rtol=1e-6)


def test_mesh_fn_cache_bounded():
    """Round-3 verdict Weak #6: equivalent transient meshes share one
    compiled-executable cache entry instead of accumulating."""
    from spartan_tpu.array import sparse as sparse_mod
    from spartan_tpu.parallel import mesh as mesh_mod

    dense = _random_sparse(16, 16, seed=13)
    before = len(sparse_mod._sharded_spmv_fn)
    x = np.ones(16, np.float32)
    for _ in range(12):  # fresh equivalent Mesh each iteration
        m = mesh_mod.build_mesh(jax.devices(), shape=(8, 1))
        with mesh_mod.use_mesh(m):
            sp = SparseDistArray.from_dense(dense, mesh=m)
            sp.spmv(x, impl="sharded")
    after = len(sparse_mod._sharded_spmv_fn)
    assert after - before <= 1, \
        f"cache grew by {after - before} for equivalent meshes"


def test_from_coo_device_no_host_roundtrip(monkeypatch):
    """Device-side construction: dedup/sort/pad on device, scipy
    oracle, zero jax.device_get calls."""
    import jax.numpy as jnp
    import scipy.sparse as ss

    rng = np.random.RandomState(14)
    n, m, nnz = 25, 18, 90  # heavy duplication: ~5 entries per coord
    r = rng.randint(0, 5, nnz)
    c = rng.randint(0, 4, nnz)
    v = rng.rand(nnz).astype(np.float32)
    calls = {"n": 0}
    real = jax.device_get

    def counting_get(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(jax, "device_get", counting_get)
    sp = SparseDistArray.from_coo_device(
        jnp.asarray(r), jnp.asarray(c), jnp.asarray(v), (n, m))
    monkeypatch.undo()
    assert calls["n"] == 0, f"from_coo_device did {calls['n']} gets"
    oracle = ss.coo_matrix((v, (r, c)), shape=(n, m)).toarray()
    np.testing.assert_allclose(sp.glom(), oracle, rtol=1e-5)
    # canonical claims hold: sorted, unique, padding out of range
    rows = np.asarray(jax.device_get(sp.rows)).astype(np.int64)
    cols = np.asarray(jax.device_get(sp.cols)).astype(np.int64)
    flat = rows * m + cols
    assert (np.diff(flat) > 0).all()
    assert sp.nnz == len(np.unique(r * m + c))
    assert (rows[sp.nnz:] >= n).all()
    # and it composes with the device transpose + spmv paths
    x = np.ones(m, np.float32)
    np.testing.assert_allclose(np.asarray(sp.spmv(x, impl="sharded")),
                               oracle @ x, rtol=1e-5)
