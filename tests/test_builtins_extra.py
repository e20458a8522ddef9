"""Oracle tests for the extended NumPy-surface builtins (SURVEY.md §4:
NumPy is the universal oracle)."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


def _np_pair(shape=(8, 8), seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(*shape).astype(np.float32)
    return x, st.from_numpy(x)


def test_var_std_ptp():
    x, ex = _np_pair(seed=1)
    np.testing.assert_allclose(st.var(ex).glom(), np.var(x), rtol=1e-5)
    np.testing.assert_allclose(st.var(ex, axis=0).glom(), np.var(x, axis=0),
                               rtol=1e-5)
    np.testing.assert_allclose(st.var(ex, axis=1, ddof=1).glom(),
                               np.var(x, axis=1, ddof=1), rtol=1e-5)
    np.testing.assert_allclose(st.std(ex).glom(), np.std(x), rtol=1e-5)
    np.testing.assert_allclose(st.ptp(ex, axis=0).glom(), np.ptp(x, axis=0),
                               rtol=1e-6)


def test_cumsum_cumprod():
    x, ex = _np_pair(seed=2)
    np.testing.assert_allclose(st.cumsum(ex, axis=0).glom(),
                               np.cumsum(x, axis=0), rtol=1e-5)
    np.testing.assert_allclose(st.cumprod(ex, axis=1).glom(),
                               np.cumprod(x, axis=1), rtol=1e-5)


def test_take():
    x, ex = _np_pair(seed=3)
    idx = [0, 3, 5, 5, 1]
    np.testing.assert_allclose(st.take(ex, idx, axis=0).glom(),
                               np.take(x, idx, axis=0), rtol=1e-6)
    np.testing.assert_allclose(st.take(ex, idx).glom(), np.take(x, idx),
                               rtol=1e-6)


def test_linspace():
    np.testing.assert_allclose(st.linspace(0.0, 1.0, 16).glom(),
                               np.linspace(0, 1, 16, dtype=np.float32),
                               rtol=1e-6)
    np.testing.assert_allclose(
        st.linspace(2.0, 5.0, 9, endpoint=False).glom(),
        np.linspace(2, 5, 9, endpoint=False, dtype=np.float32), rtol=1e-6)


def test_unary_extras():
    x, ex = _np_pair(seed=4)
    np.testing.assert_allclose(st.log1p(ex).glom(), np.log1p(x), rtol=1e-6)
    np.testing.assert_allclose(st.expm1(ex).glom(), np.expm1(x), rtol=1e-6)
    np.testing.assert_allclose(st.log2(ex + 1).glom(), np.log2(x + 1),
                               rtol=1e-6)
    np.testing.assert_allclose(st.floor(ex * 10).glom(), np.floor(x * 10))
    np.testing.assert_allclose(st.ceil(ex * 10).glom(), np.ceil(x * 10))
    np.testing.assert_allclose(st.negative(ex).glom(), -x)
    np.testing.assert_allclose(st.reciprocal(ex + 1).glom(),
                               np.reciprocal(x + 1), rtol=1e-6)


def test_binary_named_ufuncs():
    x, ex = _np_pair(seed=5)
    y, ey = _np_pair(seed=6)
    np.testing.assert_allclose(st.add(ex, ey).glom(), x + y, rtol=1e-6)
    np.testing.assert_allclose(st.subtract(ex, ey).glom(), x - y, rtol=1e-6)
    np.testing.assert_allclose(st.multiply(ex, ey).glom(), x * y, rtol=1e-6)
    np.testing.assert_allclose(st.divide(ex, ey + 1).glom(), x / (y + 1),
                               rtol=1e-6)
    np.testing.assert_allclose(st.mod(ex * 10, ey + 1).glom(),
                               np.mod((x * 10).astype(np.float32), y + 1),
                               rtol=1e-4, atol=1e-5)


def test_comparisons_and_logical():
    x, ex = _np_pair(seed=7)
    y, ey = _np_pair(seed=8)
    assert np.array_equal(st.greater(ex, ey).glom(), x > y)
    assert np.array_equal(st.less_equal(ex, ey).glom(), x <= y)
    assert np.array_equal(st.not_equal(ex, ey).glom(), x != y)
    a, b = x > 0.5, y > 0.5
    ea, eb = st.greater(ex, 0.5), st.greater(ey, 0.5)
    assert np.array_equal(st.logical_and(ea, eb).glom(), a & b)
    assert np.array_equal(st.logical_or(ea, eb).glom(), a | b)
    assert np.array_equal(st.logical_xor(ea, eb).glom(), a ^ b)


def test_outer_product():
    rng = np.random.RandomState(9)
    u = rng.rand(12).astype(np.float32)
    v = rng.rand(7).astype(np.float32)
    out = st.outer_product(st.from_numpy(u), st.from_numpy(v)).glom()
    np.testing.assert_allclose(out, np.outer(u, v), rtol=1e-6)


def test_stencil_top_level():
    rng = np.random.RandomState(10)
    img = rng.rand(2, 8, 8, 1).astype(np.float32)
    out = st.maxpool(st.from_numpy(img), window=2, stride=2).glom()
    expect = img.reshape(2, 4, 2, 4, 2, 1).max(axis=(2, 4))
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def _np_conv_same(img, flt):
    """NHWC x HWIO convolution, stride 1, SAME padding (the low side
    takes the smaller half of an odd total, as XLA pads)."""
    kh, kw = flt.shape[:2]
    pads = [(0, 0)] + [((k - 1) // 2, k - 1 - (k - 1) // 2)
                       for k in (kh, kw)] + [(0, 0)]
    xp = np.pad(img.astype(np.float64), pads)
    h, w = img.shape[1:3]
    return sum(np.einsum("nhwc,co->nhwo", xp[:, i:i + h, j:j + w],
                         flt[i, j].astype(np.float64))
               for i in range(kh) for j in range(kw))


@pytest.mark.parametrize("h,k", [(32, 3), (36, 2)],
                         ids=["even_shard_odd_filter",
                              "odd_shard_even_filter"])
def test_stencil_h_sharded(h, k):
    """A SAME stencil over images whose H axis is sharded four ways
    (8- and 9-row shards) matches the NumPy convolution, and the output
    keeps the H sharding."""
    rng = np.random.RandomState(9)
    img = rng.rand(2, h, 16, 8).astype(np.float32)
    flt = rng.rand(k, k, 8, 4).astype(np.float32)
    x = st.from_numpy(img, tiling=tiling.Tiling((None, "x", None, None)))
    out = st.stencil(x, flt).evaluate()
    assert out.tiling.axes[1] == "x"
    np.testing.assert_allclose(out.glom(), _np_conv_same(img, flt),
                               rtol=1e-4, atol=1e-5)


def test_einsum_family(mesh2d):
    """einsum / tensordot / matmul / trace / inner vs NumPy oracles on
    sharded operands."""
    rng = np.random.RandomState(30)
    a = rng.rand(16, 8).astype(np.float32)
    b = rng.rand(8, 12).astype(np.float32)
    ea = st.from_numpy(a, tiling=tiling.row(2))
    eb = st.from_numpy(b, tiling=tiling.col(2))
    np.testing.assert_allclose(
        np.asarray(st.einsum("ij,jk->ik", ea, eb).glom()), a @ b,
        rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(st.einsum("ij->j", ea).glom()), a.sum(axis=0),
        rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(st.tensordot(ea, eb, axes=([1], [0])).glom()),
        np.tensordot(a, b, axes=([1], [0])), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(st.matmul(ea, eb).glom()), a @ b, rtol=1e-4)
    # batched matmul (>2-D) takes the traced path
    c = rng.rand(4, 8, 8).astype(np.float32)
    d = rng.rand(4, 8, 8).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(st.matmul(st.from_numpy(c), st.from_numpy(d)).glom()),
        c @ d, rtol=1e-4)
    sq = rng.rand(12, 12).astype(np.float32)
    np.testing.assert_allclose(
        float(st.trace(st.from_numpy(sq)).glom()), np.trace(sq),
        rtol=1e-5)
    v = rng.rand(32).astype(np.float32)
    w = rng.rand(32).astype(np.float32)
    np.testing.assert_allclose(
        float(st.inner(st.from_numpy(v), st.from_numpy(w)).glom()),
        np.inner(v, w), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(st.inner(ea, st.from_numpy(b.T)).glom()),
        np.inner(a, b.T), rtol=1e-4)


def test_einsum_cache_keys_on_subscripts(mesh2d):
    """Different subscripts on same-shaped operands must not collide
    in the compile cache."""
    rng = np.random.RandomState(31)
    a = rng.rand(8, 8).astype(np.float32)
    ea = st.from_numpy(a)
    s1 = np.asarray(st.einsum("ij->ji", ea).glom())
    s2 = np.asarray(st.einsum("ij->ij", ea).glom())
    np.testing.assert_array_equal(s1, a.T)
    np.testing.assert_array_equal(s2, a)


def test_quantile_matches_percentile(mesh1d):
    rng = np.random.RandomState(32)
    a = rng.rand(8192).astype(np.float32)
    fa = st.from_numpy(a, tiling=tiling.row(1))
    np.testing.assert_allclose(float(st.quantile(fa, 0.37).glom()),
                               np.quantile(a, 0.37), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(st.quantile(fa, [0.1, 0.9]).glom()),
        np.quantile(a, [0.1, 0.9]), rtol=1e-5)
    with pytest.raises(ValueError, match=r"outside \[0, 1\]"):
        st.quantile(fa, 37.0)


def test_histogram_oracle(mesh1d):
    """np.histogram parity: explicit range (edges a host constant,
    out-of-range dropped, right-closed last bin) and data-dependent
    range (min/max folded into the same program)."""
    rng = np.random.RandomState(33)
    a = (rng.rand(100_000) * 10 - 2).astype(np.float32)
    fa = st.from_numpy(a, tiling=tiling.row(1))
    # explicit range
    counts, edges = st.histogram(fa, bins=16, range=(0.0, 8.0))
    rc, re = np.histogram(a, bins=16, range=(0.0, 8.0))
    np.testing.assert_array_equal(np.asarray(counts.glom()), rc)
    np.testing.assert_allclose(np.asarray(edges.glom()), re, rtol=1e-6)
    # data-dependent range: edges match; counts may differ by boundary
    # ulps in f32 vs numpy's f64 bucketing — compare totals + near-all
    counts2, edges2 = st.histogram(fa, bins=12)
    rc2, re2 = np.histogram(a, bins=12)
    g2 = np.asarray(counts2.glom())
    np.testing.assert_allclose(np.asarray(edges2.glom()), re2,
                               rtol=1e-5)
    assert g2.sum() == a.size
    assert np.abs(g2 - rc2).sum() <= 8  # boundary-ulp tolerance
    # ints, exact
    b = rng.randint(0, 50, 10_000)
    cb, eb = st.histogram(st.from_numpy(b.astype(np.int32)), bins=10)
    rcb, reb = np.histogram(b, bins=10)
    np.testing.assert_array_equal(np.asarray(cb.glom()), rcb)
    # N-d input flattens (np.histogram semantics)
    m2 = rng.rand(16, 32).astype(np.float32)
    c2d, _ = st.histogram(st.from_numpy(m2), bins=8, range=(0.0, 1.0))
    np.testing.assert_array_equal(
        np.asarray(c2d.glom()),
        np.histogram(m2, bins=8, range=(0.0, 1.0))[0])


def test_histogram_data_range_exact(mesh1d):
    """Data-dependent range over normal samples: the counts equal
    np.histogram's over the returned edges' range, bin for bin."""
    x = np.random.RandomState(1).randn(2000).astype(np.float32)
    counts, edges = (a.glom() for a in st.histogram(x, bins=32))
    want, _ = np.histogram(x, bins=32, range=(edges[0], edges[-1]))
    np.testing.assert_array_equal(counts, want)


def test_bincount_out_of_range_ids(mesh1d):
    """Negative ids count in bucket 0 and ids >= length are dropped
    (jnp.bincount's rule), over a ragged length."""
    ids = np.random.RandomState(0).randint(-3, 14, 1003).astype(np.int32)
    keep = ids < 10
    want = np.bincount(np.maximum(ids[keep], 0), minlength=10)
    np.testing.assert_array_equal(st.bincount(ids, length=10).glom(), want)


def test_histogram_edge_cases(mesh1d):
    """Degenerate range (constant data) expands value +/- 0.5 like
    np.histogram; empty input returns zero counts over (0, 1); the
    explicit-range kernel's compile cache repeats across calls."""
    const = np.full(64, 7.0, np.float32)
    c, e = st.histogram(st.from_numpy(const), bins=10)
    rc, re = np.histogram(const, bins=10)
    np.testing.assert_array_equal(np.asarray(c.glom()), rc)
    np.testing.assert_allclose(np.asarray(e.glom()), re, rtol=1e-6)
    c2, e2 = st.histogram(st.from_numpy(np.empty(0, np.float32)),
                          bins=4)
    rc2, re2 = np.histogram(np.empty(0), bins=4)
    np.testing.assert_array_equal(np.asarray(c2.glom()), rc2)
    np.testing.assert_allclose(np.asarray(e2.glom()), re2, rtol=1e-6)
    # repeated identical explicit-range calls share one compiled program
    from spartan_tpu.expr import base as base_mod

    a = np.random.RandomState(34).rand(256).astype(np.float32)
    st.histogram(st.from_numpy(a), bins=8, range=(0.0, 1.0))[0].glom()
    size1 = len(base_mod._compile_cache)
    st.histogram(st.from_numpy(a), bins=8, range=(0.0, 1.0))[0].glom()
    assert len(base_mod._compile_cache) == size1


def test_histogram_explicit_range_edge_rules(mesh1d):
    """Explicit-range validation order + degenerate expansion: a
    reversed range raises even for empty input; lo == hi expands
    +/- 0.5 like np.histogram; returned edges agree with the
    bucketing for exact-edge values."""
    with pytest.raises(ValueError, match="max >= min"):
        st.histogram(st.from_numpy(np.empty(0, np.float32)), bins=4,
                     range=(5.0, 1.0))
    a = np.full(32, 5.0, np.float32)
    c, e = st.histogram(st.from_numpy(a), bins=10, range=(5.0, 5.0))
    rc, re = np.histogram(a, bins=10, range=(5.0, 5.0))
    np.testing.assert_array_equal(np.asarray(c.glom()), rc)
    np.testing.assert_allclose(np.asarray(e.glom()), re, rtol=1e-6)
    # a value exactly on a returned interior edge lands in the bin the
    # edges imply (shared edge formula between kernel and output)
    edges = np.asarray(st.histogram(st.from_numpy(
        np.zeros(1, np.float32)), bins=7, range=(0.0, 1.0))[1].glom())
    probe = np.full(16, edges[3], np.float32)
    counts = np.asarray(st.histogram(st.from_numpy(probe), bins=7,
                                     range=(0.0, 1.0))[0].glom())
    assert counts[3] == 16 and counts.sum() == 16


def test_histogram_range_max_and_nan_bounds(mesh1d):
    """A value exactly equal to the range max lands in the closed
    last bin (endpoint pinned exactly); NaN/inf range bounds raise."""
    hi = 16.066476821899414
    a = np.array([np.float32(hi)] * 8, np.float32)
    c, e = st.histogram(st.from_numpy(a), bins=7,
                        range=(-81.8493881225586, hi))
    got = np.asarray(c.glom())
    assert got[6] == 8 and got.sum() == 8
    for bad in ((np.nan, 1.0), (0.0, np.inf)):
        with pytest.raises(ValueError, match="finite"):
            st.histogram(st.from_numpy(a), bins=4, range=bad)


def test_take_and_tensordot_validate():
    """Out-of-range take indices and over-rank tensordot axes raise
    clearly, numpy-style, instead of clamping or an opaque IndexError
    (round-5 misuse audit)."""
    x, ex = _np_pair(seed=40)
    with pytest.raises(IndexError, match="out of bounds"):
        st.take(ex, [100], axis=0)
    with pytest.raises(IndexError, match="out of bounds"):
        st.take(ex, [-9], axis=1)
    # negative indices in range still work (numpy semantics)
    np.testing.assert_allclose(
        np.asarray(st.take(ex, [-1, 0], axis=0).glom()),
        np.take(x, [-1, 0], axis=0), rtol=1e-6)
    with pytest.raises(ValueError, match="exceeds operand ranks"):
        st.tensordot(ex, ex, axes=3)


def test_take_scalar_axis_errors():
    with pytest.raises(ValueError, match="out of range"):
        st.take(st.from_numpy(np.float32(3.0)), [0], axis=0)
