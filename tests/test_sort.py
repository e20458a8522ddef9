"""Distributed 1-D sample sort vs the NumPy oracle (SURVEY.md §2.3
misc ops: the reference's sampling-based distributed sort; round-3
verdict Missing #2). Exercises the full collective pipeline — splitter
sampling, all_to_all bucket exchange, local merge, rebalance — on the
8-virtual-device mesh, including heavy skew (the case splitter
sampling exists for)."""


import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling
from spartan_tpu.expr.builtins import SampleSortExpr
from spartan_tpu.parallel import mesh as mesh_mod


def test_sample_sort_oracle_1m(mesh1d):
    rng = np.random.RandomState(0)
    a = rng.rand(1_048_576).astype(np.float32)
    e = st.sort(st.from_numpy(a, tiling=tiling.row(1)))
    assert isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sample_sort_skewed(mesh1d):
    """Zipf-ish skew + heavy duplication: most elements land in few
    buckets — the capacity-safe exchange must still be exact."""
    rng = np.random.RandomState(1)
    a = np.concatenate([
        np.zeros(40_000, np.float32),            # 40% identical
        rng.zipf(1.5, 40_000).astype(np.float32),  # heavy tail
        rng.rand(48_000).astype(np.float32) * 1e-3,  # dense cluster
    ])
    rng.shuffle(a)
    e = st.sort(st.from_numpy(a, tiling=tiling.row(1)))
    assert isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sample_sort_int_dtype(mesh1d):
    rng = np.random.RandomState(2)
    a = rng.randint(-1000, 1000, size=64_000).astype(np.int32)
    e = st.sort(st.from_numpy(a, tiling=tiling.row(1)))
    np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sample_sort_output_sharded(mesh1d):
    """The result stays row-sharded — no device holds the full array."""
    rng = np.random.RandomState(3)
    a = rng.rand(8192).astype(np.float32)
    out = st.sort(st.from_numpy(a, tiling=tiling.row(1))).evaluate()
    shards = out.jax_array.addressable_shards
    assert len({s.device for s in shards}) == 8
    assert all(s.data.shape == (1024,) for s in shards)


def test_sample_sort_2d_mesh(mesh2d):
    """On the 4x2 mesh the row axis (4 devices) carries the sort."""
    rng = np.random.RandomState(4)
    a = rng.rand(32_768).astype(np.float32)
    e = st.sort(st.from_numpy(a, tiling=tiling.row(1)))
    assert isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sort_non_divisible_distributed(mesh1d):
    """n % p != 0 stays on the distributed path (round-4 verdict #3):
    ragged tails ride the validity channel instead of gathering."""
    rng = np.random.RandomState(5)
    for n in (1001, 8191, 8193):
        a = rng.rand(n).astype(np.float32)
        e = st.sort(st.from_numpy(a))
        assert isinstance(e, SampleSortExpr)
        np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sample_sort_1m_ragged(mesh1d):
    """Oracle at 1M +/- 7 elements — the verdict's named done-bar."""
    rng = np.random.RandomState(55)
    for n in (1_048_576 - 7, 1_048_576 + 7):
        a = rng.rand(n).astype(np.float32)
        e = st.sort(st.from_numpy(a))
        assert isinstance(e, SampleSortExpr)
        np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sample_sort_tiny_ragged(mesh1d):
    """n < p and n barely above p: fully-padded shards must not
    corrupt splitters or counts."""
    rng = np.random.RandomState(56)
    for n in (1, 3, 7, 9, 17):
        a = rng.rand(n).astype(np.float32)
        e = st.sort(st.from_numpy(a))
        np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a))


def test_sort_2d_local_axis_unchanged(mesh1d):
    """ndim > 1 with the sort axis UNSHARDED keeps the traced per-axis
    sort (local under GSPMD — nothing to distribute)."""
    rng = np.random.RandomState(6)
    a = rng.rand(16, 8).astype(np.float32)
    e = st.sort(st.from_numpy(a, tiling=tiling.row(2)), axis=1)
    assert not isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()), np.sort(a, axis=1))


def test_sort_axis_sharded_no_gather(mesh1d):
    """(64, n) sorted along a SHARDED axis 1: distributed batched
    kernel, oracle-exact, and the compiled HLO moves no full-array
    all-gather (collective census — round-4 verdict #3 done-bar)."""
    import re

    from spartan_tpu.utils import profiling

    rng = np.random.RandomState(60)
    n = 65_536
    a = rng.rand(64, n).astype(np.float32)
    t = tiling.Tiling((None, tiling.AXIS_ROW))
    e = st.sort(st.from_numpy(a, tiling=t), axis=1)
    assert isinstance(e, SampleSortExpr)
    hlo = profiling.hlo_text(st.sort(st.from_numpy(a, tiling=t), axis=1))
    # census: all-gathers may move splitter samples / bucket counts,
    # never anything within 4x of the full 64 x n array
    full = a.size * 4  # bytes
    for m in re.finditer(r"(\S+)\s*=\s*\S*\s*all-gather", hlo):
        shape = re.search(r"f32\[([\d,]+)\]", m.group(0))
        if shape:
            elems = int(np.prod([int(d) for d in
                                 shape.group(1).split(",")]))
            assert elems * 4 < full / 4, \
                f"full-size all-gather in HLO: {m.group(0)}"
    np.testing.assert_array_equal(np.asarray(e.glom()),
                                  np.sort(a, axis=1))


def test_sort_axis0_sharded(mesh1d):
    """Sort along a sharded axis 0 (moveaxis wrapping of the batched
    kernel), ragged rows included."""
    rng = np.random.RandomState(61)
    a = rng.rand(8200, 6).astype(np.float32)  # 8200 % 8 != 0
    e = st.sort(st.from_numpy(a, tiling=tiling.row(2)), axis=0)
    assert isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()),
                                  np.sort(a, axis=0))


def test_sort_axis_keeps_batch_sharding(mesh2d):
    """A batch-sharded operand sorts along its sharded axis WITHOUT
    replicating the batch axis (round-5 review): the collective runs
    on the mesh axis already holding the sort axis, batch stays put."""
    rng = np.random.RandomState(63)
    a = rng.rand(64, 4096).astype(np.float32)
    t = tiling.Tiling((tiling.AXIS_ROW, tiling.AXIS_COL))
    e = st.sort(st.from_numpy(a, tiling=t), axis=1)
    assert isinstance(e, SampleSortExpr)
    out = e.evaluate()
    np.testing.assert_array_equal(np.asarray(out.glom()),
                                  np.sort(a, axis=1))
    # no shard holds the whole batch axis
    shards = out.jax_array.addressable_shards
    assert all(s.data.shape[0] < 64 for s in shards), \
        [s.data.shape for s in shards]


def test_sort_axis_out_of_range(mesh1d):
    a = st.from_numpy(np.random.rand(8, 8).astype(np.float32))
    with pytest.raises(ValueError, match="out of range"):
        st.sort(a, axis=2)
    with pytest.raises(ValueError, match="out of range"):
        st.argsort(a, axis=-3)


def test_argsort_axis_sharded(mesh1d):
    """Batched distributed argsort along a sharded axis: per-row
    permutation whose gather reproduces the sorted rows."""
    rng = np.random.RandomState(62)
    a = rng.rand(16, 32_768).astype(np.float32)
    t = tiling.Tiling((None, tiling.AXIS_ROW))
    e = st.argsort(st.from_numpy(a, tiling=t), axis=1)
    assert isinstance(e, SampleSortExpr) and e.indices
    perm = np.asarray(e.glom())
    assert perm.dtype == np.int32
    for r in range(16):
        assert np.array_equal(np.sort(perm[r]), np.arange(a.shape[1]))
        np.testing.assert_array_equal(a[r][perm[r]], np.sort(a[r]))


def _inf_values():
    a = np.random.RandomState(7).rand(4096).astype(np.float32)
    a[::100] = np.inf
    a[::173] = -np.inf
    return a, tiling.row(1)


def _nan_values():
    """A ragged length with NaNs, which sort after +inf."""
    a = np.random.RandomState(4).randn(1013).astype(np.float32)
    a[[3, 500, 1012]] = np.nan
    return a, None


def _uniform_values():
    return (np.random.RandomState(8).rand(65_536).astype(np.float32),
            tiling.row(1))


@pytest.mark.parametrize("make", [_inf_values, _nan_values],
                         ids=["inf", "nan"])
def test_sample_sort_inf_values(mesh1d, make):
    """Data containing +/-inf or NaN must not collide with exchange
    padding; NaN payloads survive the exchange bit for bit."""
    a, t = make()
    e = st.sort(st.from_numpy(a, tiling=t))
    assert isinstance(e, SampleSortExpr)
    np.testing.assert_array_equal(np.asarray(e.glom()).view(np.uint32),
                                  np.sort(a).view(np.uint32))


@pytest.mark.parametrize("make", [_uniform_values, _nan_values],
                         ids=["uniform", "nan"])
def test_sample_argsort_oracle(mesh1d, make):
    """Distributed argsort: x[perm] is sorted and perm is a true
    permutation (np.argsort's exact tie order is not guaranteed)."""
    a, t = make()
    e = st.argsort(st.from_numpy(a, tiling=t))
    assert isinstance(e, SampleSortExpr) and e.indices
    perm = np.asarray(e.glom())
    assert perm.dtype == np.int32
    assert np.array_equal(np.sort(perm), np.arange(a.size))
    np.testing.assert_array_equal(a[perm].view(np.uint32),
                                  np.sort(a).view(np.uint32))


def test_sample_argsort_duplicates(mesh2d):
    rng = np.random.RandomState(9)
    a = rng.randint(0, 7, size=16_384).astype(np.float32)
    e = st.argsort(st.from_numpy(a, tiling=tiling.row(1)))
    perm = np.asarray(e.glom())
    assert np.array_equal(np.sort(perm), np.arange(a.size))
    np.testing.assert_array_equal(a[perm], np.sort(a))


def test_argsort_non_divisible_distributed(mesh1d):
    """Ragged argsort stays distributed; indices must cover [0, n) and
    reproduce the sorted order (padding indices never leak out)."""
    rng = np.random.RandomState(10)
    a = rng.rand(1001).astype(np.float32)
    e = st.argsort(st.from_numpy(a))
    assert isinstance(e, SampleSortExpr)
    perm = np.asarray(e.glom())
    assert np.array_equal(np.sort(perm), np.arange(a.size))
    np.testing.assert_array_equal(a[perm], np.sort(a))


def test_distributed_median_percentile(mesh1d):
    """1-D sharded median/percentile ride the sample sort; oracle vs
    numpy, odd and even lengths plus interpolated percentiles."""
    rng = np.random.RandomState(11)
    for n in (8192, 65_536):
        a = rng.rand(n).astype(np.float32)
        fa = st.from_numpy(a, tiling=tiling.row(1))
        np.testing.assert_allclose(float(st.median(fa).glom()),
                                   np.median(a), rtol=1e-6)
        for q in (0.0, 25.0, 50.0, 90.5, 100.0):
            np.testing.assert_allclose(
                float(st.percentile(fa, q).glom()),
                np.percentile(a, q), rtol=1e-5, atol=1e-7)
    # non-divisible falls back to the traced path
    b = rng.rand(1001).astype(np.float32)
    np.testing.assert_allclose(float(st.median(st.from_numpy(b)).glom()),
                               np.median(b), rtol=1e-6)
    np.testing.assert_allclose(
        float(st.percentile(st.from_numpy(b), 30.0).glom()),
        np.percentile(b, 30.0), rtol=1e-5)


def test_distributed_median_nan_and_int(mesh1d):
    """Distributed median/percentile match the traced semantics: NaN
    propagates; int inputs promote before the middle sum."""
    rng = np.random.RandomState(12)
    a = rng.rand(8192).astype(np.float32)
    a[137] = np.nan
    fa = st.from_numpy(a, tiling=tiling.row(1))
    assert np.isnan(float(st.median(fa).glom()))
    assert np.isnan(float(st.percentile(fa, 75.0).glom()))
    # int32 middles near the max must not wrap
    big = np.full(4096, 2_000_000_000, np.int32)
    fb = st.from_numpy(big, tiling=tiling.row(1))
    np.testing.assert_allclose(float(st.median(fb).glom()), 2e9,
                               rtol=1e-6)


def test_distributed_median_inf_not_poisoned(mesh1d):
    """inf values (and f32 sums that overflow to inf) must NOT trip the
    NaN poison — only genuine NaN does (round-4 advisor, medium)."""
    a = np.arange(64, dtype=np.float32)
    a[7] = np.inf
    fa = st.from_numpy(a, tiling=tiling.row(1))
    np.testing.assert_allclose(float(st.median(fa).glom()),
                               np.median(a), rtol=1e-6)
    np.testing.assert_allclose(float(st.percentile(fa, 25.0).glom()),
                               np.percentile(a, 25.0), rtol=1e-5)
    # f32 sum of these overflows to inf; median itself is finite
    b = np.full(8192, 3e37, np.float32)
    fb = st.from_numpy(b, tiling=tiling.row(1))
    np.testing.assert_allclose(float(st.median(fb).glom()), 3e37,
                               rtol=1e-6)
    # -inf alongside inf: still finite-median, still no poison
    c = np.arange(128, dtype=np.float32)
    c[3], c[100] = -np.inf, np.inf
    fc = st.from_numpy(c, tiling=tiling.row(1))
    np.testing.assert_allclose(float(st.median(fc).glom()),
                               np.median(c), rtol=1e-6)


def test_percentile_vector_q(mesh1d):
    """Vector q (round-4 verdict #3): one distributed sort feeds every
    quantile; oracle vs numpy, ragged length included."""
    rng = np.random.RandomState(13)
    for n in (8192, 1001):
        a = rng.rand(n).astype(np.float32)
        fa = (st.from_numpy(a, tiling=tiling.row(1))
              if n % 8 == 0 else st.from_numpy(a))
        q = [0.0, 12.5, 50.0, 87.3, 100.0]
        got = np.asarray(st.percentile(fa, q).glom())
        assert got.shape == (len(q),)
        np.testing.assert_allclose(got, np.percentile(a, q),
                                   rtol=1e-5, atol=1e-6)
    # 2-D q rejected with a clear message
    with pytest.raises(NotImplementedError, match="1-D"):
        st.percentile(fa, [[25.0], [75.0]])
    # vector q with NaN data: every slot poisons
    b = rng.rand(640).astype(np.float32)
    b[17] = np.nan
    fb = st.from_numpy(b, tiling=tiling.row(1))
    assert np.all(np.isnan(np.asarray(
        st.percentile(fb, [10.0, 90.0]).glom())))


def test_median_percentile_nd_sharded_axis(mesh1d):
    """N-d median/percentile along a SHARDED axis ride the batched
    distributed sort instead of gathering (round-5 extension of the
    1-D order-statistics path); oracle vs numpy, ragged + NaN."""
    rng = np.random.RandomState(15)
    a = rng.rand(6, 8200).astype(np.float32)  # ragged along axis 1
    t = tiling.Tiling((None, tiling.AXIS_ROW))
    fa = st.from_numpy(a, tiling=t)
    e = st.median(fa, axis=1)
    from spartan_tpu.expr.builtins import SampleSortExpr as SSE
    from spartan_tpu.expr.optimize import dag_nodes

    assert any(isinstance(n, SSE) for n in dag_nodes(e.optimized()))
    np.testing.assert_allclose(np.asarray(e.glom()),
                               np.median(a, axis=1), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(st.percentile(fa, 37.5, axis=1).glom()),
        np.percentile(a, 37.5, axis=1), rtol=1e-5)
    # axis 0 sharded (moveaxis path) — assert the DISTRIBUTED routing,
    # not just the oracle (the gather fallback would also match it)
    b = rng.rand(4096, 5).astype(np.float32)
    fb = st.from_numpy(b, tiling=tiling.row(2))
    e0 = st.median(fb, axis=0)
    assert any(isinstance(n, SSE) for n in dag_nodes(e0.optimized()))
    np.testing.assert_allclose(np.asarray(e0.glom()),
                               np.median(b, axis=0), rtol=1e-6)
    # NaN poisons only its own slice
    c = rng.rand(4, 4096).astype(np.float32)
    c[2, 17] = np.nan
    fc = st.from_numpy(c, tiling=t)
    ec = st.median(fc, axis=1)
    assert any(isinstance(n, SSE) for n in dag_nodes(ec.optimized()))
    out = np.asarray(ec.glom())
    assert np.isnan(out[2]) and np.isfinite(out[[0, 1, 3]]).all()
    np.testing.assert_allclose(out[[0, 1, 3]],
                               np.median(c[[0, 1, 3]], axis=1),
                               rtol=1e-6)


def test_unique_distributed(mesh1d):
    """Static-size unique composes sort + blocked scan + scatter on
    the mesh; oracle vs np.unique (values and counts), ragged length
    and heavy duplication included."""
    rng = np.random.RandomState(16)
    for n in (8192, 1001):
        a = rng.randint(0, 200, n).astype(np.int32)
        ref_v, ref_c = np.unique(a, return_counts=True)
        k = ref_v.size
        vals, cnts = st.unique(st.from_numpy(a), size=k + 8,
                               fill_value=-1, return_counts=True)
        gv, gc = np.asarray(vals.glom()), np.asarray(cnts.glom())
        np.testing.assert_array_equal(gv[:k], ref_v)
        assert (gv[k:] == -1).all()
        np.testing.assert_array_equal(gc[:k], ref_c)
        assert (gc[k:] == 0).all()
    # floats with duplicates
    b = rng.choice(np.linspace(0, 1, 37).astype(np.float32), 4096)
    ref = np.unique(b)
    got = np.asarray(st.unique(st.from_numpy(b), size=64,
                               fill_value=np.inf).glom())
    np.testing.assert_array_equal(got[:ref.size], ref)
    # size smaller than the distinct count: truncation, no error
    got2 = np.asarray(st.unique(st.from_numpy(b), size=10).glom())
    np.testing.assert_array_equal(got2, ref[:10])
    # single-value edge
    c = np.full(64, 7.0, np.float32)
    gv3 = np.asarray(st.unique(st.from_numpy(c), size=4,
                               fill_value=0).glom())
    np.testing.assert_array_equal(gv3, [7.0, 0, 0, 0])
    # N-d input flattens (np.unique semantics); counts share the sort
    d = rng.randint(0, 9, (16, 8)).astype(np.int32)
    rv, rc = np.unique(d, return_counts=True)
    v4, c4 = st.unique(st.from_numpy(d), size=16, fill_value=-1,
                       return_counts=True)
    np.testing.assert_array_equal(np.asarray(v4.glom())[:rv.size], rv)
    np.testing.assert_array_equal(np.asarray(c4.glom())[:rv.size], rc)
    # tiny input (n < p)
    e5 = st.unique(st.from_numpy(np.array([3.0, 1.0, 3.0], np.float32)),
                   size=4, fill_value=9)
    np.testing.assert_array_equal(np.asarray(e5.glom()), [1, 3, 9, 9])


def test_median_ragged(mesh1d):
    """Median of non-divisible lengths stays distributed and exact."""
    rng = np.random.RandomState(14)
    for n in (1001, 999):
        a = rng.rand(n).astype(np.float32)
        fa = st.from_numpy(a)
        np.testing.assert_allclose(float(st.median(fa).glom()),
                                   np.median(a), rtol=1e-6)


def test_topk_distributed(mesh1d):
    """Distributed top-k: candidate path (k <= shard) and the
    argsort-slice path (k > shard), largest and smallest, ints and
    floats, ragged length."""
    rng = np.random.RandomState(17)
    for n in (8192, 1001):
        a = rng.rand(n).astype(np.float32)
        fa = st.from_numpy(a) if n % 8 else st.from_numpy(
            a, tiling=tiling.row(1))
        for k in (1, 5, 64):
            for largest in (True, False):
                vals, idx = st.topk(fa, k, largest=largest)
                gv, gi = np.asarray(vals.glom()), np.asarray(idx.glom())
                ref = np.sort(a)[::-1][:k] if largest else np.sort(a)[:k]
                np.testing.assert_allclose(gv, ref, rtol=1e-6)
                np.testing.assert_allclose(a[gi], gv, rtol=1e-6)
                assert gi.dtype == np.int32
                assert len(set(gi.tolist())) == k  # distinct winners
    # k > shard budget: the argsort-slice path
    b = rng.rand(800).astype(np.float32)  # shard = 100
    vals, idx = st.topk(st.from_numpy(b, tiling=tiling.row(1)), 300)
    np.testing.assert_allclose(np.asarray(vals.glom()),
                               np.sort(b)[::-1][:300], rtol=1e-6)
    # ints incl. extremes survive the order-flip (no negation overflow)
    c = rng.randint(-2**31, 2**31 - 1, 4096).astype(np.int32)
    c[0] = np.iinfo(np.int32).min
    c[1] = np.iinfo(np.int32).max
    fc = st.from_numpy(c, tiling=tiling.row(1))
    for largest in (True, False):
        gv = np.asarray(st.topk(fc, 7, largest=largest)[0].glom())
        ref = np.sort(c)[::-1][:7] if largest else np.sort(c)[:7]
        np.testing.assert_array_equal(gv, ref)
    with pytest.raises(ValueError, match="1 <= k"):
        st.topk(fc, 0)


@pytest.mark.parametrize("largest", [True, False],
                         ids=["largest", "smallest"])
def test_topk_ties_ragged(mesh1d, largest):
    """Every value thrice, on a ragged last shard: the winners are the
    k best values, each at a distinct real index holding that value."""
    rng = np.random.RandomState(2)
    a = np.repeat(rng.rand(173).astype(np.float32), 3)[:515]
    vals, idx = st.topk(st.from_numpy(a), 9, largest=largest)
    gv, gi = np.asarray(vals.glom()), np.asarray(idx.glom())
    ref = np.sort(a)[::-1][:9] if largest else np.sort(a)[:9]
    np.testing.assert_array_equal(gv, ref)
    assert gi.min() >= 0 and gi.max() < a.size
    assert len(set(gi.tolist())) == 9
    np.testing.assert_array_equal(a[gi], gv)


def test_topk_sentinel_extreme_ragged(mesh1d):
    """Data containing the padding sentinel itself (-inf for
    largest=True, INT_MIN) on a RAGGED last shard: padding slots carry
    the same key as real elements, and correctness rests on lax.top_k's
    lower-index tie-break plus padding living at the global tail (see
    the invariant comment in ops/sort.py distributed_topk). Every
    returned index must be a real (< n) position — a broken invariant
    would surface as an out-of-range index silently clamped by the
    value gather in builtins.topk."""
    n = 13  # p=8 -> m=2, 3 padding slots spanning the tail shards
    a = np.full(n, -np.inf, np.float32)
    a[3] = 1.0  # one finite element among the sentinels
    fa = st.from_numpy(a)  # ragged: default (replicated) layout
    vals, idx = st.topk(fa, 2, largest=True)
    gv, gi = np.asarray(vals.glom()), np.asarray(idx.glom())
    assert gi.min() >= 0 and gi.max() < n, f"padding index leaked: {gi}"
    assert len(set(gi.tolist())) == 2
    np.testing.assert_array_equal(gv, np.array([1.0, -np.inf], np.float32))
    np.testing.assert_array_equal(a[gi], gv)

    # all-sentinel data: every winner ties with every padding slot
    b = np.full(n, -np.inf, np.float32)
    fb = st.from_numpy(b)
    vals, idx = st.topk(fb, 2, largest=True)
    gi = np.asarray(idx.glom())
    assert gi.min() >= 0 and gi.max() < n, f"padding index leaked: {gi}"
    assert len(set(gi.tolist())) == 2
    assert np.all(np.isneginf(np.asarray(vals.glom())))

    # int dtype: INT_MIN is the largest=True sentinel
    imin = np.iinfo(np.int32).min
    c = np.full(n, imin, np.int32)
    c[7] = 5
    fc = st.from_numpy(c)
    vals, idx = st.topk(fc, 2, largest=True)
    gv, gi = np.asarray(vals.glom()), np.asarray(idx.glom())
    assert gi.min() >= 0 and gi.max() < n, f"padding index leaked: {gi}"
    np.testing.assert_array_equal(gv, np.array([5, imin], np.int32))
    np.testing.assert_array_equal(c[gi], gv)

    # smallest-k: +inf / INT_MAX are the sentinels there
    d = np.full(n, np.inf, np.float32)
    d[11] = -2.0  # on the ragged tail shard, next to padding
    fd = st.from_numpy(d)
    vals, idx = st.topk(fd, 2, largest=False)
    gv, gi = np.asarray(vals.glom()), np.asarray(idx.glom())
    assert gi.min() >= 0 and gi.max() < n, f"padding index leaked: {gi}"
    np.testing.assert_array_equal(gv, np.array([-2.0, np.inf], np.float32))
