"""Application smoke tests (SURVEY.md §4: run a few iterations on
synthetic data; check convergence/shape, not exact values)."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


def test_kmeans_converges():
    from spartan_tpu.examples.kmeans import kmeans

    rng = np.random.RandomState(0)
    pts = np.concatenate([rng.randn(64, 4) + 5,
                          rng.randn(64, 4) - 5]).astype(np.float32)
    centers, assign = kmeans(st.from_numpy(pts), k=2, num_iter=5)
    assert centers.shape == (2, 4)
    assert sorted(np.round(centers[:, 0]).astype(int).tolist()) == [-5, 5]
    assert np.bincount(assign).tolist() == [64, 64]


def test_linear_regression():
    from spartan_tpu.examples.regression import linear_regression

    rng = np.random.RandomState(1)
    X = rng.randn(256, 8).astype(np.float32)
    w_true = rng.randn(8).astype(np.float32)
    y = X @ w_true
    w = linear_regression(st.from_numpy(X), st.from_numpy(y),
                          num_iter=200, lr=0.1)
    np.testing.assert_allclose(w, w_true, atol=1e-2)


def test_logistic_regression():
    from spartan_tpu.examples.regression import logistic_regression

    rng = np.random.RandomState(2)
    X = rng.randn(256, 8).astype(np.float32)
    w_true = rng.randn(8).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)
    w = logistic_regression(st.from_numpy(X), st.from_numpy(y),
                            num_iter=100, lr=0.5)
    acc = (((X @ w) > 0) == y).mean()
    assert acc > 0.95


def test_svm():
    from spartan_tpu.examples.svm import svm

    rng = np.random.RandomState(3)
    X = rng.randn(256, 4).astype(np.float32)
    w_true = np.array([1.0, -2.0, 0.5, 1.5], np.float32)
    y = np.sign(X @ w_true).astype(np.float32)
    w = svm(st.from_numpy(X), st.from_numpy(y), num_iter=150, lr=0.1)
    acc = (np.sign(X @ w) == y).mean()
    assert acc > 0.95


def test_naive_bayes():
    from spartan_tpu.examples.naive_bayes import fit, predict

    rng = np.random.RandomState(4)
    n_per, d = 128, 12
    # class 0 heavy on first features, class 1 on last
    x0 = rng.poisson(5, (n_per, d)) * np.r_[np.ones(6), np.ones(6) * 0.2]
    x1 = rng.poisson(5, (n_per, d)) * np.r_[np.ones(6) * 0.2, np.ones(6)]
    X = np.concatenate([x0, x1]).astype(np.float32)
    y = np.concatenate([np.zeros(n_per), np.ones(n_per)]).astype(np.int32)
    lp, ll = fit(st.from_numpy(X), st.from_numpy(y), n_classes=2)
    pred = predict(st.from_numpy(X), lp, ll).glom()
    assert (pred == y).mean() > 0.9


def test_fuzzy_kmeans():
    from spartan_tpu.examples.fuzzy_kmeans import fuzzy_kmeans

    rng = np.random.RandomState(5)
    pts = np.concatenate([rng.randn(64, 2) + 4,
                          rng.randn(64, 2) - 4]).astype(np.float32)
    centers = fuzzy_kmeans(st.from_numpy(pts), k=2, num_iter=15)
    assert sorted(np.round(centers[:, 0] / 4).astype(int).tolist()) == [-1, 1]


def test_conj_gradient():
    from spartan_tpu.examples.conj_gradient import conj_gradient

    rng = np.random.RandomState(6)
    m = rng.randn(16, 16).astype(np.float32)
    a = m @ m.T + 16 * np.eye(16, dtype=np.float32)
    x_true = rng.randn(16).astype(np.float32)
    b = a @ x_true
    x = conj_gradient(st.from_numpy(a), st.from_numpy(b), num_iter=32)
    np.testing.assert_allclose(x, x_true, atol=1e-2, rtol=1e-2)


def test_als():
    from spartan_tpu.examples.als import als

    rng = np.random.RandomState(7)
    u_true = rng.rand(24, 4).astype(np.float32)
    v_true = rng.rand(16, 4).astype(np.float32)
    r = u_true @ v_true.T
    mask = rng.rand(24, 16) < 0.7
    r_obs = (r * mask).astype(np.float32)
    u, v = als(st.from_numpy(r_obs), k=4, num_iter=8, reg=0.05)
    recon = u @ v.T
    err = np.abs(recon[mask] - r[mask]).mean()
    assert err < 0.05


def _star_links(n):
    """Everyone links to node 0; node 0 links to node 1."""
    from spartan_tpu.array.sparse import SparseDistArray

    rows = np.concatenate([np.arange(1, n), [0]])
    cols = np.concatenate([np.zeros(n - 1, np.int64), [1]])
    return SparseDistArray.from_coo(rows, cols, np.ones(n, np.float32),
                                    (n, n))


def _check_star_ranks(ranks):
    assert ranks.argmax() == 0
    assert ranks[1] > ranks[2]  # node 1 gets node 0's rank
    np.testing.assert_allclose(ranks.sum(), 1.0, rtol=1e-3)


# three column windows of the windowed gather (16,384 columns each)
_STAR_N = 2 * 16384 + 8


def test_pagerank():
    from spartan_tpu.examples.pagerank import pagerank

    _check_star_ranks(pagerank(_star_links(_STAR_N), num_iter=40))


def test_pagerank_windowed(monkeypatch):
    """The one-dispatch windowed power iteration (the chip's path; the
    kernels in interpret mode here) on one device, against float64
    power iterations on the same graph."""
    import jax

    from spartan_tpu.array.sparse import SparseDistArray
    from spartan_tpu.examples.pagerank import pagerank

    n = _STAR_N
    monkeypatch.setattr(SparseDistArray, "_default_windowed",
                        lambda self: True)
    with st.use_mesh(st.build_mesh(jax.devices()[:1], shape=(1, 1))):
        got = pagerank(_star_links(n), num_iter=40)
    _check_star_ranks(got)
    # node i > 0 gives all its rank to 0, node 0 all of its to 1
    want = np.full(n, 1.0 / n)
    for _ in range(40):
        y = np.zeros(n)
        y[0], y[1] = want[1:].sum(), want[0]
        y = 0.85 * y + 0.15 / n
        want = y + (1.0 - y.sum()) / n
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 / n)


def test_ssvd():
    from spartan_tpu.examples.ssvd import ssvd

    rng = np.random.RandomState(8)
    # low-rank + noise
    a = (rng.randn(32, 6) @ rng.randn(6, 24)).astype(np.float32)
    u, s, vt = ssvd(st.from_numpy(a), rank=6, n_power_iter=2)
    assert u.shape == (32, 6) and s.shape == (6,) and vt.shape == (6, 24)
    recon = u @ np.diag(s) @ vt
    rel = np.linalg.norm(recon - a) / np.linalg.norm(a)
    assert rel < 1e-3
    s_true = np.linalg.svd(a, compute_uv=False)[:6]
    np.testing.assert_allclose(s, s_true, rtol=1e-3)


def test_sgd_matrix_factorization():
    from spartan_tpu.array.sparse import SparseDistArray
    from spartan_tpu.examples.matrix_fact import (rmse,
                                                  sgd_matrix_factorization)

    rng = np.random.RandomState(3)
    u_true = rng.rand(40, 4).astype(np.float32)
    v_true = rng.rand(30, 4).astype(np.float32)
    r = u_true @ v_true.T
    # observe 60% of entries
    obs = rng.rand(40, 30) < 0.6
    rows, cols = np.nonzero(obs)
    ratings = SparseDistArray.from_coo(rows, cols, r[rows, cols], (40, 30))

    u0 = rng.rand(40, 4).astype(np.float32)
    v0 = rng.rand(30, 4).astype(np.float32)
    before = rmse(ratings, u0 / 2, v0 / 2)
    u, v = sgd_matrix_factorization(ratings, k=4, num_epochs=60,
                                    lr=0.05, reg=1e-4, batch=256)
    after = rmse(ratings, u, v)
    assert after < 0.15
    assert after < before / 3


def test_kmeans_fused_kernel_oracle():
    """Fused assign+accumulate kernel vs the NumPy oracle (interpret
    mode on CPU; Mosaic on TPU), including driver-padding masking."""
    import jax
    import jax.numpy as jnp

    from spartan_tpu.kernels import kmeans as kk

    rng = np.random.RandomState(5)
    n, d, k = 3000, 128, 7          # pads to 3072
    pts = rng.rand(n, d).astype(np.float32)
    cen = pts[:k].copy()
    pj = jnp.zeros((3072, d), jnp.float32).at[:n].set(pts)
    sums, cnt = jax.device_get(
        kk.assign_accumulate(pj, jnp.asarray(cen), k, valid_rows=n))
    d2 = ((pts ** 2).sum(1)[:, None] - 2 * pts @ cen.T
          + (cen ** 2).sum(1)[None, :])
    a = d2.argmin(1)
    esums = np.zeros((k, d), np.float32)
    np.add.at(esums, a, pts)
    np.testing.assert_allclose(sums, esums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(cnt, np.bincount(a, minlength=k))


def test_kmeans_fused_run_matches_step():
    import jax
    import jax.numpy as jnp

    from spartan_tpu.kernels import kmeans as kk

    rng = np.random.RandomState(6)
    pts = jnp.asarray(rng.rand(2048, 128).astype(np.float32))
    c0 = pts[:5]
    c_loop = np.asarray(jax.device_get(kk.run(pts, c0, 5, jnp.int32(3))))
    c = c0
    for _ in range(3):
        c = kk.step(pts, c, 5)
    np.testing.assert_allclose(c_loop, np.asarray(jax.device_get(c)),
                               rtol=1e-5, atol=1e-6)


def test_lanczos_svd():
    from spartan_tpu.examples.lanczos import lanczos_svd

    rng = np.random.RandomState(0)
    # low-rank + noise: top singular values well separated
    base = (rng.randn(48, 8) @ rng.randn(8, 32)).astype(np.float32)
    a = base + 0.01 * rng.randn(48, 32).astype(np.float32)
    U, s, V = lanczos_svd(st.from_numpy(a, tiling=tiling.row(2)), rank=4)
    s_ref = np.linalg.svd(a, compute_uv=False)[:4]
    np.testing.assert_allclose(s, s_ref, rtol=1e-3)
    # triplets reconstruct: A v_i ~= s_i u_i
    av = a @ V
    np.testing.assert_allclose(av, U * s[None, :], rtol=1e-2, atol=1e-3)
    # orthonormal factors
    np.testing.assert_allclose(V.T @ V, np.eye(4), atol=1e-4)


def test_lda_topics():
    from spartan_tpu.examples.lda import lda, log_likelihood

    rng = np.random.RandomState(1)
    # two disjoint vocabularies -> two recoverable topics
    d, w, k = 24, 16, 2
    counts = np.zeros((d, w), np.float32)
    for i in range(d):
        half = 0 if i < d // 2 else 1
        words = rng.randint(half * w // 2, (half + 1) * w // 2, size=40)
        np.add.at(counts[i], words, 1.0)
    ce = st.from_numpy(counts, tiling=tiling.row(2))
    theta0 = np.full((d, k), 1.0 / k, np.float32)
    phi0 = np.full((k, w), 1.0 / w, np.float32)
    ll0 = log_likelihood(ce, theta0, phi0)
    theta, phi = lda(ce, k=k, num_iter=25, seed=3)
    ll1 = log_likelihood(ce, theta, phi)
    assert ll1 > ll0 + 10.0, (ll0, ll1)
    # each topic concentrates on one vocabulary half
    mass_first_half = phi[:, :w // 2].sum(axis=1)
    assert (mass_first_half.max() > 0.9) and (mass_first_half.min() < 0.1)
    # docs assign to the matching topic
    top = theta.argmax(axis=1)
    assert len(set(top[:d // 2])) == 1 and len(set(top[d // 2:])) == 1
    assert top[0] != top[-1]


def test_lsh_candidates():
    from spartan_tpu.examples.lsh import (candidate_pairs,
                                          hamming_similarity)

    rng = np.random.RandomState(2)
    base = rng.randn(7, 32).astype(np.float32)
    # rows 0/1 near-duplicates; the rest random
    pts = np.vstack([base[0], base[0] + 0.01 * rng.randn(32)
                     .astype(np.float32), base[1:]]).astype(np.float32)
    pairs = candidate_pairs(st.from_numpy(pts, tiling=tiling.row(2)),
                            n_bits=64, bands=16)
    assert (0, 1) in pairs
    sim = hamming_similarity(st.from_numpy(pts, tiling=tiling.row(2)),
                             0, 1)
    assert sim > 0.95


def test_models_namespace_importable():
    """spartan_tpu.models is the stable estimator surface — every name
    in __all__ must import (this was silently broken: the namespace
    imported a function name that didn't exist)."""
    import spartan_tpu.models as models

    for name in models.__all__:
        assert getattr(models, name, None) is not None, name
