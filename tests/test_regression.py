"""The regression drivers against a plain float64 NumPy loop: the same
full-batch steps from the same start, at 4,096 x 32 on the 4x2 test
mesh (X row-tiled, so Xᵀr is summed across the mesh)."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling
from spartan_tpu.examples import regression as reg
from spartan_tpu.utils import profiling as prof

N, D, ITERS = 4096, 32, 20
# float32 products and sums: a sum of 4,096 terms errs by about
# sqrt(n) u = 4e-6 of its largest term, and a step below 2/L contracts
# what earlier steps left, so w stays within about 1e-6 relative
TOL = 1e-5


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


def _data(kind: str):
    rng = np.random.default_rng(7)
    x = rng.standard_normal((N, D), dtype=np.float32)
    w_true = rng.standard_normal(D)
    z = x @ w_true + rng.standard_normal(N)
    y = (z > 0) if kind == "logistic" else z
    return x, y.astype(np.float32), rng.random(D).astype(np.float32)


def _reference(kind: str, x, y, w0, lr: float, ridge: float = 0.0):
    x64, y64, w = x.astype(np.float64), y.astype(np.float64), \
        w0.astype(np.float64)
    for _ in range(ITERS):
        z = x64 @ w
        r = 1.0 / (1.0 + np.exp(-z)) - y64 if kind == "logistic" \
            else z - y64
        w = w - lr * (x64.T @ r / N + ridge * w)
    return w


def _fit(kind: str, x, y, w0, fused: bool):
    xs, ys = st.from_numpy(x, tiling=tiling.row(2)), st.from_numpy(y)
    if kind == "logistic":
        return reg.logistic_regression(xs, ys, ITERS, 1.0, fused=fused,
                                       w0=w0), 1.0, 0.0
    if kind == "ridge":
        if fused:
            return reg.ridge_regression(xs, ys, ITERS, 0.1, alpha=0.5,
                                        w0=w0), 0.1, 0.5
        return reg.linear_regression(xs, ys, ITERS, 0.1, ridge=0.5,
                                     fused=False, w0=w0), 0.1, 0.5
    return reg.linear_regression(xs, ys, ITERS, 0.1, fused=fused,
                                 w0=w0), 0.1, 0.0


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "stepped"])
@pytest.mark.parametrize("kind", ["linear", "ridge", "logistic"])
def test_matches_float64_loop(kind, fused):
    x, y, w0 = _data(kind)
    w, lr, ridge = _fit(kind, x, y, w0, fused)
    ref = _reference(kind, x, y, w0, lr, ridge)
    assert w.dtype == np.float32 and w.shape == (D,)
    assert np.abs(w - ref).max() / np.abs(ref).max() < TOL


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "stepped"])
def test_fused_job_is_one_evaluation(fused):
    """A fused job is one plan evaluated, which runs all ``num_iter``
    iterations; a stepped one evaluates a plan an iteration."""
    x, y, w0 = _data("logistic")
    before = prof.counters()
    _fit("logistic", x, y, w0, fused)
    after = prof.counters()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("evaluations") == (1 if fused else ITERS)
    assert delta("loop_iterations") == (ITERS if fused else 0)


def test_start_defaults_to_zeros_and_is_checked():
    x, y, _ = _data("logistic")
    xs, ys = st.from_numpy(x, tiling=tiling.row(2)), st.from_numpy(y)
    w = reg.logistic_regression(xs, ys, ITERS, 1.0)
    np.testing.assert_allclose(
        w, _reference("logistic", x, y, np.zeros(D, np.float32), 1.0),
        rtol=0, atol=TOL * np.abs(w).max())
    with pytest.raises(ValueError, match="w0 has shape"):
        reg.logistic_regression(xs, ys, ITERS, 1.0,
                                w0=np.zeros(D + 1, np.float32))
