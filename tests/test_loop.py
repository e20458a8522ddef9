"""On-device loop (``st.loop`` -> lax.fori_loop). NumPy is the oracle;
conftest runs everything on an 8-CPU-device mesh so carries cross the
sharded path."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.expr.base import compile_cache_size


def test_scalar_counter():
    out = st.loop(10, lambda c: c + 1.0, 0.0)
    assert float(out.glom()) == pytest.approx(10.0)


def test_matrix_iteration_vs_numpy():
    rng = np.random.RandomState(0)
    a = rng.rand(16, 16).astype(np.float32)
    x0 = rng.rand(16, 16).astype(np.float32)
    ea = st.from_numpy(a)
    out = st.loop(5, lambda c: st.dot(c, ea) * 0.1, st.from_numpy(x0))
    want = x0
    for _ in range(5):
        want = (want @ a) * 0.1
    np.testing.assert_allclose(out.glom(), want, rtol=2e-4)


def test_multi_carry():
    # fibonacci-style pair recurrence on arrays
    a0 = np.ones((8, 4), np.float32)
    b0 = np.full((8, 4), 2.0, np.float32)
    ea, eb = st.from_numpy(a0), st.from_numpy(b0)
    fa, fb = st.loop(6, lambda a, b: (b, a + b), ea, eb)
    wa, wb = a0, b0
    for _ in range(6):
        wa, wb = wb, wa + wb
    np.testing.assert_allclose(fa.glom(), wa)
    np.testing.assert_allclose(fb.glom(), wb)


def test_with_index():
    # sum of 0..9 via the induction variable
    out = st.loop(10, lambda i, c: c + i.astype(np.float32), 0.0,
                  with_index=True)
    assert float(out.glom()) == pytest.approx(45.0)


def test_dtype_promotion_in_body():
    # int init, float update: carry stabilizes at float
    out = st.loop(4, lambda c: c + 0.5, 0)
    assert float(out.glom()) == pytest.approx(2.0)


def test_sharded_carry_with_reduction():
    rng = np.random.RandomState(1)
    x = rng.rand(64, 8).astype(np.float32)
    ex = st.from_numpy(x)
    # normalize-by-global-sum iterated: exercises psum inside the body
    out = st.loop(3, lambda c: c / c.sum() * 64.0, ex)
    want = x
    for _ in range(3):
        want = want / want.sum() * 64.0
    np.testing.assert_allclose(out.glom(), want, rtol=1e-4)


def test_iteration_count_does_not_recompile():
    rng = np.random.RandomState(2)
    x = rng.rand(8, 8).astype(np.float32)

    def run(n):
        return st.loop(n, lambda c: c * 0.5, st.from_numpy(x)).glom()

    r5 = run(5)
    before = compile_cache_size()
    r7 = run(7)
    assert compile_cache_size() == before  # n is a traced scalar
    np.testing.assert_allclose(r5, x * 0.5 ** 5, rtol=1e-5)
    np.testing.assert_allclose(r7, x * 0.5 ** 7, rtol=1e-5)


def test_composes_with_downstream_exprs():
    rng = np.random.RandomState(3)
    x = rng.rand(32, 4).astype(np.float32)
    out = st.loop(4, lambda c: c * 1.5, st.from_numpy(x))
    total = (out * 2.0).sum()
    want = (x * 1.5 ** 4 * 2.0).sum()
    assert float(total.glom()) == pytest.approx(want, rel=1e-4)


def test_body_shape_change_rejected():
    x = st.zeros((4, 4))
    with pytest.raises(ValueError, match="keep its shape"):
        st.loop(3, lambda c: c.sum(), x)


def test_carry_escape_rejected():
    x = st.zeros((4, 4))
    escaped = []
    st.loop(2, lambda c: (escaped.append(c) or c + 1.0), x).glom()
    with pytest.raises(RuntimeError, match="outside its loop body"):
        (escaped[0] + 1.0).glom()


def test_kmeans_style_loop():
    """Whole k-means run as ONE program (SURVEY.md §3.4 latency floor
    removed)."""
    from spartan_tpu.examples.kmeans import kmeans_step

    rng = np.random.RandomState(4)
    pts = np.concatenate([
        rng.randn(64, 4).astype(np.float32) + 5.0,
        rng.randn(64, 4).astype(np.float32) - 5.0,
    ])
    ep = st.from_numpy(pts)
    c0 = st.from_numpy(pts[:2].copy())
    final = st.loop(8, lambda c: kmeans_step(ep, c, 2), c0)
    centers = np.asarray(final.glom())
    means = sorted(centers[:, 0])
    assert means[0] < -4.0 and means[1] > 4.0


def test_nested_loops_distinct_signatures():
    """Outer vs inner binder must not collide in the compile cache
    (de Bruijn levels in CarryExpr._sig)."""
    x = st.from_numpy(np.zeros((4,), np.float32))

    def run(use_outer_index):
        def outer_body(i, c):
            idx = i.astype(np.float32)

            def inner_body(j, d):
                inc = idx if use_outer_index else j.astype(np.float32)
                return d + inc

            return st.loop(4, inner_body, c, with_index=True)

        return st.loop(3, outer_body, x, with_index=True).glom()

    got_outer = run(True)
    got_inner = run(False)
    # asymmetric counts (3 outer, 4 inner) so the oracles differ:
    # outer-index -> 12, inner-index -> 18
    w_outer = np.zeros(4, np.float32)
    for i in range(3):
        for _ in range(4):
            w_outer += i
    w_inner = np.zeros(4, np.float32)
    for _ in range(3):
        for j in range(4):
            w_inner += j
    assert w_outer[0] != w_inner[0]
    np.testing.assert_allclose(got_outer, w_outer)
    np.testing.assert_allclose(got_inner, w_inner)


def test_nested_loop_carry_order():
    """Inner body 'd - c' vs 'c - d' with same shapes must not share an
    executable."""
    a0 = np.full((4,), 5.0, np.float32)
    b0 = np.full((4,), 2.0, np.float32)

    def run(flip):
        ea = st.from_numpy(a0)

        def outer(c):
            inner = (lambda d: c - d) if flip else (lambda d: d - c)
            return st.loop(2, inner, st.from_numpy(b0))

        return st.loop(1, outer, ea).glom()

    # flip=False: d=2 -> d-c twice with c=5: 2-5=-3, -3-5=-8
    np.testing.assert_allclose(run(False), np.full(4, -8.0))
    # flip=True: c-d: 5-2=3, 5-3=2
    np.testing.assert_allclose(run(True), np.full(4, 2.0))


def test_multi_carry_single_program():
    """Consuming every carry of a multi-carry loop must compile ONE
    executable and run the loop once (TupleExpr-style forcing)."""
    from spartan_tpu.expr import base

    base.clear_compile_cache()
    ea = st.from_numpy(np.ones((4, 4), np.float32))
    eb = st.from_numpy(np.full((4, 4), 2.0, np.float32))
    fa, fb = st.loop(6, lambda a, b: (b, a + b), ea, eb)
    ga, gb = fa.glom(), fb.glom()
    assert base.compile_cache_size() == 1

    a, b = np.ones((4, 4)), np.full((4, 4), 2.0)
    for _ in range(6):
        a, b = b, a + b
    np.testing.assert_allclose(ga, a)
    np.testing.assert_allclose(gb, b)


def test_loop_iterations_counter():
    """Each evaluation adds the static count to ``loop_iterations``
    once, however many carries the loop has; an early-exit loop adds
    its count too, the most it may run; a forced result read again
    adds nothing."""
    from spartan_tpu.utils import profiling as prof

    def added(fn):
        before = prof.counters()
        fn()
        after = prof.counters()
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in ("loop_iterations", "evaluations")}

    out = st.loop(7, lambda c: c + 1.0, 0.0)
    assert added(out.glom) == {"loop_iterations": 7, "evaluations": 1}
    assert added(out.glom)["loop_iterations"] == 0
    ea = st.from_numpy(np.ones((4, 4), np.float32))
    fa, fb = st.loop(6, lambda a, b: (b, a + b), ea, ea)
    assert added(lambda: (fa.glom(), fb.glom())) == {
        "loop_iterations": 6, "evaluations": 1}
    stop = st.loop(50, lambda c: c * 1e30, st.from_numpy(
        np.ones(4, np.float32)), early_exit=True)
    assert added(stop.glom)["loop_iterations"] == 50
