"""Auxiliary subsystem tests: profiling/cost analysis, error
attribution, lineage recompute, sort/stencil ops."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.utils import profiling
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


def test_cost_analysis_reports_flops():
    a = st.from_numpy(np.ones((32, 32), np.float32))
    b = st.from_numpy(np.ones((32, 32), np.float32))
    stats = profiling.cost_analysis(st.dot(a, b))
    # reported per partition: global 2*n^3 spread over the 8 devices
    assert stats.get("flops", 0) >= 2 * 32 * 32 * 32 / 8


def test_benchmark_harness():
    x = st.from_numpy(np.ones((8, 8), np.float32))
    res = profiling.benchmark(lambda: (x + 1.0).glom(), iters=3)
    assert res["best"] > 0 and res["iters"] == 3


def test_error_attribution():
    """Errors surfacing at force-time (not construction) are annotated
    with the user line that built the failing expr. ShardMap2Expr defers
    kernel tracing to lowering, so the failure happens inside evaluate."""
    import jax.numpy as jnp

    from spartan_tpu.array import tiling

    x = st.from_numpy(np.ones((8, 8), np.float32))
    t = tiling.row(2)
    bad = st.shard_map2([x], lambda v: jnp.broken_fn(v), [t], t,  # noqa
                        (8, 8), np.float32)
    with pytest.raises(Exception) as exc_info:
        bad.glom()
    notes = getattr(exc_info.value, "__notes__", [])
    assert any("test_aux.py" in n for n in notes), notes


def test_lineage_recompute():
    x = st.from_numpy(np.ones((8, 8), np.float32))
    e = (x * 3.0).sum()
    first = e.glom()
    assert e._result is not None
    e.invalidate()
    assert e._result is None
    second = e.recompute().glom()
    np.testing.assert_array_equal(first, second)


def test_determinism_check_flag():
    FLAGS.check_determinism = True
    try:
        x = st.from_numpy(np.ones((8, 8), np.float32))
        out = (x + x).glom()
        np.testing.assert_array_equal(out, np.full((8, 8), 2.0))
    finally:
        FLAGS.check_determinism = False


def test_sort_argsort_median():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 16).astype(np.float32)
    ex = st.from_numpy(x)
    np.testing.assert_array_equal(st.sort(ex).glom(), np.sort(x, axis=-1))
    np.testing.assert_array_equal(st.sort(ex, axis=0).glom(),
                                  np.sort(x, axis=0))
    np.testing.assert_array_equal(st.argsort(ex).glom(),
                                  np.argsort(x, axis=-1))
    np.testing.assert_allclose(st.median(ex).glom(), np.median(x),
                               rtol=1e-6)


def test_stencil_and_pooling():
    from spartan_tpu.ops.stencil import avgpool, maxpool, stencil

    rng = np.random.RandomState(1)
    img = rng.rand(2, 8, 8, 3).astype(np.float32)
    filt = rng.rand(3, 3, 3, 4).astype(np.float32)
    out = stencil(img, filt, stride=1, padding="SAME").glom()
    assert out.shape == (2, 8, 8, 4)
    # oracle via scipy-style direct computation on one pixel
    patch = img[0, 0:3, 0:3, :]
    np.testing.assert_allclose(out[0, 1, 1, 0],
                               (patch * filt[..., 0]).sum(), rtol=1e-4)
    mp = maxpool(img, 2).glom()
    assert mp.shape == (2, 4, 4, 3)
    np.testing.assert_allclose(mp[0, 0, 0, 0], img[0, :2, :2, 0].max())
    ap = avgpool(img, 2).glom()
    np.testing.assert_allclose(ap[0, 0, 0, 0], img[0, :2, :2, 0].mean(),
                               rtol=1e-5)


def test_device_memory_stats_shape():
    stats = profiling.device_memory_stats()
    assert isinstance(stats, dict)


def test_fault_injection_lineage_recovery():
    """SURVEY.md §5 failure recovery, migrated to the resilience
    injection API (PR 5): a TRANSIENT execution fault (the analogue
    of a lost worker/tile) is injected at the real dispatch seam by
    ``st.chaos`` and retried by the in-evaluate policy engine —
    exprs are deterministic, so the DAG is the recovery log and a
    plain ``evaluate()`` recovers by itself."""
    from spartan_tpu.utils.config import FLAGS

    x = st.from_numpy(np.arange(64, dtype=np.float32).reshape(8, 8))
    e = (x * 2.0 + 1.0).sum(axis=0)
    expected = (np.arange(64, dtype=np.float32).reshape(8, 8)
                * 2.0 + 1.0).sum(axis=0)

    before = st.metrics()["counters"].get("resilience_retries", 0)
    saved = FLAGS.retry_backoff_s
    FLAGS.retry_backoff_s = 0.0
    try:
        with st.chaos("transient@0x2") as plan:  # two failed dispatches
            out = e.evaluate()
    finally:
        FLAGS.retry_backoff_s = saved
    assert [f["kind"] for f in plan.fired] == ["transient", "transient"]
    after = st.metrics()["counters"].get("resilience_retries", 0)
    assert after - before == 2  # attempt 1+2 faulted, attempt 3 ran
    np.testing.assert_allclose(np.asarray(out.glom()), expected,
                               rtol=1e-6)


def test_evaluate_with_recovery_api(monkeypatch):
    """The legacy driver-level loop (utils/recovery.py) survives as a
    DEPRECATED shim over resilience.engine.retry_evaluate: transient
    faults retry from lineage, and — the classifier routing — user
    errors propagate immediately even though they are RuntimeError
    siblings under the old blind default."""
    from spartan_tpu.utils.recovery import evaluate_with_recovery

    x = st.from_numpy(np.full((4, 4), 2.0, np.float32))
    e = (x * x).sum()

    calls = {"n": 0, "hook": []}
    real = type(e).evaluate

    def flaky(self):
        calls["n"] += 1
        if calls["n"] <= 2:  # a transient-classified status message
            raise RuntimeError("UNAVAILABLE: injected device loss")
        return real(self)

    monkeypatch.setattr(type(e), "evaluate", flaky)
    with pytest.warns(DeprecationWarning, match="policy engine"):
        out = evaluate_with_recovery(
            e, retries=3,
            on_failure=lambda a, exc: calls["hook"].append(a))
    monkeypatch.undo()
    assert calls["n"] == 3 and calls["hook"] == [0, 1]
    np.testing.assert_allclose(np.asarray(out.glom()), 64.0)

    # a user error is NOT retried...
    bad = st.from_numpy(np.ones((4, 4), np.float32))
    b = (bad * 1.0).sum()

    def user_error(self):
        calls["n"] += 100
        raise ValueError("user bug")

    monkeypatch.setattr(type(b), "evaluate", user_error)
    before = calls["n"]
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            evaluate_with_recovery(b, retries=3)
    monkeypatch.undo()
    assert calls["n"] == before + 100  # exactly one attempt

    # ... and neither is a DETERMINISTIC RuntimeError under the
    # classifier default (the old shim would have retried it)
    c = (bad * 2.0).sum()

    def compile_error(self):
        calls["n"] += 1000
        raise RuntimeError("INVALID_ARGUMENT: bad layout")

    monkeypatch.setattr(type(c), "evaluate", compile_error)
    before = calls["n"]
    with pytest.warns(DeprecationWarning):
        with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
            evaluate_with_recovery(c, retries=3)
    monkeypatch.undo()
    assert calls["n"] == before + 1000  # exactly one attempt

    # an explicit retryable tuple keeps legacy isinstance semantics
    d = (bad * 3.0).sum()
    calls["m"] = 0
    real_d = type(d).evaluate

    def generic_fault(self):
        calls["m"] += 1
        if calls["m"] == 1:
            raise RuntimeError("some generic failure")
        return real_d(self)

    monkeypatch.setattr(type(d), "evaluate", generic_fault)
    with pytest.warns(DeprecationWarning):
        out = evaluate_with_recovery(d, retries=2,
                                     retryable=(RuntimeError,))
    monkeypatch.undo()
    assert calls["m"] == 2
    np.testing.assert_allclose(np.asarray(out.glom()), 48.0)


@pytest.mark.parametrize("from_env", [False, True])
def test_compilation_cache_dir_resolution(monkeypatch, tmp_path, from_env):
    """st.initialize() places JAX's persistent compilation cache: a set
    JAX_COMPILATION_CACHE_DIR is left to JAX, else <checkout>/.jax_cache
    (a fixed path: the cache key includes it)."""
    import os

    import jax

    import spartan_tpu as st

    prev = jax.config.jax_compilation_cache_dir
    placed = str(tmp_path / "placed")
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        jax.config.update("jax_compilation_cache_dir", placed)  # as JAX
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        st.initialize([])
        checkout = os.path.dirname(os.path.dirname(st.__file__))
        want = placed if from_env else os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


