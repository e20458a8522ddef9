"""The logistic-regression cell, driven through the harness on the CPU at
a tiny size (see test_chipbench_jobs.py): a sound run is correct, the
control and each planted fault are not; and its two readers by hand, on
a synthetic capture and on counts of the cell's own."""

import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import harness  # noqa: E402

from spartan_tpu.examples import regression as reg  # noqa: E402

CELL = "logreg_10m_d32.fit100"
SEED = 2 ** 33 + 12345  # wider than 32 bits, as benchmark seeds can be
TINY = {"n": 65536}  # the width stays 32


@pytest.fixture(autouse=True)
def _program_state(monkeypatch, tmp_path):
    """Keep what a run sets (the compile cache, span recording) out of
    the other tests this worker runs."""
    from spartan_tpu.utils.config import FLAGS

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = FLAGS.trace, FLAGS.trace_ring
    yield
    FLAGS.trace, FLAGS.trace_ring = saved


def run(control: bool = False) -> dict:
    cell = harness.load_cell(harness.load_bench(), CELL)
    cell.config.update(TINY)
    return harness.run_cell(cell, SEED, 0.3, False, jax.devices(),
                            control=control)


def test_sound_run_is_correct():
    line = run()
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["diagnostics"]["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"step_ms", "setup_s"}


def test_control_is_not_correct():
    line = run(control=True)
    assert not line["correct"]
    # bfloat16 keeps 8 bits: w rounds by 2^-9 of itself every step
    assert line["checks"]["w_rel_err"]["value"] > 1e-3


# -- faults planted in the program ---------------------------------------------


def _one_step_short(orig):
    def logistic_regression(x, y, num_iter=10, lr=0.1, fused=True,
                            w0=None):
        return orig(x, y, num_iter - 1, lr, fused=fused, w0=w0)
    return logistic_regression


def _coordinates_swapped(orig):
    def logistic_regression(*args, **kw):
        w = np.array(orig(*args, **kw))
        w[[0, 1]] = w[[1, 0]]
        return w
    return logistic_regression


@pytest.mark.parametrize("fault", [_one_step_short, _coordinates_swapped],
                         ids=lambda f: f.__name__.strip("_"))
def test_driver_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(reg, "logistic_regression",
                        fault(reg.logistic_regression))
    assert not run()["correct"]


def test_gradient_not_divided_by_n_is_caught(monkeypatch):
    """A fault inside the step: ``logistic_regression`` reaches
    ``logistic_grad`` through the module, as the timed path does."""
    orig = reg.logistic_grad
    monkeypatch.setattr(reg, "logistic_grad",
                        lambda x, y, w: orig(x, y, w) * x.shape[0])
    assert not run()["correct"]


# -- the readers by hand ----------------------------------------------------------


def _ctx(steps: int, ops) -> SimpleNamespace:
    from devtrace import Reduction

    red = Reduction(lo=0.0, hi=1e9)
    red.ops = {0: ops}
    cell = harness.load_cell(harness.load_bench(), CELL)
    return SimpleNamespace(trace=red, steps=steps, config=cell.config,
                           traffic=cell.traffic, spans=[],
                           peak=harness.peak("TPU v5 lite"),
                           costs=lambda k: harness.load_module("costs", k))


def test_roofline_by_hand():
    """Two jobs of 100 iterations, each a 400 ms ``jit_traced`` loop
    holding nested fusions, beside a 5 ms op of another program: the
    busy time an iteration is 4 ms, counted once."""
    from devtrace import Op

    ops = []
    for j in range(2):
        t = j * 450e6
        ops += [Op("while.1", "while", "jit_traced", t, t + 400e6),
                Op("multiply_reduce_fusion.4", "fusion:kLoop", "jit_traced",
                   t + 1e6, t + 3e6),
                Op("copy.1", "copy", "jit_other", t + 400e6, t + 405e6)]
    ctx = _ctx(200, ops)
    # one pass over the float32 X and y: 4nd + 4n bytes at 819 GB/s
    least = (4.0 * 10_000_000 * 32 + 4.0 * 10_000_000) / 819e9
    assert least == pytest.approx(1.6117e-3, rel=1e-4)
    reader = harness.load_reader("logreg_roofline")
    assert reader.read(ctx) == pytest.approx(100.0 * least / 4e-3)
    assert reader.read(_ctx(200, [])) is None
    assert reader.read(_ctx(0, ops)) is None


def test_iters_per_eval_by_hand(monkeypatch):
    import importlib

    sut = importlib.import_module("sut.logreg")
    reader = harness.load_reader("loop.iters_per_eval")
    ctx = _ctx(300, [])
    monkeypatch.setitem(sut.COUNTS, "loop_iterations", 300)
    monkeypatch.setitem(sut.COUNTS, "evaluations", 3)
    assert reader.read(ctx) == 100.0
    # a plan an iteration, each a one-iteration loop
    monkeypatch.setitem(sut.COUNTS, "evaluations", 300)
    assert reader.read(ctx) == 1.0
    # a program that counts no loop iterations: nothing to read
    monkeypatch.setitem(sut.COUNTS, "loop_iterations", 0)
    assert reader.read(ctx) is None


def test_sound_run_counts_one_evaluation_a_job():
    import importlib

    run()
    counts = importlib.import_module("sut.logreg").COUNTS
    assert counts["evaluations"] >= 1
    assert counts["loop_iterations"] == 100 * counts["evaluations"]


def test_reference_takes_the_steps_it_names(monkeypatch):
    """``descend`` in blocks against the same steps written whole."""
    from reference import logreg as ref

    rng = np.random.default_rng(4)
    x = rng.standard_normal((3000, 32)).astype(np.float32)
    y = (rng.random(3000) < 0.5).astype(np.float32)
    w0 = ref.job_start(SEED, 3, 32)
    assert w0.dtype == np.float32 and 0.0 <= w0.min() and w0.max() < 1.0
    w = w0.astype(np.float64)
    x64 = x.astype(np.float64)
    for _ in range(5):
        w = w - 0.5 * x64.T @ (1.0 / (1.0 + np.exp(-x64 @ w)) - y) / 3000
    monkeypatch.setattr(ref, "BLOCK", 1024)
    np.testing.assert_allclose(ref.descend(x, y, w0, 5, 0.5), w, rtol=1e-12)
