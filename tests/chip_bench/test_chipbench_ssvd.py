"""The SSVD cell and the plain k-means cell, driven through the harness on
the CPU at tiny sizes (see test_chipbench_jobs.py): a sound run is
correct, the control and each planted fault are not; and the SSVD
readers on a capture recorded on a v5e chip."""

import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import devtrace  # noqa: E402
import harness  # noqa: E402

from spartan_tpu.examples import ssvd as sv  # noqa: E402

SEED = 2 ** 33 + 12345  # wider than 32 bits, as benchmark seeds can be
# rank 16: U and Vt have columns past the TOP = 10 that subspace_err reads
TINY = {"ssvd_faces": {"m": 1024, "n": 96, "rank": 16},
        "kmeans_1m": {"n": 8192, "k": 8}}


@pytest.fixture(autouse=True)
def _program_state(monkeypatch, tmp_path):
    """Keep what a run sets (the compile cache, span recording) out of
    the other tests this worker runs."""
    from spartan_tpu.utils.config import FLAGS

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = FLAGS.trace, FLAGS.trace_ring
    yield
    FLAGS.trace, FLAGS.trace_ring = saved


def run(workload: str, control: bool = False) -> dict:
    cell = harness.load_cell(harness.load_bench(), workload)
    cell.config.update(TINY[cell.config["name"]])
    return harness.run_cell(cell, SEED, 0.3, False, jax.devices(),
                            control=control)


@pytest.mark.parametrize("workload", ["ssvd_faces.rank100",
                                      "kmeans_1m.fit20_plain"])
def test_sound_run_is_correct(workload):
    line = run(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"step_ms", "setup_s"}


def test_ssvd_evaluates_one_plan_a_call():
    assert run("ssvd_faces.rank100")["diagnostics"][
        "evaluations_per_call"] == 1.0


def test_ssvd_control_is_not_correct():
    line = run("ssvd_faces.rank100", control=True)
    assert not line["correct"]
    # bfloat16 singular values are off by up to 2^-9 of the largest
    assert line["checks"]["sv_rel_err"]["value"] > 1e-3


# -- faults planted in the program ---------------------------------------------


def _no_power_iterations(orig):
    def ssvd(a, rank, n_oversample=10, n_power_iter=2, seed=0):
        return orig(a, rank, n_oversample, 0, seed)
    return ssvd


def _wrong_sketch(orig):
    def ssvd(a, rank, n_oversample=10, n_power_iter=2, seed=0):
        return orig(a, rank, n_oversample, n_power_iter, seed + 1)
    return ssvd


def _column_perturbed(orig):
    def ssvd(a, rank, n_oversample=10, n_power_iter=2, seed=0):
        u, s, vt = orig(a, rank, n_oversample, n_power_iter, seed)
        u = u.copy()
        u[:, 1] += 0.01 * u[:, 0]
        return u, s, vt
    return ssvd


def _tail_swapped(side):
    """Two columns of U, or two rows of Vt, past the leading ten traded:
    U stays orthonormal, s and the leading subspaces stay as they were."""
    def fault(orig):
        def ssvd(a, rank, n_oversample=10, n_power_iter=2, seed=0):
            u, s, vt = orig(a, rank, n_oversample, n_power_iter, seed)
            u, vt = u.copy(), vt.copy()
            if side == "u":
                u[:, [11, 12]] = u[:, [12, 11]]
            else:
                vt[[11, 12]] = vt[[12, 11]]
            return u, s, vt
        return ssvd
    fault.__name__ = f"_tail_swapped_{side}"
    return fault


@pytest.mark.parametrize("fault", [_no_power_iterations, _wrong_sketch,
                                   _column_perturbed, _tail_swapped("u"),
                                   _tail_swapped("vt")],
                         ids=lambda f: f.__name__.strip("_"))
def test_ssvd_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(sv, "ssvd", fault(sv.ssvd))
    line = run("ssvd_faces.rank100")
    assert not line["correct"]
    if "tail" in fault.__name__:
        failed = {n for n, c in line["checks"].items()
                  if c["value"] > c["limit"]}
        assert failed == {"triplet_err"}


def test_triplet_err_reads_the_triplets_against_a():
    """Zero up to rounding for HMT's own triplets, whatever the sketch;
    two columns of U traded put their singular values off the
    diagonal."""
    from reference import ssvd as ref

    rng = np.random.default_rng(3)
    a = rng.standard_normal((300, 40)).astype(np.float32)
    u, s, vt = ref.hmt(a, ref.omega(5, 40, 12), 8, 0)
    assert ref.triplet_err(a, u, s, vt, s[0]) < 1e-12
    u[:, [5, 6]] = u[:, [6, 5]]
    assert ref.triplet_err(a, u, s, vt, s[0]) == pytest.approx(
        np.linalg.norm([[0.0, s[6]], [s[5], 0.0]] - np.diag(s[5:7]), 2)
        / s[0], rel=1e-6)


# -- the readers on a recorded capture ----------------------------------------

# a --trace 1 run of ssvd_faces.rank100 on one v5e: a 0.2 s window, three
# full-size calls
CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "ssvd_rank100_v5e.xplane.pb")


@pytest.fixture(scope="module")
def chip_ctx():
    red = devtrace.reduce(CHIP_TRACE)
    cfg = harness.load_cell(harness.load_bench(),
                            "ssvd_faces.rank100").config
    jobs = sum(1 for name, _, _ in red.spans if name == "bench.job")
    return SimpleNamespace(trace=red, steps=jobs, config=cfg, spans=[],
                           peak=harness.peak("TPU v5 lite"),
                           costs=lambda k: harness.load_module("costs", k))


def test_recorded_capture_holds_six_passes_a_job(chip_ctx):
    reader = harness.load_reader("ssvd.factor_ms")
    ops = harness.load_reader("ssvd_roofline").program_ops(chip_ctx)
    passes = reader.products(chip_ctx, ops)
    assert chip_ctx.steps >= 1 and passes is not None
    assert len(passes) == 6 * chip_ctx.steps
    # nothing in the program copies or transposes A: every other op
    # lasts under a fifth of one pass's least time
    least_ns = harness.load_module("costs", "ssvd").passes(
        chip_ctx.config)["bytes"] / chip_ctx.peak["hbm_bytes_per_s"] * 1e9
    others = [o for o in ops if o not in passes
              and o.kind not in devtrace.CONTAINERS]
    assert max(o.end - o.start for o in others) < 0.2 * least_ns


def test_readers_on_recorded_capture(chip_ctx):
    roof = harness.load_reader("ssvd_roofline").read(chip_ctx)
    factor = harness.load_reader("ssvd.factor_ms").read(chip_ctx)
    assert 0.0 < roof <= 100.0
    assert factor > 0.0
    # the least time of a job's passes: 6 x 4mn bytes at 819 GB/s
    assert harness.load_reader("ssvd_roofline").least_s(
        chip_ctx) == pytest.approx(6 * 4.0 * 98304 * 7254 / 819e9)


def _hand_ctx(steps: int, passes_per_job: int = 6) -> SimpleNamespace:
    """``steps`` calls of the full-size program by hand: each a 4 ms
    product op per pass over A, a 3 ms ``while`` whose body holds ten
    0.2 ms QR ops, and a 1 ms op of another program."""
    from devtrace import Op, Reduction

    red = Reduction(lo=0.0, hi=steps * 40e6)
    ops = []
    for j in range(steps):
        t = j * 40e6
        for p in range(passes_per_job):
            ops.append(Op(f"fusion.{p}", "fusion:kOutput", "jit_traced",
                          t, t + 4e6))
            t += 4e6
        ops.append(Op("while.1", "while", "jit_traced", t, t + 3e6))
        ops += [Op("fusion.9", "fusion:kLoop", "jit_traced",
                   t + i * 0.3e6, t + i * 0.3e6 + 0.2e6) for i in range(10)]
        ops.append(Op("copy.1", "copy", "jit_other", t + 4e6, t + 5e6))
    red.ops = {0: ops}
    cfg = harness.load_cell(harness.load_bench(),
                            "ssvd_faces.rank100").config
    return SimpleNamespace(trace=red, steps=steps, config=cfg, spans=[],
                           peak=harness.peak("TPU v5 lite"),
                           costs=lambda k: harness.load_module("costs", k))


def test_device_readers_by_hand():
    ctx = _hand_ctx(steps=3)
    # busy in the program: 6 x 4 + 3 = 27 ms a call, 24 of them passes
    least = 6 * 4.0 * 98304 * 7254 / 819e9
    assert harness.load_reader("ssvd_roofline").read(ctx) == \
        pytest.approx(100.0 * least / 27e-3)
    assert harness.load_reader("ssvd.factor_ms").read(ctx) == \
        pytest.approx(3.0)
    # a pass too few or too many a call: the identification failed
    for passes in (5, 7):
        assert harness.load_reader("ssvd.factor_ms").read(
            _hand_ctx(steps=3, passes_per_job=passes)) is None


def test_host_reader_takes_the_wait_off():
    ms = 1e6
    ctx = SimpleNamespace(steps=2, spans=[
        ("ssvd", 0.0, 10 * ms, 0), ("fetch_wait", 2 * ms, 8 * ms, 0),
        ("ssvd", 20 * ms, 26 * ms, 0), ("fetch_wait", 21 * ms, 25 * ms, 0),
        ("dispatch", 1 * ms, 2 * ms, 0)])
    # (10 - 6) + (6 - 4) ms over two calls
    assert harness.load_reader("ssvd.host_ms").read(ctx) == 3.0
    assert harness.load_reader("ssvd.host_ms").read(
        SimpleNamespace(steps=2, spans=[])) is None


def test_plain_kmeans_roofline_by_hand():
    """Twenty iterations, each a 10 ms ``jit_traced`` plan holding a
    nested 4 ms fusion, beside a 5 ms op of another program: the busy
    time an iteration is 10 ms, counted once."""
    from devtrace import Op, Reduction

    red = Reduction(lo=0.0, hi=20 * 12e6)
    ops = []
    for i in range(20):
        t = i * 12e6
        ops += [Op("while.1", "while", "jit_traced", t, t + 10e6),
                Op("fusion.3", "fusion:kOutput", "jit_traced", t + 1e6,
                   t + 5e6),
                Op("custom-call", "tpu_custom_call", "jit_run", t + 10e6,
                   t + 15e6)]
    red.ops = {0: ops}
    cell = harness.load_cell(harness.load_bench(), "kmeans_1m.fit20_plain")
    ctx = SimpleNamespace(trace=red, steps=20, config=cell.config,
                          spans=[], peak=harness.peak("TPU v5 lite"),
                          costs=lambda k: harness.load_module("costs", k))
    cfg = cell.config
    # one pass over the float32 points and the centres read and written
    least = (4.0 * cfg["n"] * cfg["d"] + 8.0 * cfg["k"] * cfg["d"]) / 819e9
    assert harness.load_reader("kmeans_plain_roofline").read(ctx) == \
        pytest.approx(100.0 * least / 10e-3)
    assert harness.load_reader("kmeans_plain_roofline").read(
        SimpleNamespace(trace=Reduction(lo=0.0, hi=1.0), steps=20)) is None
