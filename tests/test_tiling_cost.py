"""Smart-tiling cost model tests: assignment shape + result invariance
under the FLAGS toggle (SURVEY.md §7 hard part 4: the ablation is part of
the observable behavior)."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling
from spartan_tpu.expr import optimize
from spartan_tpu.expr.tiling_cost import (assign_tilings, candidates,
                                          reshard_cost)
from spartan_tpu.parallel import mesh as mesh_mod
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _flags():
    yield
    FLAGS.reset_all()


def test_candidates_divisible(mesh2d):
    e = st.zeros((8, 8))
    cands = {t.axes for t in candidates(e, mesh_mod.get_mesh())}
    assert ("x", None) in cands and (None, "y") in cands
    assert ("x", "y") in cands and (None, None) in cands
    # indivisible dims lose their candidates
    e2 = st.zeros((7, 8))
    cands2 = {t.axes for t in candidates(e2, mesh_mod.get_mesh())}
    assert ("x", None) not in cands2


def test_reshard_cost_model(mesh2d):
    m = mesh_mod.get_mesh()
    r, c, rep = tiling.row(2), tiling.col(2), tiling.replicated(2)
    assert reshard_cost(r, r, 1024, m) == 0
    assert reshard_cost(rep, r, 1024, m) == 0  # slicing is local
    assert reshard_cost(r, rep, 1024, m) > 0  # all-gather
    assert reshard_cost(r, c, 1024, m) > 0  # all-to-all


def test_assignment_prefers_sharded_chain(mesh2d):
    x = st.from_numpy(np.ones((64, 64), np.float32), tiling=tiling.row(2))
    y = st.from_numpy(np.ones((64, 64), np.float32), tiling=tiling.row(2))
    expr = ((x + y) * 2.0).optimized()
    # the chain stays on the operands' row axis — either kept as-is or
    # refined to block (a free local slice, no collective); it must NOT
    # move rows to the other mesh axis (that would be an all-to-all)
    assert expr.out_tiling().axes in {("x", None), ("x", "y")}


def test_assignment_avoids_thrash(mesh2d):
    """Mixed-tiling operands: the model picks ONE layout for the chain
    instead of bouncing."""
    x = st.from_numpy(np.ones((64, 64), np.float32), tiling=tiling.row(2))
    y = st.from_numpy(np.ones((64, 64), np.float32), tiling=tiling.col(2))
    expr = (x + y).optimized()
    assert expr.out_tiling().sharded_axes()  # stayed parallel


def test_toggle_equivalence(mesh2d):
    rng = np.random.RandomState(0)
    a = rng.rand(16, 16).astype(np.float32)
    b = rng.rand(16, 16).astype(np.float32)

    def compute():
        ea = st.from_numpy(a, tiling=tiling.row(2))
        eb = st.from_numpy(b, tiling=tiling.col(2))
        return ((ea + eb).dot(ea.T) + 1.0).sum(axis=0).glom()

    FLAGS.opt_auto_tiling = True
    on = compute()
    FLAGS.opt_auto_tiling = False
    off = compute()
    np.testing.assert_allclose(on, off, rtol=1e-4)


def test_single_device_noop():
    m = mesh_mod.build_mesh(mesh_mod.jax.devices()[:1], shape=(1, 1))
    with mesh_mod.use_mesh(m):
        x = st.from_numpy(np.ones((8, 8), np.float32))
        e = (x + 1.0)
        dag = optimize(e)
        assert dag._forced_tiling is None
        np.testing.assert_array_equal(e.glom(), np.full((8, 8), 2.0))


def test_transposed_candidates_present(mesh2d):
    e = st.zeros((8, 8))
    cands = {t.axes for t in candidates(e, mesh_mod.get_mesh())}
    assert ("y", None) in cands  # row on the col mesh axis
    assert (None, "x") in cands  # col on the row mesh axis
    assert ("y", "x") in cands  # transposed block


def test_dot_obeys_chosen_plan(mesh2d):
    """VERDICT r1 #5: the cost model's choice must reach DotExpr.
    Canonical DAG: dot of two arrays row-sharded on the *col* mesh axis
    (row_t) — the receive-bytes + FLOP-priced model routes the GEMM
    onto the psum row arm (rows on x, contraction sharded on y where
    A's columns can cheaply land), which the round-5 measured-arm
    sweep shows is the fastest arm for this combo (pick_vs_best 1.00,
    benchmarks/tiling_sweep.json; the round-4 byte model's block_t
    pick measured 1.8x slower)."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.optimize import dag_nodes

    rng = np.random.RandomState(0)
    a = rng.rand(32, 32).astype(np.float32)
    b = rng.rand(32, 32).astype(np.float32)
    ea = st.from_numpy(a, tiling=tiling.row_t(2))
    eb = st.from_numpy(b, tiling=tiling.row_t(2))
    expr = st.dot(ea, eb).optimized()
    dots = [n for n in dag_nodes(expr) if isinstance(n, DotExpr)]
    assert len(dots) == 1
    assert dots[0]._forced_tiling is not None
    # psum row arm: rows on x, contraction sharded on y
    assert dots[0]._forced_tiling.axes == ("x", None)
    assert dots[0]._dot_strategy == "y"
    np.testing.assert_allclose(np.asarray(expr.glom()), a @ b, rtol=1e-4)


def test_dot_psum_strategy_chosen(mesh2d):
    """Contraction-sharded operands: the plan keeps the data in place
    and pays only the output all-reduce (the psum strategy), matching
    what GSPMD's partial-sum trick does."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.optimize import dag_nodes

    rng = np.random.RandomState(3)
    a = rng.rand(32, 32).astype(np.float32)
    b = rng.rand(32, 32).astype(np.float32)
    ea = st.from_numpy(a, tiling=tiling.row_t(2))  # rows on y
    eb = st.from_numpy(b, tiling=tiling.row(2))    # rows (contraction) on x
    expr = st.dot(ea, eb).optimized()
    d = [n for n in dag_nodes(expr) if isinstance(n, DotExpr)][0]
    assert d._forced_tiling is not None
    assert d._dot_strategy == "x"  # contraction stays where B lives
    np.testing.assert_allclose(np.asarray(expr.glom()), a @ b, rtol=1e-4)


def test_dot_plain_keeps_canonical_block(mesh2d):
    """Without a transposing consumer the pass keeps (or the default
    gives) the canonical block layout — operands row x col."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.optimize import dag_nodes

    rng = np.random.RandomState(1)
    a = rng.rand(32, 32).astype(np.float32)
    ea = st.from_numpy(a, tiling=tiling.row(2))
    eb = st.from_numpy(a, tiling=tiling.col(2))
    expr = st.dot(ea, eb).optimized()
    dots = [n for n in dag_nodes(expr) if isinstance(n, DotExpr)]
    assert dots[0].out_tiling().axes in {("x", "y"), ("y", "x")}
    np.testing.assert_allclose(np.asarray(expr.glom()), a @ a, rtol=1e-4)


def test_auto_tiling_ablation_changes_plan(mesh2d):
    """--opt_auto_tiling off: no forced tilings and no GEMM plan
    anywhere; on: the dot gets a searched plan that reaches its
    lowering (operand constraints + compile-cache key), even when the
    chosen grid equals the default. Results oracle-equal either way."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.optimize import dag_nodes

    rng = np.random.RandomState(2)
    a = rng.rand(16, 16).astype(np.float32)

    FLAGS.opt_auto_tiling = False
    e_off = st.dot(st.from_numpy(a), st.from_numpy(a)).transpose()
    dag_off = optimize(e_off)
    assert all(n._forced_tiling is None for n in dag_nodes(dag_off))
    assert all(getattr(n, "_dot_plan", None) is None
               for n in dag_nodes(dag_off))
    off = np.asarray(e_off.glom())

    FLAGS.opt_auto_tiling = True
    e_on = st.dot(st.from_numpy(a), st.from_numpy(a)).transpose()
    dag_on = optimize(e_on)
    dots = [n for n in dag_nodes(dag_on) if isinstance(n, DotExpr)]
    assert dots and all(d._dot_plan is not None for d in dots)
    np.testing.assert_allclose(np.asarray(e_on.glom()), off, rtol=1e-4)
    np.testing.assert_allclose(off, (a @ a).T, rtol=1e-4)


# -- redistribution-planner edge pricing (ISSUE 10) ----------------------


def _vocab(mesh):
    return (tiling.replicated(2), tiling.row(2), tiling.col(2),
            tiling.block(2), tiling.row_t(2), tiling.col_t(2),
            tiling.block_t(2))


def test_reshard_cost_replicated_roundtrips(mesh2d):
    """replicated <-> row/col/block in BOTH directions: carving a
    replicated source is free; re-replicating a sharded layout pays
    the all-gather fraction."""
    m = mesh_mod.get_mesh()
    rep = tiling.replicated(2)
    for dst in (tiling.row(2), tiling.col(2), tiling.block(2)):
        assert reshard_cost(rep, dst, 1024, m) == 0.0  # local carve
        back = reshard_cost(dst, rep, 1024, m)
        n = 1
        for s in dst.tiles_per_dim(m):
            n *= s
        assert back == pytest.approx(1024 * (n - 1) / n)


def test_edge_cost_monotone_above_receive_floor(mesh2d):
    """Schedule-vs-heuristic monotonicity: the planner's modeled edge
    cost is NEVER below the receive-bytes floor (the minimum a correct
    redistribution must deliver), for every vocabulary pair."""
    from spartan_tpu.parallel import redistribute as rd

    m = mesh_mod.get_mesh()
    for src in _vocab(m):
        for dst in _vocab(m):
            ec = rd.edge_cost(src, dst, 4096.0, m)
            assert ec >= reshard_cost(src, dst, 4096.0, m) - 1e-9


def test_edge_cost_tuple_axes_fall_back(mesh2d):
    """Tuple-sharded mesh axes (flat_row) are outside the step
    vocabulary: no schedules, edge cost falls back to the heuristic."""
    from spartan_tpu.parallel import redistribute as rd

    m = mesh_mod.get_mesh()
    flat = tiling.flat_row(2)
    row = tiling.row(2)
    assert rd.schedules(flat, row, m) == ()
    assert rd.edge_cost(flat, row, 4096.0, m) == pytest.approx(
        reshard_cost(flat, row, 4096.0, m))
    assert rd.edge_cost(row, flat, 4096.0, m) == pytest.approx(
        reshard_cost(row, flat, 4096.0, m))


def test_edge_cost_single_device_degenerate():
    """1-device mesh: nothing moves, nothing is explicit."""
    from spartan_tpu.parallel import redistribute as rd

    m = mesh_mod.build_mesh(mesh_mod.jax.devices()[:1], shape=(1, 1))
    with mesh_mod.use_mesh(m):
        row, rep = tiling.row(2), tiling.replicated(2)
        assert rd.edge_cost(row, rep, 1024.0, m) == 0.0
        d = rd.decide(row, rep, (8, 8), np.float32, m)
        assert d is None or not d.explicit


def test_planner_flag_changes_dp_edge_prices(mesh2d):
    """The DP's edge pricing is schedule-modeled under the flag: a
    block -> block_t style transition prices at the cheaper collective
    route, not the gather-everything heuristic's upper bound — and
    with the flag off the legacy heuristic is untouched."""
    from spartan_tpu.parallel import redistribute as rd

    m = mesh_mod.get_mesh()
    src, dst = tiling.row(2), tiling.col_t(2)  # ('x',None)->(None,'x')
    heur = reshard_cost(src, dst, 4096.0, m)
    planned = rd.edge_cost(src, dst, 4096.0, m)
    # the all_to_all schedule achieves exactly the receive floor here
    assert planned == pytest.approx(heur)
    sched = rd.schedules(src, dst, m)
    assert any(s.steps[0].kind == "all_to_all" and len(s.steps) == 1
               for s in sched)


# -- contraction pricing per platform ------------------------------------


def _shape_only_leaf(n, axes, dtype=np.float32):
    """An n x n operand the planner sees by shape, dtype and tiling
    alone: nothing is allocated."""
    from types import SimpleNamespace

    from spartan_tpu.expr.base import ValExpr

    return ValExpr(SimpleNamespace(shape=(n, n), dtype=np.dtype(dtype),
                                   tiling=tiling.Tiling(axes)))


@pytest.mark.parametrize("platform,plan", [
    # the chip gathers bf16 panels: the gathered (x, y) plan, no psum
    ("tpu", (("x", "y"), None)),
    # the CPU mesh's pick is what it was before platforms were told
    # apart: rows on x, the contraction sharded on y
    ("cpu", (("x", None), "y")),
])
def test_gemm_8192_plan_per_platform(monkeypatch, platform, plan):
    """The dot_8192 cell's GEMM — 8192^2 f32 operands tiled (x, y) on
    a 2x2 mesh, default precision — is planned as its platform runs
    it."""
    from spartan_tpu.expr import tiling_cost

    monkeypatch.setattr(tiling_cost, "_platform",
                        lambda mesh=None: platform)
    m = mesh_mod.build_mesh(mesh_mod.jax.devices()[:4], shape=(2, 2))
    with mesh_mod.use_mesh(m):
        d = assign_tilings(st.dot(_shape_only_leaf(8192, ("x", "y")),
                                  _shape_only_leaf(8192, ("x", "y"))))
    assert (d._dot_plan[0].axes, d._dot_plan[1]) == plan


@pytest.mark.parametrize("platform,precision,dtype,width", [
    ("tpu", None, np.float32, 0.5),
    ("tpu", "default", np.float32, 0.5),
    ("tpu", "highest", np.float32, 1.0),
    ("tpu", "high", np.float32, 1.0),
    ("tpu", None, np.int32, 1.0),
    ("cpu", None, np.float32, 1.0),
])
def test_operand_moved_width(platform, precision, dtype, width):
    """Operand moves are priced at the width the chip moves them: on
    TPU at a one-pass precision a float32 operand crosses the
    interconnect as bf16; at HIGH or HIGHEST, or off TPU, in full."""
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.tiling_cost import _moved_width

    d = DotExpr(_shape_only_leaf(64, (None, None), dtype),
                _shape_only_leaf(64, (None, None), dtype), precision)
    assert _moved_width(d, platform) == (width, width)
