"""Delta-aware incremental evaluation (ISSUE 16): the lineage-logged
mutation seam (``DistArray.update``), dirty propagation through the
raw DAG, restrict+splice bit-equality against full recomputes, the
honest-fallback contract (reasons in metrics/explain), mesh-epoch
fencing, donation hygiene, and the chaos leg (a transient fault
mid-incremental-dispatch degrades to a full recompute)."""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import distarray as da_mod
from spartan_tpu.array.distarray import _MUTLOG_MAX, Lineage
from spartan_tpu.array.extent import TileExtent
from spartan_tpu.expr import base as expr_base
from spartan_tpu.expr import incremental as inc
from spartan_tpu.expr.base import evaluate, lazify
from spartan_tpu.parallel import mesh as mesh_mod
from spartan_tpu.utils import profiling as prof
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _setup(mesh2d):
    saved = {n: getattr(FLAGS, n) for n in (
        "incremental", "result_cache_bytes",
        "incremental_max_dirty_frac", "retry_max", "retry_backoff_s")}
    FLAGS.incremental = True
    FLAGS.retry_backoff_s = 0.0
    inc.clear()
    st.chaos_clear()
    yield
    st.chaos_clear()
    inc.clear()
    for n, v in saved.items():
        setattr(FLAGS, n, v)


def _counter(name):
    return prof.counters().get(name, 0)


def _rand(shape, seed=0):
    return np.random.RandomState(seed).rand(*shape).astype(np.float32)


def _arr(a):
    return da_mod.from_numpy(np.ascontiguousarray(a))


def _full_reference(build, *np_args):
    """The oracle: the same DAG over FRESH arrays with the engine off —
    an ordinary full dispatch of identical data."""
    prev = FLAGS.incremental
    FLAGS.incremental = False
    try:
        out = evaluate(build(*[_arr(a) for a in np_args]))
        return out.glom()
    finally:
        FLAGS.incremental = prev


# -- the lineage log (array/distarray.py) --------------------------------


def test_lineage_bbox_and_overflow():
    shape = (16, 16)
    lin = Lineage()
    v0 = lin.latest
    lin.note(TileExtent((0, 0), (2, 2), shape))
    lin.note(TileExtent((4, 4), (6, 8), shape))
    box = lin.dirty_between(v0, lin.latest, shape)
    assert (tuple(box.ul), tuple(box.lr)) == ((0, 0), (6, 8))
    # an empty version range is clean (no box, nothing dropped)
    assert lin.dirty_between(lin.latest, lin.latest, shape) is None
    # a whole-array marker poisons any range containing it
    lin.note(None)
    assert lin.dirty_between(v0, lin.latest, shape) is None

    # overflow collapses the bounded log to one whole-array marker
    lin2 = Lineage()
    for _ in range(_MUTLOG_MAX + 5):
        lin2.note(TileExtent((0, 0), (1, 1), shape))
    assert lin2.dirty_between(0, lin2.latest, shape) is None
    # versions that fell off the log also read as whole-array
    lin3 = Lineage()
    first = lin3.note(TileExtent((0, 0), (1, 1), shape))
    for _ in range(_MUTLOG_MAX):
        lin3.note(TileExtent((2, 2), (3, 3), shape))
    assert lin3.dirty_between(first - 1, lin3.latest, shape) is None


def test_update_threads_lineage_and_values():
    a_np = _rand((16, 16))
    a = _arr(a_np)
    b = a.update((slice(2, 4), slice(0, 16)),
                 np.zeros((2, 16), np.float32))
    assert b is not a
    assert b._lineage is a._lineage  # shared family history
    assert b._version == a._version + 1
    box = b._lineage.dirty_between(a._version, b._version, a.shape)
    assert (tuple(box.ul), tuple(box.lr)) == ((2, 0), (4, 16))
    host = b.glom()
    assert np.array_equal(host[2:4], np.zeros((2, 16), np.float32))
    assert np.array_equal(host[:2], a_np[:2])
    assert np.array_equal(host[4:], a_np[4:])
    # the parent handle is untouched (functional update)
    assert np.array_equal(a.glom(), a_np)


# -- warm-path behavior ---------------------------------------------------


def test_all_clean_warm_evaluate_is_zero_dispatch():
    a = _arr(_rand((32, 32)))
    r1 = evaluate(lazify(a) * 2.0 + 1.0)
    h0 = _counter("incremental_hits")
    r2 = evaluate(lazify(a) * 2.0 + 1.0)
    # byte-identical leaves: the cached result IS the answer
    assert r2 is r1
    assert _counter("incremental_hits") == h0 + 1


def test_map_delta_is_incremental_and_bitequal():
    a_np = _rand((64, 64))
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) * 3.0 + 0.5

    evaluate(build(a))  # seed the result cache
    a2 = a.update((slice(10, 12), slice(0, 64)), 7.0)
    a2_np = a_np.copy()
    a2_np[10:12] = 7.0
    h0 = _counter("incremental_hits")
    t0 = _counter("incremental_recomputed_tiles")
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))
    assert _counter("incremental_hits") == h0 + 1
    assert _counter("incremental_recomputed_tiles") > t0
    assert _counter("incremental_fallbacks") == f0
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))


def test_overlapping_updates_coalesce_to_bbox():
    a_np = _rand((64, 64), seed=3)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) + 1.0

    evaluate(build(a))
    a2 = a.update((slice(4, 8), slice(0, 64)), 1.0)
    a3 = a2.update((slice(6, 10), slice(0, 64)), 2.0)  # overlaps a2's
    ref = a_np.copy()
    ref[4:8] = 1.0
    ref[6:10] = 2.0
    h0 = _counter("incremental_hits")
    r = evaluate(build(a3))
    assert _counter("incremental_hits") == h0 + 1
    assert np.array_equal(r.glom(), _full_reference(build, ref))


def test_full_overwrite_falls_back_with_reason():
    a_np = _rand((32, 32), seed=1)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) * 2.0

    evaluate(build(a))
    new = _rand((32, 32), seed=2)
    a2 = a.update((slice(0, 32), slice(0, 32)), new)
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))
    # 100% dirty: a full recompute is cheaper; reason is 'dirty-frac'
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r.glom(), _full_reference(build, new))
    rep = str(st.explain(build(a2)))
    assert "incremental: full" in rep
    assert "dirty-frac" in rep


def test_multi_leaf_updates_union_and_bitequal():
    a_np, b_np = _rand((64, 64), 5), _rand((64, 64), 6)
    a, b = _arr(a_np), _arr(b_np)

    def build(x, y):
        return lazify(x) * 2.0 + lazify(y)

    evaluate(build(a, b))
    a2 = a.update((slice(0, 2), slice(0, 64)), 3.0)
    b2 = b.update((slice(6, 8), slice(0, 64)), 4.0)
    a2_np = a_np.copy()
    a2_np[0:2] = 3.0
    b2_np = b_np.copy()
    b2_np[6:8] = 4.0
    h0 = _counter("incremental_hits")
    r = evaluate(build(a2, b2))
    assert _counter("incremental_hits") == h0 + 1
    assert np.array_equal(
        r.glom(), _full_reference(build, a2_np, b2_np))
    # one dirty + one clean leaf also stays incremental and exact
    a3 = a2.update((slice(20, 22), slice(0, 64)), 9.0)
    a3_np = a2_np.copy()
    a3_np[20:22] = 9.0
    r2 = evaluate(build(a3, b2))
    assert np.array_equal(
        r2.glom(), _full_reference(build, a3_np, b2_np))


def test_reduce_axis_delta_bitequal():
    a_np = _rand((64, 32), seed=7)
    a = _arr(a_np)

    def build(arr):
        return (lazify(arr) * 2.0).sum(axis=1)

    evaluate(build(a))
    a2 = a.update((slice(12, 14), slice(0, 32)), 5.0)
    a2_np = a_np.copy()
    a2_np[12:14] = 5.0
    h0 = _counter("incremental_hits")
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))
    assert _counter("incremental_hits") == h0 + 1
    assert _counter("incremental_fallbacks") == f0
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))


def test_reduce_all_falls_back_and_stays_correct():
    a_np = _rand((32, 32), seed=8)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr).sum()

    evaluate(build(a))
    a2 = a.update((slice(0, 1), slice(0, 4)), 2.0)
    a2_np = a_np.copy()
    a2_np[0, 0:4] = 2.0
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))
    # reduce_all: every output element sees the dirt -> honest full
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))


def test_dot_column_delta_bitequal():
    n = 64
    r_np = _rand((n,), seed=9)
    a_np = _rand((n, n), seed=10)
    r0, A = _arr(r_np), _arr(a_np)

    def build(rank, mat):
        return lazify(rank).dot(lazify(mat)) * 0.85 + 0.15 / n

    evaluate(build(r0, A))
    patch = _rand((n, 2), seed=11)
    A2 = A.update((slice(0, n), slice(6, 8)), patch)
    a2_np = a_np.copy()
    a2_np[:, 6:8] = patch
    h0 = _counter("incremental_hits")
    t0 = _counter("incremental_recomputed_tiles")
    r = evaluate(build(r0, A2))
    assert _counter("incremental_hits") == h0 + 1
    assert _counter("incremental_recomputed_tiles") > t0
    assert np.array_equal(
        r.glom(), _full_reference(build, r_np, a2_np))


@pytest.mark.parametrize("split_k", [False, True])
def test_matmul_row_delta_bitequal(split_k):
    """Dirty rows of A recompute only those output rows — unless the
    contracted dim is split over the mesh: the full plan then sums
    per-shard partial products, which a restricted dot would round
    differently, so the engine recomputes in full. Bit-equal both ways."""
    from spartan_tpu.array import tiling

    a_np = _rand((64, 32), seed=12)
    b_np = _rand((32, 48), seed=13)
    ta = tiling.Tiling(("x", "y")) if split_k else tiling.row(2)
    tb = tiling.replicated(2)

    def arrs(x, y):
        return (da_mod.from_numpy(np.ascontiguousarray(x), tiling=ta),
                da_mod.from_numpy(np.ascontiguousarray(y), tiling=tb))

    def build(x, y):
        return lazify(x) @ lazify(y)

    a, b = arrs(a_np, b_np)
    evaluate(build(a, b))
    a2 = a.update((slice(30, 32), slice(0, 32)), 0.25)
    a2_np = a_np.copy()
    a2_np[30:32] = 0.25
    h0 = _counter("incremental_hits")
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2, b))
    assert _counter("incremental_hits") == h0 + (0 if split_k else 1)
    assert _counter("incremental_fallbacks") == f0 + (1 if split_k else 0)
    FLAGS.incremental = False
    ref = evaluate(build(*arrs(a2_np, b_np))).glom()
    assert np.array_equal(r.glom(), ref)


def test_loop_carry_falls_back_full_and_stays_correct():
    from spartan_tpu.expr.loop import loop as st_loop

    a_np = _rand((16, 16), seed=14)
    a = _arr(a_np)

    def build(arr):
        la = lazify(arr)
        return st_loop(3, lambda x: x * 0.5 + la, la)

    evaluate(build(a))
    a2 = a.update((slice(0, 2), slice(0, 16)), 1.0)
    a2_np = a_np.copy()
    a2_np[0:2] = 1.0
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))
    # loop bodies have no propagation rule: whole-node dirty -> full
    assert _counter("incremental_fallbacks") >= f0 + 1
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))


def test_shuffle_output_new_identity_falls_back_full():
    from spartan_tpu.expr.shuffle import shuffle

    a_np = _rand((16, 16), seed=15)

    def transpose_kernel(ext, block):
        yield (TileExtent((ext.ul[1], ext.ul[0]),
                          (ext.lr[1], ext.lr[0]), (16, 16)),
               np.ascontiguousarray(block.T))

    def run():
        src = shuffle(_arr(a_np), transpose_kernel,
                      target_shape=(16, 16), dtype=np.float32)
        return evaluate(src * 2.0)

    r1 = run()
    f0 = _counter("incremental_fallbacks")
    r2 = run()  # same plan, but the shuffled leaf is a NEW identity
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r1.glom(), 2.0 * a_np.T)
    assert np.array_equal(r2.glom(), r1.glom())


def test_scalar_constant_change_falls_back_full():
    a_np = _rand((32, 32), seed=16)
    a = _arr(a_np)
    evaluate(lazify(a) * 2.0)
    f0 = _counter("incremental_fallbacks")
    # same plan (scalar signatures are value-free), different constant:
    # a changed scalar feeds everything -> honest full recompute
    r = evaluate(lazify(a) * 3.0)
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r.glom(), np.float32(3.0) * a_np)


def test_update_inside_loop_body_stream():
    """The streaming shape: update between warm steps of one plan."""
    from spartan_tpu.expr.loop import loop as st_loop

    a_np = _rand((16, 16), seed=17)
    a = _arr(a_np)

    def build(arr):
        return st_loop(2, lambda x: x * 0.5, lazify(arr))

    r = evaluate(build(a))
    cur_np = a_np.copy()
    for i in range(3):
        a = a.update((slice(i, i + 1), slice(0, 16)), float(i))
        cur_np[i] = float(i)
        r = evaluate(build(a))
        assert np.array_equal(r.glom(), _full_reference(build, cur_np))


# -- propagation rules (whitebox) ----------------------------------------


def test_propagation_map_box_passthrough_and_broadcast_full():
    a = lazify(_arr(_rand((8, 8))))
    b = lazify(_arr(_rand((8,))))
    ex = a + b
    box = TileExtent((2, 0), (4, 8), (8, 8))
    r = inc._propagate(ex, {a._id: box}, {}, [])
    assert (tuple(r.ul), tuple(r.lr)) == ((2, 0), (4, 8))
    # a dirty broadcast child (shape differs) dirties the whole node
    r2 = inc._propagate(ex, {b._id: TileExtent((0,), (2,), (8,))},
                        {}, [])
    assert r2 is inc.FULL
    # clean everywhere: None
    assert inc._propagate(ex, {}, {}, []) is None


def test_propagation_reduce_collapse_rules():
    a = lazify(_arr(_rand((8, 8))))
    box = TileExtent((2, 1), (4, 3), (8, 8))
    # axis drop: rows survive, reduced axis disappears
    r = inc._propagate((a * 2.0).sum(axis=1), {a._id: box}, {}, [])
    assert (tuple(r.ul), tuple(r.lr)) == ((2,), (4,))
    # keepdims: reduced axis collapses to [0, 1)
    rk = inc._propagate((a * 2.0).sum(axis=0, keepdims=True),
                        {a._id: box}, {}, [])
    assert (tuple(rk.ul), tuple(rk.lr)) == ((0, 1), (1, 3))
    # reduce_all: FULL
    assert inc._propagate(
        (a * 2.0).sum(), {a._id: box}, {}, []) is inc.FULL


def test_propagation_dot_rules():
    from spartan_tpu.expr.dot import DotExpr

    a = lazify(_arr(_rand((8, 4))))
    b = lazify(_arr(_rand((4, 6))))
    ex = DotExpr(a, b)
    rows = TileExtent((2, 0), (5, 4), (8, 4))
    r = inc._propagate(ex, {a._id: rows}, {}, [])
    assert (tuple(r.ul), tuple(r.lr)) == ((2, 0), (5, 6))
    cols = TileExtent((0, 1), (4, 3), (4, 6))
    r2 = inc._propagate(ex, {b._id: cols}, {}, [])
    assert (tuple(r2.ul), tuple(r2.lr)) == ((0, 1), (8, 3))
    # both sides dirty: FULL (cross terms everywhere)
    assert inc._propagate(
        ex, {a._id: rows, b._id: cols}, {}, []) is inc.FULL


def test_quantize_pow2_and_clamped():
    q = inc._quantize(TileExtent((3, 5), (6, 9), (16, 16)), (16, 16))
    assert (tuple(q.ul), tuple(q.lr)) == ((3, 5), (7, 9))  # 4, 4 wide
    # clamped to the dim and slid in-bounds
    q2 = inc._quantize(TileExtent((15, 0), (16, 16), (16, 16)),
                       (16, 16))
    assert (tuple(q2.ul), tuple(q2.lr)) == ((15, 0), (16, 16))
    q3 = inc._quantize(TileExtent((10, 0), (16, 1), (16, 16)), (16, 16))
    assert q3.lr[0] - q3.ul[0] == 8 and q3.lr[0] <= 16


# -- fencing, donation, budget -------------------------------------------


def test_epoch_fence_purges_entries():
    a = _arr(_rand((16, 16)))
    evaluate(lazify(a) + 1.0)
    assert inc.cache_entries() >= 1
    assert inc.evict_stale() == 0  # current epoch: nothing stale
    mesh_mod._EPOCH += 1
    try:
        expr_base.evict_stale_plans()
        assert inc.cache_entries() == 0
        assert inc.cache_bytes() == 0
    finally:
        mesh_mod._EPOCH -= 1


def test_update_after_donation_raises_with_site():
    a = _arr(_rand((16, 16)))
    ex = lazify(a) * 2.0
    a.donate()
    evaluate(ex)  # consumes the donated buffer
    assert a.is_donated
    with pytest.raises(RuntimeError, match="after donation.*donated at"):
        a.update((slice(0, 2), slice(0, 4)), 0.0)


def test_donated_leaf_evaluate_falls_back():
    a = _arr(_rand((16, 16), seed=18))
    evaluate(lazify(a) * 2.0)  # seed
    ex = lazify(a) * 2.0
    a.donate()
    f0 = _counter("incremental_fallbacks")
    r = evaluate(ex)  # donating dispatch: never served from cache
    assert _counter("incremental_fallbacks") == f0 + 1
    assert a.is_donated
    assert r.glom().shape == (16, 16)


def test_donated_cached_result_drops_entry():
    a_np = _rand((16, 16), seed=19)
    a = _arr(a_np)
    r1 = evaluate(lazify(a) * 2.0)
    consume = lazify(r1) + 1.0
    r1.donate()
    evaluate(consume)
    assert r1.is_donated
    f0 = _counter("incremental_fallbacks")
    r2 = evaluate(lazify(a) * 2.0)
    # the entry held a donated buffer: dropped on touch, full dispatch
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r2.glom(), np.float32(2.0) * a_np)


def test_result_cache_budget_is_bounded():
    one = int(np.prod((32, 32))) * 4  # one f32 result
    FLAGS.result_cache_bytes = 2 * one + 64
    for seed in range(4):  # 4 distinct plans' results
        a = _arr(_rand((32, 32), seed=seed))
        evaluate(lazify(a) * float(seed + 2))
    assert inc.cache_bytes() <= FLAGS.result_cache_bytes
    assert inc.cache_entries() <= 2
    # a single result over budget is never cached
    inc.clear()
    FLAGS.result_cache_bytes = one - 1
    a = _arr(_rand((32, 32), seed=9))
    evaluate(lazify(a) * 2.0)
    assert inc.cache_entries() == 0


def test_flag_off_no_cache_activity():
    FLAGS.incremental = False
    inc.clear()
    a_np = _rand((32, 32), seed=20)
    a = _arr(a_np)
    h0 = _counter("incremental_hits")
    f0 = _counter("incremental_fallbacks")
    evaluate(lazify(a) + 1.0)
    a2 = a.update((slice(0, 2), slice(0, 32)), 5.0)
    r = evaluate(lazify(a2) + 1.0)
    a2_np = a_np.copy()
    a2_np[0:2] = 5.0
    assert np.array_equal(r.glom(), a2_np + np.float32(1.0))
    assert inc.cache_entries() == 0
    assert _counter("incremental_hits") == h0
    assert _counter("incremental_fallbacks") == f0


# -- chaos leg ------------------------------------------------------------


def test_chaos_mid_incremental_dispatch_degrades_to_full():
    FLAGS.retry_max = 0  # let the transient escape the inner evaluate
    a_np = _rand((64, 64), seed=21)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) * 2.0 + 1.0

    evaluate(build(a))  # seed the warm path
    a2 = a.update((slice(4, 6), slice(0, 64)), 3.0)
    a2_np = a_np.copy()
    a2_np[4:6] = 3.0
    f0 = _counter("incremental_fallbacks")
    with st.chaos("transient@0"):
        # the fault fires in the restricted sub-dispatch; the engine
        # degrades to the ordinary full path, which succeeds
        r = evaluate(build(a2))
    assert _counter("incremental_fallbacks") == f0 + 1
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))
    rep = str(st.explain(build(a2)))
    assert "fallback: error:" in rep


# -- observability --------------------------------------------------------


def test_explain_shows_incremental_section():
    a_np = _rand((64, 64), seed=22)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) * 2.0

    evaluate(build(a))
    a2 = a.update((slice(8, 10), slice(0, 64)), 1.5)
    evaluate(build(a2))
    rep = str(st.explain(build(a2)))
    assert "incremental: incremental" in rep
    assert "dirty_frac=" in rep
    assert "box (" in rep
    assert "dirty" in rep and "tile(s)" in rep
    # an all-clean warm read reports the cache hit
    evaluate(build(a2))
    rep2 = str(st.explain(build(a2)))
    assert "incremental: cache-hit" in rep2


def test_flightrec_and_metrics_surface_incremental():
    a = _arr(_rand((32, 32), seed=23))
    evaluate(lazify(a) * 4.0)
    evaluate(lazify(a) * 4.0)  # warm hit
    snap = st.flightrec()
    assert "incremental" in snap
    assert snap["incremental"].get("incremental_hits", 0) >= 1
    assert "incremental_cache_bytes" in snap["incremental"]
    counters = st.metrics()["counters"]
    assert counters.get("incremental_hits", 0) >= 1


def test_memory_governor_sees_result_cache():
    from spartan_tpu.resilience import memory as mem_mod

    mesh = mesh_mod.get_mesh()
    assert mem_mod.resident_cache_bytes_per_chip(mesh) == 0
    a = _arr(_rand((32, 32), seed=24))
    evaluate(lazify(a) + 2.0)
    assert inc.cache_bytes() > 0
    per_chip = mem_mod.resident_cache_bytes_per_chip(mesh)
    ndev = 1
    for v in dict(mesh.shape).values():
        ndev *= v
    assert per_chip == inc.cache_bytes() // ndev


# -- the mutation-seam stash (gather-free restricted leaves) -------------


def test_stash_serves_delta_without_dynamic_slice(monkeypatch):
    """A single 'set' write stashes its post-write values; the engine
    restricts to the EXACT (un-quantized) box and takes the stash as a
    materialized leaf — no traced-start slice of the sharded parent
    (which GSPMD can only lower to a gather of the sliced dim)."""
    n, w = 64, 3  # w deliberately not a power of two
    a_np = _rand((n, n), seed=30)
    r_np = _rand((n,), seed=31)
    a, r = _arr(a_np), _arr(r_np)

    calls = []
    orig = inc._dyn_slice
    monkeypatch.setattr(inc, "_dyn_slice",
                        lambda nn, box: calls.append(1) or orig(nn, box))

    def build(arr):
        return lazify(r).dot(lazify(arr)) * 0.5 + 0.1

    evaluate(build(a))
    cols = _rand((n, w), seed=32)
    a2 = a.update((slice(0, n), slice(5, 5 + w)), cols)
    assert a2._lineage.stashed_between(a._version, a2._version) is not None
    h0 = _counter("incremental_hits")
    out = evaluate(build(a2))
    assert _counter("incremental_hits") == h0 + 1
    assert not calls  # the stash replaced every dynamic-slice leaf
    a2_np = a_np.copy()
    a2_np[:, 5:5 + w] = cols
    assert np.array_equal(out.glom(),
                          _full_reference(lambda x: build(x), a2_np))


def test_stash_absent_for_reducers_and_sequential_writes():
    """Combine reducers' post-write values only exist inside the full
    array (no stash), and stashes of sequential writes don't compose —
    both degrade to the quantized dynamic-slice path, never to a wrong
    answer."""
    a = _arr(_rand((16, 16), seed=33))
    b = a.update((slice(0, 16), slice(2, 4)), 1.5, reducer="add")
    assert b._lineage.stashed_between(a._version, b._version) is None
    c = _arr(_rand((16, 16), seed=34))
    d = c.update((slice(0, 16), slice(0, 2)), 1.0)
    e = d.update((slice(0, 16), slice(1, 3)), 2.0)
    assert e._lineage.stashed_between(c._version, e._version) is None
    # the single-write window on the same lineage still stashes
    assert e._lineage.stashed_between(d._version, e._version) is not None


def test_stash_respects_byte_cap(monkeypatch):
    monkeypatch.setattr(Lineage, "_STASH_MAX_BYTES", 8)
    a = _arr(_rand((16, 16), seed=35))
    b = a.update((slice(0, 16), slice(0, 4)), 3.0)  # 256 bytes > cap
    assert b._lineage.stashed_between(a._version, b._version) is None
    # the oversized write is still lineage-logged (correctness intact)
    box = b._lineage.dirty_between(a._version, b._version, a.shape)
    assert (tuple(box.ul), tuple(box.lr)) == ((0, 0), (16, 4))


# -- lineage branching (update() is functional: histories may fork) ------


def test_branching_update_gets_fresh_lineage():
    base = _arr(_rand((16, 16), seed=36))
    a = base.update((slice(0, 2), slice(0, 16)), 5.0)
    assert a._lineage is base._lineage
    # a second child cut from the same (now non-tip) parent forks the
    # history: it must NOT share the sibling's log
    b = base.update((slice(4, 6), slice(0, 16)), 7.0)
    assert b._lineage is not a._lineage
    assert base._lineage is a._lineage  # the parent keeps its original
    # the branch's own chain is linear again from here on
    c = b.update((slice(8, 10), slice(0, 16)), 9.0)
    assert c._lineage is b._lineage
    box = c._lineage.dirty_between(b._version, c._version, b.shape)
    assert (tuple(box.ul), tuple(box.lr)) == ((8, 0), (10, 16))


def test_branching_update_is_not_served_a_sibling_delta():
    """a = base.update(r1) warms the cache; b = base.update(r2) shares
    base but LACKS a's write. Treating the lineage as one linear chain
    would splice only r2 over a's cached result and serve a's stale r1
    rows — b must be bit-equal to a full recompute."""
    a_np = _rand((32, 32), seed=37)
    base = _arr(a_np)

    def build(arr):
        return lazify(arr) * 2.0 + 1.0

    evaluate(build(base))  # seed the cache at base
    a = base.update((slice(0, 2), slice(0, 32)), 5.0)
    evaluate(build(a))  # the entry now snapshots a (r1 spliced in)
    b = base.update((slice(4, 6), slice(0, 32)), 7.0)  # the branch
    r = evaluate(build(b))
    b_np = a_np.copy()
    b_np[4:6] = 7.0
    assert np.array_equal(r.glom(), _full_reference(build, b_np))


# -- residency accounting (entry pins leaves; lineage pins stash) --------


def test_cache_accounting_includes_leaf_snapshots_and_stash():
    inc.clear()
    one = int(np.prod((32, 32))) * 4  # one f32 buffer
    a_np = _rand((32, 32), seed=38)
    a = _arr(a_np)
    evaluate(lazify(a) + 1.0)
    # the entry pins the result AND the leaf snapshot: both charged
    assert inc.cache_bytes() >= 2 * one
    a2 = a.update((slice(0, 2), slice(0, 32)), 3.0)
    evaluate(lazify(a2) + 1.0)  # warm splice re-snapshots a2
    lin = a2._lineage
    assert lin is not None and lin.stash_bytes > 0
    # the mutation-seam stash the cached snapshot keeps alive is
    # governor-visible too
    assert inc.cache_bytes() >= 2 * one + lin.stash_bytes


# -- dirt-phase failures honor the honest-fallback contract --------------


def test_dirt_phase_error_degrades_to_full(monkeypatch):
    a_np = _rand((32, 32), seed=39)
    a = _arr(a_np)

    def build(arr):
        return lazify(arr) * 3.0

    evaluate(build(a))  # seed the warm path
    a2 = a.update((slice(0, 2), slice(0, 32)), 1.0)

    def boom(*_a, **_k):
        raise ValueError("malformed node")

    monkeypatch.setattr(inc, "_propagate", boom)
    f0 = _counter("incremental_fallbacks")
    r = evaluate(build(a2))  # propagation blows up -> full dispatch
    assert _counter("incremental_fallbacks") == f0 + 1
    a2_np = a_np.copy()
    a2_np[0:2] = 1.0
    assert np.array_equal(r.glom(), _full_reference(build, a2_np))
