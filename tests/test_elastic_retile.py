"""Cross-mesh elastic re-tiling (ISSUE 14): planned, chaos-hardened
migration that survives host loss.

Covers the tier-1-safe half of the tentpole on the 8-virtual-CPU-device
world: cross-MESH-SHAPE transition planning (divisible direct
repartition vs reasoned gather fallback, flat_row status), the planned
rehome/restore migration pipeline (schedule + bytes + route + reason in
``_migration`` records, ``elastic_*`` metrics and ``st.explain``),
recovery idempotency under chaos injected DURING recovery (the
``recover`` fault seam), donated-handle rehome skips, and cross-replica
loop-carry sharding (``FLAGS.shard_loop_carries``). The N-process
``jax.distributed`` leg lives in ``tests/test_multihost.py``; this file
is the simulated-shrink coverage that runs everywhere.
"""

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.array import tiling
from spartan_tpu.parallel import mesh as mesh_mod
from spartan_tpu.parallel import redistribute as rd
from spartan_tpu.resilience import classify as cls
from spartan_tpu.resilience import elastic, engine, faults
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _world(mesh2d):
    """Every test here may mutate global mesh state (epoch, survivor
    set) and the retry engine: restore the seed world afterwards."""
    saved = {n: getattr(FLAGS, n) for n in (
        "retry_backoff_s", "shard_loop_carries", "shard_carry_min_bytes",
        "redistribution_planner", "elastic_recovery")}
    FLAGS.retry_backoff_s = 0.0
    engine.reset()
    st.chaos_clear()
    yield mesh2d
    st.chaos_clear()
    engine.reset()
    from spartan_tpu.serve import shutdown_default

    shutdown_default()
    mesh_mod.reset_epoch_for_tests()
    for n, v in saved.items():
        setattr(FLAGS, n, v)


def _counter(name):
    return st.metrics()["counters"].get(name, 0)


SRC = {"x": 4, "y": 2}
DST = {"x": 3, "y": 2}


# -- cross-mesh-shape planning (parallel/redistribute) -------------------


def test_plan_transition_divisible_direct():
    """A row tiling whose axis divides BOTH grids repartitions
    directly: single transfer step, per-chip receive = the survivor
    shard, not the full gather."""
    d = rd.plan_transition(tiling.row(2), tiling.row(2), SRC, DST,
                          (24, 8), np.float32)
    assert d.route == "direct"
    assert d.schedule is not None
    assert [s.kind for s in d.schedule.steps] == ["transfer"]
    nbytes = 24 * 8 * 4
    assert d.bytes == pytest.approx(nbytes / 3)  # one dst-row shard
    assert "transfer" in d.reason


def test_plan_transition_indivisible_gathers():
    """8 rows do not divide the 3-way survivor grid: the direct route
    would mis-slice padded shards, so the planner emits the reasoned
    gather fallback."""
    d = rd.plan_transition(tiling.row(2), tiling.row(2), SRC, DST,
                          (8, 8), np.float32)
    assert d.route == "gather"
    assert "indivisible" in d.reason and "survivor" in d.reason


def test_plan_transition_flat_row_reasoned_fallback():
    """Tuple-sharded (flat_row) axes are outside the step vocabulary:
    the fallback is REASONED (named in the record), not silent, and
    the modeled bytes reflect the gather of the two-axis split."""
    d = rd.plan_transition(tiling.flat_row(2), tiling.row(2), SRC, DST,
                          (24, 8), np.float32)
    assert d.route == "gather"
    assert d.schedule is None
    assert "flat_row" in d.reason
    nbytes = 24 * 8 * 4
    assert d.bytes == pytest.approx(nbytes * (1 - 1 / 8))  # 8-way split


def test_plan_transition_replicated_is_free():
    """Replicated -> replicated across a shrink moves nothing: every
    survivor already holds a full copy."""
    d = rd.plan_transition(tiling.replicated(2), tiling.replicated(2),
                          SRC, DST, (24, 8), np.float32)
    assert d.route == "direct" and d.bytes == 0.0


def test_plan_transition_multi_step_schedule():
    """A sharded source whose destination wants a DIFFERENT axis
    decomposes into the multi-step gather + transfer + slice schedule
    — and the transfer of the replicated intermediate is free."""
    d = rd.plan_transition(tiling.row(2), tiling.row_t(2), SRC, DST,
                          (24, 8), np.float32)
    assert d.schedule is not None
    kinds = [s.kind for s in d.schedule.steps]
    assert kinds == ["all_gather", "transfer", "slice"]
    # comm: the src-grid gather only — transfer free, slice local
    assert set(d.schedule.comm_frac) == {"all_gather"}


def test_cross_mesh_cheaper_than_gather_when_divisible():
    """The modeled direct repartition undercuts the gather-everything
    reference — the cost model prefers the decomposition exactly when
    it moves fewer bytes."""
    direct = rd.plan_transition(tiling.row(2), tiling.row(2), SRC, DST,
                                (24, 8), np.float32)
    scheds = rd.cross_mesh_schedules(tiling.row(2), SRC,
                                     tiling.row(2), DST)
    costs = sorted(s.cost(24 * 8 * 4.0) for s in scheds)
    assert direct.cost == pytest.approx(costs[0])
    assert len(costs) >= 2 and costs[0] < costs[-1]


# -- planned rehome on a simulated shrink --------------------------------


def test_simulated_shrink_rehome_through_planner():
    """The tier-1-safe shrink leg: arrays on an 8-device (4,2) grid
    survive a rebuild onto 6 devices — each re-tiled through the
    planner, values intact, with per-array schedule/bytes/route/reason
    records feeding the elastic_* metrics."""
    vals = np.arange(24 * 8, dtype=np.float32).reshape(24, 8)
    arrs = {
        "row": st.from_numpy(vals.copy(), tiling=tiling.row(2)),
        "flat": st.from_numpy(vals.copy(), tiling=tiling.flat_row(2)),
        "rep": st.from_numpy(vals.copy(), tiling=tiling.replicated(2)),
    }
    b0 = _counter("elastic_migrated_bytes")
    mesh_mod.rebuild_mesh(exclude_devices=[6, 7])
    n = elastic.rehome(list(arrs.values()))
    assert n == 3
    report = elastic.last_rehome_report()
    assert len(report) == 3
    by_route = {}
    for r in report:
        assert r["reason"] and "route" in r
        by_route.setdefault(r["route"], []).append(r)
    # the divisible row tiling went direct; flat_row fell back with
    # its documented reason
    assert any("flat_row" in r["reason"] for r in by_route["gather"])
    assert "direct" in by_route
    for name, arr in arrs.items():
        a = getattr(arr, "value", arr)
        assert a._epoch == mesh_mod._EPOCH
        np.testing.assert_array_equal(np.asarray(arr.glom()), vals)
        assert a._migration["to_epoch"] == mesh_mod._EPOCH
    assert _counter("elastic_migrated_bytes") > b0
    assert _counter("elastic_rehomed") >= 3


def test_rehome_skips_donated_with_labeled_reason():
    """Satellite: rehoming a donated (invalidated) handle is a labeled
    SKIP, never a crash — and live arrays in the same pass still
    heal."""
    a, ok = np.ones((8, 8), np.float32), None
    live = st.from_numpy(a.copy())
    donated = st.from_numpy(a.copy())
    dv = getattr(donated, "value", donated)
    dv._release_donated()  # simulate a consumed donation
    s0 = _counter("elastic_rehome_skipped")
    mesh_mod.rebuild_mesh(exclude_devices=[7])
    n = elastic.rehome([donated, live])
    assert n == 1  # the live one
    assert _counter("elastic_rehome_skipped") == s0 + 1
    rep = elastic.last_rehome_report()
    skip = [r for r in rep if r["route"] == "skipped"]
    assert skip and "donat" in skip[0]["reason"]
    lv = getattr(live, "value", live)
    assert lv._epoch == mesh_mod._EPOCH
    np.testing.assert_array_equal(np.asarray(live.glom()), a)


def test_explain_names_migrations():
    """st.explain's migrations section: a plan whose leaves crossed a
    mesh-shape transition names each migration (schedule + bytes +
    route + reason)."""
    vals = np.arange(24 * 8, dtype=np.float32).reshape(24, 8)
    x = st.from_numpy(vals, tiling=tiling.row(2))
    mesh_mod.rebuild_mesh(exclude_devices=[6, 7])
    elastic.rehome([x])
    rep = st.explain((x * 2.0).sum(), cost=False)
    migs = rep.data.get("migrations")
    assert migs and migs[0]["route"] in ("direct", "gather")
    assert migs[0]["bytes"] >= 0 and migs[0]["reason"]
    text = str(rep)
    assert "migrations (cross-mesh re-tiling):" in text


# -- chaos during recovery (the `recover` seam) --------------------------


def test_recover_grammar_and_classifier():
    plan = faults.ChaosPlan("recover@1", 0)
    assert plan.specs[0].kind == "recover" and plan.specs[0].at == 1
    err = faults.InjectedRecoveryError("UNAVAILABLE: injected")
    assert cls.classify(err) == cls.TRANSIENT
    # recover tokens consume the recover seam's OWN occurrence space:
    # dispatch occurrences do not advance it
    with faults.ChaosPlan("recover@0", 0) as p:
        p.fire("dispatch")
        p.fire("dispatch")
        with pytest.raises(faults.InjectedRecoveryError):
            p.fire("recover")
    assert [f["site"] for f in p.fired] == ["recover"]


def test_second_handle_failure_same_epoch_is_noop():
    """Satellite: recovery is idempotent per epoch — a second
    handle_failure for the same loss must not shrink the mesh again
    or re-run drain/rebuild."""
    _ = st.from_numpy(np.ones((8, 8), np.float32))
    with st.chaos("device_loss@0"):
        with pytest.raises(st.FatalMeshError) as ei:
            (st.from_numpy(np.ones((8, 8), np.float32)) * 2.0
             ).sum().evaluate()
    epoch = mesh_mod._EPOCH
    survivors = mesh_mod.get_mesh().devices.size
    r0 = _counter("elastic_recoveries")
    # replay the SAME failure (a second worker observing the same
    # loss): no-op — same epoch, same survivor count, no new recovery
    m = elastic.on_fatal_mesh(ei.value.__cause__ or ei.value)
    assert m is not None
    assert mesh_mod._EPOCH == epoch
    assert mesh_mod.get_mesh().devices.size == survivors
    assert _counter("elastic_recoveries") == r0


@pytest.mark.parametrize("probe", [0, 1, 2])
def test_chaos_during_recovery_reenters_cleanly(probe, tmp_path):
    """The chaos-during-recovery matrix: a transient fault injected at
    each recovery probe (pre-drain / pre-rebuild / pre-evict) kills
    the recovery mid-flight; the checkpointed loop's retry re-enters,
    recovery finishes idempotently, and the loop converges bit-stable
    on the shrunken mesh."""
    a = np.ones((8, 8), np.float32)
    x = st.from_numpy(a * 0.5)

    def body(c):
        return c * 1.01 + x

    p = str(tmp_path / "ck")
    # device_loss fires twice: the second occurrence re-triggers
    # recovery after the injected recovery fault aborted the first
    # attempt (a real dead device keeps failing dispatches the same
    # way)
    with st.chaos(f"device_loss@2x2,recover@{probe}"):
        res = st.loop(20, body, st.from_numpy(a.copy()),
                      checkpoint_every=5, checkpoint_path=p)
        out = np.asarray(res.glom())
    assert mesh_mod._EPOCH >= 1
    # recovery COMPLETED despite the mid-flight fault: completion
    # tracking caught up with the epoch
    assert elastic._completed_epoch == mesh_mod._EPOCH
    assert not elastic._pending
    x2 = st.from_numpy(a * 0.5)
    ref = np.asarray(st.loop(20, lambda c: c * 1.01 + x2,
                             st.from_numpy(a.copy())).glom())
    np.testing.assert_array_equal(out, ref)


def test_chaos_during_rehome_reenters(tmp_path):
    """A fault inside the rehome pass itself (mid-migration): the loop
    driver re-enters recovery instead of dying, and the next pass
    heals."""
    a = np.ones((8, 8), np.float32)
    x = st.from_numpy(a * 0.5)
    p = str(tmp_path / "ck")
    # recover@3: probes 0-2 are the drain/rebuild/evict of the (only)
    # recovery; probe 3 is the first rehome pass
    with st.chaos("device_loss@2,recover@3"):
        res = st.loop(20, lambda c: c * 1.01 + x,
                      st.from_numpy(a.copy()),
                      checkpoint_every=5, checkpoint_path=p)
        out = np.asarray(res.glom())
    x2 = st.from_numpy(a * 0.5)
    ref = np.asarray(st.loop(20, lambda c: c * 1.01 + x2,
                             st.from_numpy(a.copy())).glom())
    np.testing.assert_array_equal(out, ref)


# -- elastic recovery composed with the redistribution planner -----------


def test_device_loss_loop_with_planner_on_bit_stable(tmp_path):
    """The composed acceptance (CPU half): elastic recovery routed
    through the redistribution planner — checkpointed loop loses a
    device, survivors re-tile through planned migrations, restored
    carries carry migration records, and the loop finishes bit-stable
    vs an uninterrupted run on the same shrunken mesh."""
    FLAGS.redistribution_planner = True
    a = np.ones((24, 8), np.float32)
    x = st.from_numpy(a * 0.5, tiling=tiling.row(2))

    def body(c):
        return c * 1.01 + x

    p = str(tmp_path / "ck")
    b0 = _counter("elastic_migrated_bytes")
    with st.chaos("device_loss@2"):
        res = st.loop(20, body, st.from_numpy(a.copy()),
                      checkpoint_every=5, checkpoint_path=p)
        out = np.asarray(res.glom())
    rec = res._resilience
    assert rec["mesh_rebuilt"] and rec["rehomed"] >= 1
    # the rehomed leaf went through the migration planner
    xv = getattr(x, "value", x)
    assert xv._migration is not None and xv._migration["reason"]
    assert _counter("elastic_migrated_bytes") >= b0
    x2 = st.from_numpy(a * 0.5)
    ref = np.asarray(st.loop(20, lambda c: c * 1.01 + x2,
                             st.from_numpy(a.copy())).glom())
    np.testing.assert_array_equal(out, ref)


def test_checkpoint_restore_across_mesh_shapes_records_migration(
        tmp_path):
    """A snapshot written on the full grid restored after a shrink is
    a planned migration: the carry carries a 'restore' record with
    the planned transition."""
    from spartan_tpu.utils import checkpoint as ckpt

    vals = np.arange(24 * 8, dtype=np.float32).reshape(24, 8)
    arr = st.from_numpy(vals, tiling=tiling.row(2))
    path = str(tmp_path / "a")
    ckpt.save(path, getattr(arr, "value", arr))
    mesh_mod.rebuild_mesh(exclude_devices=[6, 7])
    loaded = ckpt.load(path)
    np.testing.assert_array_equal(loaded.glom(), vals)
    mig = loaded._migration
    assert mig is not None and mig["route"] == "restore"
    assert mig["src_mesh"] == {"x": 4, "y": 2}
    assert mig["dst_mesh"] == {"x": 3, "y": 2}
    assert mig["reason"]


# -- cross-replica loop-carry sharding -----------------------------------


def test_shard_loop_carries_bit_equal_and_keyed():
    """FLAGS.shard_loop_carries: a large replicated carry is
    constrained to the sharded layout for the whole loop — results
    bit-equal for an elementwise body, plan keys separated, and the
    lowered program carries the extra layout constraint."""
    import jax

    from spartan_tpu.expr import base as eb

    a = np.random.RandomState(0).rand(512, 64).astype(np.float32)
    rep = tiling.replicated(2)
    x = st.from_numpy(a * 0.5, tiling=rep)

    def build():
        return st.loop(10, lambda c: c * 1.01 + x,
                       st.from_numpy(a.copy(), tiling=rep))

    def key_and_hlo(expr):
        plan_key, rctx = eb.plan_signature(expr)
        plan, _dag, leaves = eb._build_plan(
            expr, mesh_mod.get_mesh(), rctx, plan_key)
        args = [eb._leaf_arg(l) for l in leaves]
        txt = jax.jit(plan.traced).lower(*args).as_text()
        # Shardy (JAX's partitioner) spells a layout constraint
        # sdy.sharding_constraint
        return plan_key, txt.count("sdy.sharding_constraint")

    off = build()
    out_off = np.asarray(off.glom())
    key_off, n_off = key_and_hlo(
        st.loop(10, lambda c: c * 1.01 + x,
                st.from_numpy(a.copy(), tiling=rep)))

    FLAGS.shard_loop_carries = True
    FLAGS.shard_carry_min_bytes = 1024
    on = build()
    # the carry is marked sharded on the loop expr itself
    loop_expr = on.loop
    assert any(c.sharded for c in loop_expr.carries)
    assert loop_expr.carries[0]._tiling.axes[0] is not None
    out_on = np.asarray(on.glom())
    key_on, n_on = key_and_hlo(build())
    np.testing.assert_array_equal(out_off, out_on)
    assert key_on != key_off  # sharded/replicated programs never alias
    assert n_on > n_off  # the carry constraint is IN the program


def test_shard_loop_carries_respects_min_bytes_and_existing_tilings():
    FLAGS.shard_loop_carries = True
    FLAGS.shard_carry_min_bytes = 1 << 20
    a = np.ones((64, 8), np.float32)  # 2KB: under the bound
    res = st.loop(3, lambda c: c + 1.0,
                  st.from_numpy(a, tiling=tiling.replicated(2)))
    assert not any(c.sharded for c in res.loop.carries)
    # an already-sharded init keeps the user's layout
    FLAGS.shard_carry_min_bytes = 16
    res2 = st.loop(3, lambda c: c + 1.0,
                   st.from_numpy(np.ones((64, 8), np.float32),
                                 tiling=tiling.row(2)))
    assert not any(c.sharded for c in res2.loop.carries)


def test_shard_loop_carries_composes_with_checkpoint(tmp_path):
    FLAGS.shard_loop_carries = True
    FLAGS.shard_carry_min_bytes = 1024
    a = np.random.RandomState(1).rand(512, 64).astype(np.float32)
    rep = tiling.replicated(2)
    x = st.from_numpy(a * 0.5, tiling=rep)

    def body(c):
        return c * 1.01 + x

    p = str(tmp_path / "ck")
    out = np.asarray(st.loop(10, body,
                             st.from_numpy(a.copy(), tiling=rep),
                             checkpoint_every=3,
                             checkpoint_path=p).glom())
    FLAGS.shard_loop_carries = False
    x2 = st.from_numpy(a * 0.5, tiling=rep)
    ref = np.asarray(st.loop(10, lambda c: c * 1.01 + x2,
                             st.from_numpy(a.copy(),
                                           tiling=rep)).glom())
    np.testing.assert_array_equal(out, ref)


def test_chaos_io_during_restore_reenters(tmp_path):
    """Mid-RESTORE fault: the io chaos token fires on the snapshot
    read that follows a device loss (checkpoint occurrences: save@5,
    save@10, restore). The driver re-enters from the held carries,
    stale leaves rehome, and the loop still finishes bit-stable."""
    a = np.ones((8, 8), np.float32)
    x = st.from_numpy(a * 0.5)
    p = str(tmp_path / "ck")
    with st.chaos("device_loss@2,io@2"):
        res = st.loop(20, lambda c: c * 1.01 + x,
                      st.from_numpy(a.copy()),
                      checkpoint_every=5, checkpoint_path=p)
        out = np.asarray(res.glom())
    assert res._resilience["mesh_rebuilt"]
    x2 = st.from_numpy(a * 0.5)
    ref = np.asarray(st.loop(20, lambda c: c * 1.01 + x2,
                             st.from_numpy(a.copy())).glom())
    np.testing.assert_array_equal(out, ref)
