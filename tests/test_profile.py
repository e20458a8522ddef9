"""Device-time attribution profiler (ISSUE 11): st.profile attributes
the whole-plan device wall to named expr nodes on the {map, dot,
reduce, loop} matrix, st.explain shows measured device time next to
modeled cost, sampling keeps served results bit-equal under concurrent
clients, the ledger grows device columns fit_profile calibrates from,
and the obs stack stays tear-free under concurrent submitters."""

import json
import threading

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.expr import base
from spartan_tpu.obs import flight, ledger
from spartan_tpu.obs import profile as profile_mod
from spartan_tpu.obs.explain import key_hash
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _setup(mesh1d):
    saved = {n: getattr(FLAGS, n) for n in (
        "profile_sample_every", "profile_tier", "profile_max_nodes",
        "cost_ledger", "trace", "flightrec")}
    FLAGS.cost_ledger = True
    FLAGS.profile_sample_every = 0
    FLAGS.profile_tier = "auto"
    profile_mod.reset()
    ledger.set_profile(None)
    ledger.reset()
    flight.clear()
    st.serve.shutdown_default()
    yield
    st.serve.shutdown_default()
    profile_mod.reset()
    ledger.set_profile(None)
    ledger.reset()
    flight.clear()
    for n, v in saved.items():
        setattr(FLAGS, n, v)


def _leaves(seed=0, n=256, d=64):
    rng = np.random.RandomState(seed)
    x = st.as_expr(rng.rand(n, d).astype(np.float32)).evaluate()
    y = st.as_expr(rng.rand(n, d).astype(np.float32)).evaluate()
    a = st.as_expr(rng.rand(128, 128).astype(np.float32)).evaluate()
    b = st.as_expr(rng.rand(128, 128).astype(np.float32)).evaluate()
    return x, y, a, b


def _matrix(x, y, a, b):
    """Fresh structurally-distinct roots per call: one per op family
    of the acceptance matrix."""
    return {
        "map": (st.as_expr(x) + st.as_expr(y)) * 3.0 - st.as_expr(x),
        "dot": st.dot(st.as_expr(a), st.as_expr(b)),
        "reduce": st.as_expr(x).sum(axis=0),
        "loop": st.loop(3, lambda c: c * 0.5 + 1.0, st.as_expr(a)),
    }


# -- the acceptance criterion --------------------------------------------


def test_attribution_matrix_cpu():
    """>=90% of the measured whole-plan device wall attributed to
    named expr nodes on every family, residual reported as
    unattributed, every node keyed by a _sig digest."""
    x, y, a, b = _leaves()
    for name, expr in _matrix(x, y, a, b).items():
        prof = st.profile(expr, reps=3)
        assert prof.tier in ("replay", "xplane"), (name, prof.tier)
        assert prof.wall_s > 0, name
        assert prof.nodes, name
        assert prof.attributed_fraction >= 0.9, (
            name, prof.attributed_fraction, str(prof))
        # the residual is reported, not silently dropped
        assert prof.unattributed_s >= 0.0
        assert abs(prof.attributed_s + prof.unattributed_s
                   - max(prof.wall_s, prof.attributed_s)) < 1e-9
        for node in prof.nodes:
            assert node["digest"], (name, node)
            assert node["seconds"] > 0
            assert "modeled_cost" in node  # measured NEXT TO modeled
            assert node["op_class"]


def test_profile_report_shapes():
    x, y, a, b = _leaves()
    prof = st.profile(_matrix(x, y, a, b)["map"], reps=2)
    d = prof.to_dict()
    json.dumps(d)  # JSON-serializable end to end
    assert d["tier"] == prof.tier
    assert d["class_seconds"]
    assert prof.top(1) and prof.top(1)[0]["seconds"] == max(
        n["seconds"] for n in prof.nodes)
    assert "device profile" in str(prof)


def test_explain_shows_measured_next_to_modeled():
    x, y, a, b = _leaves()
    st.profile(_matrix(x, y, a, b)["dot"], reps=2)
    rep = st.explain(_matrix(x, y, a, b)["dot"], cost=False)
    dp = rep.data.get("device_profile")
    assert dp is not None
    assert dp["nodes"]
    for node in dp["nodes"]:  # every attributed node: measured + modeled
        assert node["seconds"] > 0
        assert "modeled_cost" in node
    text = str(rep)
    assert "device profile" in text
    assert "attributed" in text


def test_profile_preplans_like_explain():
    """Profiling a never-evaluated expr builds (and caches) its plan —
    the next evaluate is a plan-cache hit."""
    from spartan_tpu.utils import profiling

    x, y, a, b = _leaves()
    e = _matrix(x, y, a, b)["reduce"]
    profiling.reset_counters()
    st.profile(e, reps=1)
    before = profiling.counters().get("plan_hits", 0)
    _matrix(x, y, a, b)["reduce"].evaluate()
    assert profiling.counters().get("plan_hits", 0) == before + 1


def test_profile_result_matches_evaluate():
    """The profiled sub-plans replay the same computation: profiling
    does not disturb the evaluated result."""
    x, y, a, b = _leaves()
    ref = _matrix(x, y, a, b)["map"].glom()
    st.profile(_matrix(x, y, a, b)["map"], reps=1)
    got = _matrix(x, y, a, b)["map"].glom()
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_scope_names_carry_digest():
    """Inside a naming session every node's named_scope label carries
    its structural-signature digest — the trace-parse join key — and
    the digest matches the signing context's memoized signature."""
    x, y, a, b = _leaves()
    e = _matrix(x, y, a, b)["map"]
    dag = e.optimized()
    with profile_mod.naming_session():
        name = profile_mod.scope_name(dag)
        assert profile_mod._SCOPE_MARK in name
        digest = name.split(profile_mod._SCOPE_MARK, 1)[1]
        ctx = base._SigCtx()
        ctx.of(dag)
        assert digest == key_hash(ctx._memo[dag._id])
    # outside a session the legacy label (no digest) is unchanged
    assert profile_mod._SCOPE_MARK not in profile_mod.scope_name(dag)


def test_profile_export_merges_host_and_device(tmp_path):
    x, y, a, b = _leaves()
    st.profile(_matrix(x, y, a, b)["map"], reps=1)
    path = tmp_path / "merged.json"
    doc = st.profile_export(str(path))
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["traceEvents"]
    device = [ev for ev in loaded["traceEvents"]
              if ev.get("tid") == 1_000_000]
    # the device track: thread metadata + >=1 attributed segment with
    # the digest in the event name
    assert any(ev.get("ph") == "M" for ev in device)
    segs = [ev for ev in device if ev.get("ph") == "X"]
    assert segs and any("[" in ev["name"] for ev in segs)
    # host spans from the trace ring are in the same document
    assert any(ev.get("tid") != 1_000_000 and ev.get("ph") == "X"
               for ev in loaded["traceEvents"])
    assert doc["traceEvents"]


def test_xplane_tier_explicit_raises_or_measures():
    """tier='xplane' either attributes from a real capture or raises
    the documented error — never silently falls back."""
    x, y, a, b = _leaves()
    try:
        prof = st.profile(_matrix(x, y, a, b)["map"], tier="xplane",
                          reps=1)
    except RuntimeError as e:
        assert "xplane" in str(e)
    else:
        assert prof.tier == "xplane"
        assert prof.nodes


# -- sampled continuous profiling ----------------------------------------


def _kstep(pts, c, k=8):
    from spartan_tpu.examples.kmeans import kmeans_step
    from spartan_tpu.expr.base import ValExpr

    return kmeans_step(pts, ValExpr(c), k)


def test_sampler_counts_every_nth():
    rng = np.random.RandomState(1)
    pts = st.from_numpy(rng.rand(128, 16).astype(np.float32))
    c = st.as_expr(rng.rand(8, 16).astype(np.float32)).evaluate()
    c = _kstep(pts, c).evaluate()  # compile run (never sampled)
    FLAGS.profile_sample_every = 3
    before = st.metrics()["counters"].get("profile_samples", 0)
    for _ in range(7):  # 7 warm dispatches -> samples at 3 and 6
        c = _kstep(pts, c).evaluate()
    FLAGS.profile_sample_every = 0
    took = st.metrics()["counters"].get("profile_samples", 0) - before
    assert took == 2


def test_sampled_results_bit_equal_and_no_key_changes():
    """The sampling wrapper is dispatch-time only: same plan key, same
    executable, bit-equal results sampled vs unsampled."""
    rng = np.random.RandomState(2)
    pts = st.from_numpy(rng.rand(128, 16).astype(np.float32))
    c0 = st.as_expr(rng.rand(8, 16).astype(np.float32)).evaluate()

    key_off, _ = base.plan_signature(_kstep(pts, c0))
    ref = _kstep(pts, c0).evaluate().glom()

    FLAGS.profile_sample_every = 1
    key_on, _ = base.plan_signature(_kstep(pts, c0))
    got = _kstep(pts, c0).evaluate().glom()
    FLAGS.profile_sample_every = 0

    assert key_on == key_off  # no plan/compile-key changes
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_sampled_serving_bit_equal_16_clients():
    """The ISSUE-11 acceptance leg: profile_sample_every=4 under 16
    concurrent clients — every future resolves bit-equal to the
    unsampled serial result."""
    rng = np.random.RandomState(3)
    pts = st.from_numpy(rng.rand(128, 16).astype(np.float32))
    c0 = st.as_expr(rng.rand(8, 16).astype(np.float32)).evaluate()
    ref = np.asarray(_kstep(pts, c0).evaluate().glom())

    FLAGS.profile_sample_every = 4
    eng = st.ServeEngine(workers=4)
    with eng:
        futs = [eng.submit(_kstep(pts, c0)) for _ in range(16)]
        outs = [np.asarray(f.glom()) for f in futs]
    FLAGS.profile_sample_every = 0
    for out in outs:
        np.testing.assert_array_equal(ref, out)


def test_sampled_requests_stamped_in_flight_recorder():
    rng = np.random.RandomState(4)
    pts = st.from_numpy(rng.rand(128, 16).astype(np.float32))
    c0 = st.as_expr(rng.rand(8, 16).astype(np.float32)).evaluate()
    _kstep(pts, c0).evaluate()  # warm: the serve dispatches all hit
    _kstep(pts, c0).evaluate()

    FLAGS.profile_sample_every = 1
    eng = st.ServeEngine(workers=2, coalesce_requests=False)
    with eng:
        futs = [eng.submit(_kstep(pts, c0)) for _ in range(4)]
        for f in futs:
            f.glom()
    FLAGS.profile_sample_every = 0
    rec = st.flightrec()
    stamped = [r for r in rec["requests"].values() if "profiled" in r]
    assert stamped
    p = stamped[0]["profiled"]
    assert p["tier"] in ("replay", "xplane")
    assert p["device_s"] >= 0


def test_ledger_device_columns_and_device_fit():
    """Sampled per-node device seconds land as per-op-class DEVICE
    columns and fit_profile calibrates from them (meta.source says
    so)."""
    x, y, a, b = _leaves()
    prof = st.profile(_matrix(x, y, a, b)["dot"], reps=2)
    snap = st.ledger()
    entry = snap["plans"].get(prof.plan_digest)
    assert entry is not None
    dev = entry["measured"]["device"]
    assert dev is not None
    assert dev["samples"] >= 1
    assert dev["class_seconds_mean"]
    assert dev["attributed_mean_s"] > 0
    fitted = st.fit_profile()
    assert fitted is not None
    assert fitted.meta["source"] == "device_time"
    assert fitted.meta["device_rows"] >= 1
    assert fitted.factors


def test_profile_schema_roundtrip_versions(tmp_path):
    """st.save_profile writes v2; st.load_profile accepts BOTH v2 and
    pre-device-column v1 files (version field + defaulting)."""
    p2 = ledger.CalibrationProfile(
        {"map": 1.5, "contraction": 0.7},
        meta={"source": "device_time", "device_rows": 4})
    path2 = tmp_path / "v2.json"
    st.save_profile(str(path2), p2)
    with open(path2) as f:
        on_disk = json.load(f)
    assert on_disk["version"] == ledger.PROFILE_VERSION == 2
    loaded = st.load_profile(str(path2))
    assert loaded.factors == p2.factors
    assert loaded.meta["source"] == "device_time"
    assert loaded.fingerprint() == p2.fingerprint()

    # a v1 file (pre-device-column schema: no source/device_rows)
    path1 = tmp_path / "v1.json"
    with open(path1, "w") as f:
        json.dump({"version": 1, "factors": {"reshard": 4.1},
                   "meta": {"fitted_from_plans": 3}}, f)
    old = st.load_profile(str(path1))
    assert old.factors == {"reshard": 4.1}
    assert old.meta["source"] == "host_wall"  # defaulted
    assert old.meta["device_rows"] == 0
    assert old.meta["fitted_from_plans"] == 3

    # versions beyond this build still refuse loudly
    path9 = tmp_path / "v9.json"
    with open(path9, "w") as f:
        json.dump({"version": 9, "factors": {}}, f)
    with pytest.raises(ValueError, match="version"):
        st.load_profile(str(path9))
    ledger.set_profile(None)


# -- obs thread-safety under serving (ISSUE-11 satellite) ----------------


def test_obs_thread_safety_under_concurrent_submitters(mesh1d):
    """Trace ring + flight recorder + sampled profiler hit by N
    concurrent evaluate_async submitters: no deadlock, no torn
    records, results bit-equal to serial on the same mesh (the mesh is
    thread-local: a client thread enters the one the reference ran on,
    since a reduction's partition, and so its order of additions,
    follows the mesh)."""
    from spartan_tpu.parallel import mesh as mesh_mod

    rng = np.random.RandomState(5)
    pts = st.from_numpy(rng.rand(128, 16).astype(np.float32))
    c0 = st.as_expr(rng.rand(8, 16).astype(np.float32)).evaluate()
    ref = np.asarray(_kstep(pts, c0).evaluate().glom())

    FLAGS.profile_sample_every = 2
    n_threads, per_thread = 8, 3
    results = [[None] * per_thread for _ in range(n_threads)]
    errors = []

    eng = st.ServeEngine(workers=4)

    def client(i):
        try:
            with mesh_mod.use_mesh(mesh1d):
                for j in range(per_thread):
                    fut = eng.submit(_kstep(pts, c0))
                    results[i][j] = np.asarray(fut.glom())
        except Exception as e:  # noqa: BLE001 - collected for assert
            errors.append(e)

    with eng:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "deadlock"
    FLAGS.profile_sample_every = 0
    assert not errors, errors
    for row in results:
        for out in row:
            np.testing.assert_array_equal(ref, out)

    # no torn flight records: every resolve carries its full latency
    # decomposition, every profiled stamp its full field set
    rec = st.flightrec()
    resolves = [e for e in rec["events"] if e["kind"] == "resolve"]
    assert resolves
    for e in resolves:
        for k in ("queue_wait_s", "coalesce_wait_s", "dispatch_s"):
            assert e.get(k) is not None and e[k] >= 0
    for r in rec["requests"].values():
        if "profiled" in r:
            assert r["profiled"]["tier"] in ("replay", "xplane")
            assert r["profiled"]["device_s"] is not None
    # the trace ring survived concurrent appends (snapshot iterates)
    assert st.obs.trace_events() is not None
