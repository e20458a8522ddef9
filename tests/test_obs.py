"""Observability subsystem (spartan_tpu/obs/): span tracer, metrics
registry, plan introspection.

Covers the ISSUE-3 acceptance surface: span nesting/ordering under
threads (the ``_stats_lock`` pattern), ring-buffer wraparound, Chrome
trace-event JSON schema round-trip, cold-vs-warm evaluate span trees,
``st.explain`` on cache-miss vs cache-hit plans (passes, tilings,
donation slots, cost_analysis FLOPs), metrics snapshot stability
across ``reset()``, exception-safe ``phase()``, and per-iteration
``st.loop`` spans."""

import gc
import json
import threading

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.examples.kmeans import kmeans_step
from spartan_tpu.expr.base import ValExpr, evaluate
from spartan_tpu.obs import trace as obs_trace
from spartan_tpu.utils import profiling
from spartan_tpu.utils.config import FLAGS


@pytest.fixture(autouse=True)
def _mesh(mesh2d):
    yield


@pytest.fixture(autouse=True)
def _fresh():
    st.clear_compile_cache()
    profiling.reset_counters()
    st.trace_clear()
    yield
    st.clear_compile_cache()
    profiling.reset_counters()
    st.trace_clear()


# -- span tracer ---------------------------------------------------------


def test_span_nesting_under_threads():
    """Concurrent nested spans: every span lands in the ring, children
    complete before their parents (per-thread completion order), and
    depths are consistent per thread."""
    n_threads, reps = 4, 25
    barrier = threading.Barrier(n_threads)  # overlap the threads so
    # OS thread idents cannot be sequentially reused across workers

    def work(k):
        barrier.wait()
        for i in range(reps):
            with profiling.span(f"outer-{k}"):
                with profiling.span(f"inner-{k}"):
                    pass
        barrier.wait()

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    spans = st.trace_events()
    mine = [s for s in spans if s.name.startswith(("outer-", "inner-"))]
    assert len(mine) == n_threads * reps * 2
    by_tid = {}
    for s in mine:
        by_tid.setdefault(s.tid, []).append(s)
    assert len(by_tid) == n_threads  # distinct stable tids per thread
    for tid, seq in by_tid.items():
        # one (outer, inner) pair namespace per thread
        names = {s.name.split("-")[1] for s in seq}
        assert len(names) == 1
        for a, b in zip(seq, seq[1:]):
            assert a.ts <= b.ts + b.dur  # completion order is coherent
        for s in seq:
            assert s.depth == (1 if s.name.startswith("inner") else 0)
            # the inner span nests inside SOME outer span's window
        outers = [s for s in seq if s.name.startswith("outer")]
        for s in seq:
            if s.name.startswith("inner"):
                assert any(o.ts <= s.ts and
                           s.ts + s.dur <= o.ts + o.dur + 1.0
                           for o in outers)


def test_ring_buffer_wraparound():
    old = FLAGS.trace_ring
    gc.disable()  # a collection would add its own "gc" span
    try:
        FLAGS.trace_ring = 8
        st.trace_clear()
        for i in range(20):
            with profiling.span(f"s{i}"):
                pass
        spans = st.trace_events()
        assert len(spans) == 8
        assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    finally:
        gc.enable()
        FLAGS.trace_ring = old
        st.trace_clear()


def test_trace_flag_off_records_nothing():
    old = FLAGS.trace
    try:
        FLAGS.trace = False
        st.trace_clear()
        with profiling.span("invisible") as sp:
            pass
        # the null span still measures (callers rely on .seconds) ...
        assert sp.seconds >= 0.0
        # ... but nothing is recorded
        assert st.trace_events() == []
    finally:
        FLAGS.trace = old


def test_phase_raises_still_records_elapsed_and_error_span():
    """ISSUE-3 satellite: a raising phase must record its elapsed time
    AND an error=True span naming the exception type."""
    before = profiling.phase_seconds().get("explode", 0.0)
    with pytest.raises(ValueError):
        with profiling.phase("explode"):
            raise ValueError("boom")
    after = profiling.phase_seconds().get("explode", 0.0)
    assert after > before  # elapsed recorded despite the raise
    spans = [s for s in st.trace_events() if s.name == "explode"]
    assert len(spans) == 1
    assert spans[0].error
    assert spans[0].args["exc"] == "ValueError"


def test_chrome_trace_schema_roundtrip(tmp_path):
    """Export -> json.load: every event carries the required Chrome
    trace-event keys, cold evaluates show the full plan-lifecycle span
    tree, warm ones the hit path only."""
    x = st.from_numpy(np.ones((8, 8), np.float32))

    (st.as_expr(x) * 2.0).sum().evaluate()          # cold: full pipeline
    cold_names = [s.name for s in st.trace_events()]
    st.trace_clear()
    (st.as_expr(x) * 2.0).sum().evaluate().glom()   # warm: hit + fetch
    warm = st.trace_events()
    warm_names = [s.name for s in warm]

    for name in ("evaluate", "sign", "optimize", "tiling", "compile",
                 "pass:map_fusion", "pass:auto_tiling"):
        assert name in cold_names, (name, cold_names)
    assert "dispatch" in warm_names and "fetch" in warm_names
    assert "optimize" not in warm_names  # hits never replan
    ev = next(s for s in warm if s.name == "evaluate")
    assert ev.args["cache"] == "hit"
    assert ev.args["plan_key"]  # the plan-cache key rides the span

    path = tmp_path / "trace.json"
    doc = st.trace_export(str(path))
    loaded = json.load(open(path))
    assert loaded == json.loads(json.dumps(doc))
    # a collection between the two reads adds a "gc" span to the export
    evts = loaded["traceEvents"]
    assert evts and len([e for e in evts if e["name"] != "gc"]) == len(
        [s for s in warm if s.name != "gc"])
    for e in evts:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e, (key, e)
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0


# -- metrics registry ----------------------------------------------------


def test_metrics_typed_instruments():
    reg = st.obs.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(5.0)
    reg.gauge("g").set(2.0)
    h = reg.histogram("h")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == {"value": 2.0, "max": 5.0}
    hs = snap["histograms"]["h"]
    assert hs["count"] == 5 and hs["sum"] == 110.0 and hs["max"] == 100.0
    assert hs["p50"] == 3.0
    assert hs["p95"] == 100.0


def test_metrics_snapshot_stable_across_reset():
    profiling.count("widgets", 7)
    profiling.record_phase("whirr", 0.5)
    before = st.metrics()
    assert before["counters"]["widgets"] == 7
    assert before["histograms"]["phase:whirr"]["count"] == 1
    profiling.reset_counters()
    after = st.metrics()
    # registrations survive the reset with identical keys, zeroed —
    # benchmark brackets can diff snapshots without key juggling
    assert set(after["counters"]) == set(before["counters"])
    assert set(after["histograms"]) == set(before["histograms"])
    assert after["counters"]["widgets"] == 0
    assert after["histograms"]["phase:whirr"]["count"] == 0
    assert after["histograms"]["phase:whirr"]["sum"] == 0.0


def test_metrics_prometheus_format():
    profiling.count("plan_hits", 3)
    profiling.record_phase("sign", 0.25)
    text = st.metrics(fmt="prometheus")
    assert "# TYPE spartan_plan_hits counter" in text
    assert "spartan_plan_hits 3" in text
    assert 'spartan_phase_sign{quantile="0.5"} 0.25' in text
    assert "spartan_phase_sign_count 1" in text
    with pytest.raises(ValueError):
        st.metrics(fmt="xml")


def test_prometheus_help_type_and_hostile_label_roundtrip():
    """Exposition-format conformance (ISSUE 9 satellite): # HELP /
    # TYPE pairs, and label values escaped so a hostile tenant label
    (quotes, backslash, newline) survives a parse round-trip."""
    from spartan_tpu.obs.metrics import (REGISTRY, labeled,
                                         parse_labels, split_labels)

    hostile = 'hostile "corp"\\division\nnewline'
    key = labeled("serve_requests", tenant=hostile)
    REGISTRY.counter(key, "requests submitted to the serve "
                     "engine").inc(2)
    text = st.metrics(fmt="prometheus")
    assert "# HELP spartan_serve_requests" in text
    assert "# TYPE spartan_serve_requests counter" in text
    # exactly one physical line carries the hostile series: the raw
    # newline was escaped, not emitted
    lines = [ln for ln in text.splitlines()
             if ln.startswith("spartan_serve_requests{")
             and "division" in ln]
    assert len(lines) == 1
    series = lines[0].rsplit(" ", 1)[0]
    assert "\n" not in series
    # round-trip: parsing the rendered series recovers the raw label
    _base, labels = parse_labels(series)
    assert labels["tenant"] == hostile
    # the canonical instrument key parses back to the same value too
    assert parse_labels(key)[1]["tenant"] == hostile
    assert split_labels(key)[0] == "serve_requests"


def test_metrics_plan_cache_view_matches_shims():
    x = st.from_numpy(np.ones((8, 8), np.float32))
    (st.as_expr(x) + 1.0).evaluate()
    (st.as_expr(x) + 1.0).evaluate()
    snap = st.metrics()
    assert snap["plan_cache"] == profiling.plan_cache_stats()
    assert snap["plan_cache"]["plan_hits"] == 1
    # per-phase histograms carry the percentile fields
    disp = snap["histograms"]["phase:dispatch"]
    for key in ("count", "sum", "p50", "p95", "max"):
        assert key in disp


# -- plan introspection --------------------------------------------------


def _kmeans_expr():
    rng = np.random.RandomState(0)
    pts = st.from_numpy(rng.rand(64, 8).astype(np.float32))
    c = st.as_expr(rng.rand(4, 8).astype(np.float32)).evaluate()
    return pts, c


def test_explain_miss_then_hit():
    pts, c = _kmeans_expr()
    e = kmeans_step(pts, ValExpr(c), 4)
    rep = st.explain(e)                        # never evaluated: miss
    assert rep.cache == "miss"
    assert rep.passes and all(
        {"name", "nodes_before", "nodes_after"} <= set(p) for p in
        rep.passes)
    assert any(p["name"] == "auto_tiling" for p in rep.passes)
    assert rep.tilings  # per-node chosen tilings
    assert rep.leaves and rep.arg_order is not None
    assert rep.cost_analysis and rep.flops and rep.flops > 0
    assert rep.plan_key
    assert "passes:" in str(rep) and "cost_analysis" in str(rep)

    # explain pre-planned it: the first evaluate is already a HIT
    profiling.reset_counters()
    kmeans_step(pts, ValExpr(c), 4).evaluate()
    counts = profiling.counters()
    assert counts.get("plan_hits", 0) == 1
    assert counts.get("plan_misses", 0) == 0

    rep2 = st.explain(kmeans_step(pts, ValExpr(c), 4))
    assert rep2.cache == "hit"
    assert rep2.plan_key == rep.plan_key
    # the hit report is the memoized one — cost_analysis included
    assert rep2.flops == rep.flops


def test_explain_reports_donation_slots():
    rng = np.random.RandomState(1)
    xn = rng.rand(8, 8).astype(np.float32)
    x = st.from_numpy(xn).evaluate()
    evaluate(st.as_expr(x) + 1.0, donate=[x])
    y = st.from_numpy(xn).evaluate()           # same structure, fresh leaf
    rep = st.explain(st.as_expr(y) + 1.0, cost=False)
    assert rep.cache == "hit"
    assert rep.donation["last_donated_args"] == [0]
    assert rep.donation["donated_dispatches"] == 1


def test_explain_already_evaluated():
    x = st.from_numpy(np.ones((4, 4), np.float32))
    e = st.as_expr(x) + 1.0
    e.evaluate()
    rep = st.explain(e)
    assert rep.cache == "evaluated"


def test_explain_does_not_touch_counters_or_dispatch():
    x = st.from_numpy(np.ones((8, 8), np.float32))
    e = (st.as_expr(x) * 3.0).sum()
    profiling.reset_counters()
    st.explain(e, cost=False)
    counts = profiling.counters()
    assert counts.get("plan_hits", 0) == 0
    assert counts.get("plan_misses", 0) == 0
    assert counts.get("evaluations", 0) == 0
    assert e._result is None  # explain never dispatches


# -- st.loop per-iteration spans ----------------------------------------


def test_loop_step_spans():
    old = FLAGS.trace_loop_steps
    try:
        FLAGS.trace_loop_steps = True
        w0 = st.from_numpy(np.zeros((8,), np.float32)).evaluate()
        out = st.loop(5, lambda w: w + 1.0, ValExpr(w0))
        np.testing.assert_allclose(np.asarray(out.glom()), np.full(8, 5.0))
        spans = st.trace_events()
        steps = [s for s in spans if s.name == "loop_step"]
        assert len(steps) == 5
        assert sorted(s.args["step"] for s in steps) == [0, 1, 2, 3, 4]
        assert len({s.args["loop"] for s in steps}) == 1
        loop_spans = [s for s in spans if s.name == "loop"]
        assert loop_spans and loop_spans[0].args["n"] == 5
    finally:
        FLAGS.trace_loop_steps = old


def test_loop_span_without_step_callbacks():
    """Default mode: one 'loop' span, no per-step callbacks baked into
    the program."""
    w0 = st.from_numpy(np.zeros((4,), np.float32)).evaluate()
    out = st.loop(3, lambda w: w + 2.0, ValExpr(w0))
    np.testing.assert_allclose(np.asarray(out.glom()), np.full(4, 6.0))
    spans = st.trace_events()
    assert [s for s in spans if s.name == "loop"]
    assert not [s for s in spans if s.name == "loop_step"]
