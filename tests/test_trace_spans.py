"""Spans for the host time outside the plan tree: a served request's
submit, queue, linger and wake (built from the engine's own stamps and
joined by its flight-recorder ids), uploads, the wait for the device
inside ``fetch``, garbage collections, and PageRank's set-up and
dispatch. With tracing off none of them records anything."""

import gc
import threading

import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.obs import trace as trace_mod
from spartan_tpu.utils.config import FLAGS

US = 1e-3  # span edges are microseconds since the epoch: 1 ns slack


@pytest.fixture(autouse=True)
def _fresh(mesh1d):
    saved = FLAGS.trace
    FLAGS.trace = True
    st.serve.shutdown_default()
    st.trace_clear()
    yield
    FLAGS.trace = saved
    st.serve.shutdown_default()
    st.trace_clear()


def _shared(seed=0, n=16):
    rng = np.random.RandomState(seed)
    x = st.as_expr(rng.rand(n, n).astype(np.float32)).evaluate()
    y = st.as_expr(rng.rand(n, n).astype(np.float32)).evaluate()
    return st.as_expr(x), st.as_expr(y)


def _us(t):
    return (t - trace_mod.epoch()) * 1e6


def _by_rid(name):
    return {s.args["rid"]: s for s in st.trace_events() if s.name == name}


@pytest.mark.parametrize("clients", [1, 4])
def test_served_query_spans_are_the_flight_stamps(clients):
    """Solo (1) and coalesced (4): each answered request has one
    serve_submit, serve_queue, serve_linger and serve_wake span. Queue
    and linger are the flight record's queue and coalesce waits, wake
    starts at the future's resolution, and queue, linger, wake and the
    dispatch's own span carry the same dispatch id."""
    xe, ye = _shared(seed=clients)
    exprs = [(xe + ye).sum() * float(i + 1) for i in range(clients)]
    float(((xe + ye).sum() * 0.5).glom())  # the plan is cached
    st.trace_clear()
    st.obs.flight.clear()
    with st.ServeEngine(workers=1, batch_window_s=0.05,
                        max_batch=clients) as eng:
        futs = [eng.submit(e) for e in exprs]
        for f in futs:
            f.glom(timeout=60)
    assert all(f.coalesced == clients for f in futs)
    rec = st.flightrec()["requests"]
    submit, queue = _by_rid("serve_submit"), _by_rid("serve_queue")
    linger, wake = _by_rid("serve_linger"), _by_rid("serve_wake")
    dispatch = {s.args["span"]: s for s in st.trace_events()
                if s.name == ("serve_solo" if clients == 1
                              else "serve_batch")}
    for f in futs:
        q, lg, w = queue[f.rid], linger[f.rid], wake[f.rid]
        assert f.rid in submit
        assert q.ts == pytest.approx(_us(f.t_submit), abs=US)
        assert lg.ts == pytest.approx(q.ts + q.dur, abs=US)
        assert w.ts == pytest.approx(_us(f.t_resolved), abs=US)
        assert q.dur * 1e-6 == pytest.approx(rec[f.rid]["queue_wait_s"],
                                             abs=1e-6)
        assert lg.dur * 1e-6 == pytest.approx(
            rec[f.rid]["coalesce_wait_s"], abs=1e-6)
        ids = {q.args["span"], lg.args["span"], w.args["span"],
               f.dispatch_span, rec[f.rid]["dispatch_span"]}
        assert len(ids) == 1 and ids.pop() in dispatch
        # the request's own timeline, in order
        assert submit[f.rid].ts <= q.ts + q.dur <= lg.ts + lg.dur \
            <= w.ts + 1.0
    if clients == 1:
        sp = dispatch[futs[0].dispatch_span]
        assert sp.ts == pytest.approx(lg.ts + lg.dur, abs=US)
        assert sp.ts + sp.dur == pytest.approx(w.ts, abs=US)


def test_wake_recorded_once_per_request():
    xe, ye = _shared(seed=5)
    with st.ServeEngine(workers=1, batch_window_s=0.0) as eng:
        fut = eng.submit((xe * ye).sum())
        fut.result(timeout=60)
        fut.result(timeout=60)
        fut.glom(timeout=60)
    assert len(_by_rid("serve_wake")) == 1
    assert [s.name for s in st.trace_events()].count("serve_wake") == 1


def test_tracing_off_records_nothing_and_runs_no_gc_callback():
    xe, ye = _shared(seed=6)
    x = st.from_numpy(np.ones((8, 8), np.float32))
    FLAGS.trace = False
    gc.collect()  # the callback sees tracing off and takes itself out
    assert trace_mod._on_gc not in gc.callbacks
    st.trace_clear()
    with st.ServeEngine(workers=1, batch_window_s=0.0) as eng:
        eng.submit((xe - ye).sum()).glom(timeout=60)
    (st.as_expr(x) * 2.0).evaluate().glom()
    st.from_numpy(np.ones((4, 4), np.float32))
    gc.collect()
    assert st.trace_events() == []
    assert trace_mod._on_gc not in gc.callbacks
    FLAGS.trace = True
    with trace_mod.span("first"):  # the first span hooks it again
        pass
    assert trace_mod._on_gc in gc.callbacks


def test_fetch_wait_nests_inside_fetch():
    x = st.from_numpy(np.arange(64, dtype=np.float32).reshape(8, 8))
    got = (st.as_expr(x) + 1.0).evaluate().glom()
    np.testing.assert_array_equal(got, np.arange(64).reshape(8, 8) + 1)
    spans = st.trace_events()
    fetch = [s for s in spans if s.name == "fetch"]
    wait = [s for s in spans if s.name == "fetch_wait"]
    assert len(fetch) == len(wait) == 1
    f, w = fetch[0], wait[0]
    assert w.tid == f.tid and w.depth == f.depth + 1
    assert f.ts <= w.ts and w.ts + w.dur <= f.ts + f.dur
    assert f.args["shape"] == (8, 8)


def test_upload_span_around_from_numpy():
    a = np.ones((16, 32), np.float32)
    st.from_numpy(a)
    up = [s for s in st.trace_events() if s.name == "upload"]
    assert len(up) == 1 and up[0].args["bytes"] == a.nbytes


def test_record_appends_a_finished_span():
    t0 = trace_mod.now()
    trace_mod.record("stamped", t0, t0 + 0.25, rid=7)
    sp = next(s for s in st.trace_events() if s.name == "stamped")
    assert sp.ts == pytest.approx(_us(t0), abs=US)
    assert sp.dur == pytest.approx(0.25e6, abs=US)
    assert sp.seconds == pytest.approx(0.25) and sp.args == {"rid": 7}
    FLAGS.trace = False
    trace_mod.record("unseen", t0, t0 + 1.0)
    assert not [s for s in st.trace_events() if s.name == "unseen"]


def test_gc_collect_records_a_span():
    with trace_mod.span("hook"):
        pass
    st.trace_clear()
    gc.collect()
    spans = [s for s in st.trace_events() if s.name == "gc"]
    assert spans and spans[-1].args["generation"] == 2
    assert spans[-1].dur >= 0


def test_collection_under_the_tracer_lock_does_not_deadlock():
    """A collection can start while this thread holds the tracer's
    lock (an allocation inside _append); the callback must not wait for
    it. Run in a thread with a timeout, so a deadlock fails the test."""
    with trace_mod.span("hook"):
        pass
    st.trace_clear()
    done = []

    def collect_holding_lock():
        with trace_mod._lock:
            gc.collect()
        done.append(True)

    t = threading.Thread(target=collect_holding_lock, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and done
    assert [s for s in st.trace_events() if s.name == "gc"]


def test_other_gc_callbacks_see_every_phase_when_it_unhooks():
    """Taking the tracer's callback out mid-collection shifts the list
    CPython is walking; the callback after it still sees both phases."""
    seen = []

    def neighbour(phase, info):
        seen.append(phase)

    with trace_mod.span("hook"):
        pass
    gc.callbacks.append(neighbour)
    try:
        FLAGS.trace = False
        gc.collect()
        gc.collect()
    finally:
        gc.callbacks.remove(neighbour)
    assert seen == ["start", "stop", "start", "stop"]


def test_pagerank_setup_and_dispatch_spans():
    from spartan_tpu.examples.pagerank import pagerank
    from spartan_tpu.ops.segment import SegmentPlan

    rng = np.random.RandomState(0)
    n = 64
    rows, cols = rng.randint(0, n, 512), rng.randint(0, n, 512)
    links = st.SparseDistArray.from_coo(rows, cols, np.ones(512), (n, n))
    ranks = pagerank(links, num_iter=5)
    assert ranks.shape == (n,)
    names = [s.name for s in st.trace_events()]
    assert "transition" in names and "fetch" in names
    st.trace_clear()
    t = links.transition()
    t._ensure_plan()
    spans = st.trace_events()
    plan = next(s for s in spans if s.name == "segment_plan")
    inner = [s for s in spans if s.name in ("fetch", "upload")
             and plan.ts <= s.ts <= plan.ts + plan.dur]
    assert [s.name for s in inner].count("fetch") == 3
    # the data in plan order, and the plan's four index buffers
    assert [s.name for s in inner].count("upload") == 5
    assert plan.args["entries"] == t.nse
    grid_step = SegmentPlan.GB * SegmentPlan.GR
    assert plan.args["padded"] % grid_step == 0
    assert plan.args["groups"] * SegmentPlan.GB == plan.args["padded"]


def test_pagerank_fused_dispatch_then_split_fetch(monkeypatch):
    """The one-dispatch power iteration (the chip's path; here the
    kernel runs in interpret mode on one device) is a ``dispatch``
    phase followed by ``fetch`` with its ``fetch_wait``."""
    import jax

    from spartan_tpu.examples.pagerank import pagerank

    rng = np.random.RandomState(1)
    n = 64
    with st.use_mesh(st.build_mesh(jax.devices()[:1], shape=(1, 1))):
        links = st.SparseDistArray.from_coo(
            rng.randint(0, n, 512), rng.randint(0, n, 512), np.ones(512),
            (n, n))
        want = pagerank(links, num_iter=5)
        monkeypatch.setattr(st.SparseDistArray, "_default_windowed",
                            lambda self: True)
        st.trace_clear()
        got = pagerank(links, num_iter=5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    names = [s.name for s in st.trace_events() if s.name != "gc"]
    assert names[-3:] == ["dispatch", "fetch_wait", "fetch"]
