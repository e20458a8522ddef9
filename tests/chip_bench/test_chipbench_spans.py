"""The readers of the program's host spans, checked against hand counts
on made-up spans: (name, start_ns, end_ns, thread), as the harness hands
them over on the capture's clock."""

import os
import sys
from types import SimpleNamespace

import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import harness  # noqa: E402

MS = 1e6  # ns

# two client threads (1, 2) and a serve worker (9), 2 queries answered
SERVE = [
    ("upload", 0.0, 0.4 * MS, 1),
    ("serve_submit", 0.5 * MS, 1.0 * MS, 1),
    ("sign", 0.6 * MS, 0.9 * MS, 1),
    ("upload", 0.2 * MS, 0.8 * MS, 2),
    ("serve_submit", 0.9 * MS, 1.2 * MS, 2),
    ("serve_queue", 0.5 * MS, 1.5 * MS, 9),
    ("serve_queue", 0.9 * MS, 1.6 * MS, 9),
    ("serve_linger", 1.5 * MS, 3.5 * MS, 9),
    ("serve_linger", 1.6 * MS, 3.5 * MS, 9),
    ("serve_batch", 3.5 * MS, 4.5 * MS, 9),
    ("dispatch", 3.6 * MS, 4.4 * MS, 9),
    ("build", 4.0 * MS, 4.2 * MS, 9),  # nested in dispatch: counts once
    ("serve_wake", 4.6 * MS, 4.9 * MS, 1),
    ("serve_wake", 4.6 * MS, 5.1 * MS, 2),
    ("fetch", 4.9 * MS, 6.9 * MS, 1),
    ("fetch_wait", 5.0 * MS, 6.5 * MS, 1),
    ("fetch", 5.1 * MS, 6.1 * MS, 2),
    ("fetch_wait", 5.1 * MS, 5.6 * MS, 2),
    ("gc", 2.0 * MS, 2.3 * MS, 9),
    ("gc", 5.5 * MS, 5.7 * MS, 1),
]


def _read(metric, spans, steps=2):
    return harness.load_reader(metric).read(
        SimpleNamespace(spans=spans, steps=steps))


@pytest.mark.parametrize("metric,ms", [
    ("serve.queue_ms.serve", (1.0 + 0.7) / 2),
    ("serve.linger_ms.serve", (2.0 + 1.9) / 2),
    ("serve.wake_ms.serve", (0.3 + 0.5) / 2),
    ("array.upload_ms.serve", (0.4 + 0.6) / 2),
])
def test_per_query_span_sums_by_hand(metric, ms):
    assert _read(metric, SERVE) == pytest.approx(ms)


def test_plan_self_time_leaves_out_the_device_wait():
    # thread 1: sign 0.3, fetch 2.0 less its wait 1.5; thread 2: fetch
    # 1.0 less 0.5; thread 9: dispatch 0.8 (build inside it)
    assert _read("plan.host_ms.serve", SERVE) == pytest.approx(
        (0.3 + 2.0 + 1.0 + 0.8) / 2)
    assert _read("plan.self_ms.serve", SERVE) == pytest.approx(
        (0.3 + 0.5 + 0.5 + 0.8) / 2)


def test_plan_self_time_counts_each_thread_apart():
    """Two threads' overlapping fetches both count; a wait on another
    thread takes nothing off."""
    spans = [("fetch", 0.0, 4 * MS, 1), ("fetch", 1 * MS, 5 * MS, 2),
             ("fetch_wait", 1 * MS, 3 * MS, 1)]
    assert _read("plan.self_ms.serve", spans, steps=1) == pytest.approx(
        2.0 + 4.0)


def test_gc_union_per_step_and_per_query():
    spans = SERVE + [("gc", 5.6 * MS, 5.8 * MS, 2)]  # overlaps one
    for metric in ("host.gc_ms.step", "host.gc_ms.serve"):
        assert _read(metric, spans) == pytest.approx((0.3 + 0.3) / 2)


def test_no_span_reads_none():
    """A program that records none of these spans: every new reader
    reads nothing there (the collector's reader only with no span at
    all), and nothing with no step."""
    plan_only = [("dispatch", 0.0, 1 * MS, 0), ("fetch", 1 * MS, 2 * MS, 0)]
    for metric in ("serve.queue_ms.serve", "serve.linger_ms.serve",
                   "serve.wake_ms.serve", "array.upload_ms.serve"):
        assert _read(metric, plan_only) is None, metric
        assert _read(metric, SERVE, steps=0) is None, metric
    for metric in ("host.gc_ms.step", "host.gc_ms.serve"):
        assert _read(metric, []) is None, metric
        assert _read(metric, SERVE, steps=0) is None, metric
    assert _read("plan.self_ms.serve", []) is None
    # without fetch_wait spans, self time is the plan time
    assert _read("plan.self_ms.serve", plan_only) == pytest.approx(1.0)


def test_gc_reads_zero_in_a_traced_window_without_a_collection():
    """Tracing on and no collection in the window: 0 ms, not nothing."""
    plan_only = [("dispatch", 0.0, 1 * MS, 0), ("fetch", 1 * MS, 2 * MS, 0)]
    for metric in ("host.gc_ms.step", "host.gc_ms.serve"):
        assert _read(metric, plan_only, steps=7) == 0.0, metric
