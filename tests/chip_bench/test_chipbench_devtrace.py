"""The reduction from a device trace to metrics, and the cost and peak
arithmetic behind the roofline shares, checked against hand counts and
against a small trace recorded on a v5e chip."""

import os
import sys
from types import SimpleNamespace

import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import devtrace  # noqa: E402
import harness  # noqa: E402
from devtrace import Op, Reduction  # noqa: E402

# a --trace 1 run of pagerank_1m.rank10 on one v5e (my chip run, PR 22):
# a 5 s window, 4 jobs of 10 power iterations
CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "pagerank_rank10_v5e.xplane.pb")
V5E = harness.peak("TPU v5 lite")


def test_interval_arithmetic_by_hand():
    iv = [(5, 6), (0, 2), (1, 3)]
    assert devtrace.union(iv) == [(0, 3), (5, 6)]
    assert devtrace.covered(iv, 0, 10) == 4
    assert devtrace.covered(iv, 1, 5.5) == 2.5
    assert devtrace.gaps(iv, 0, 10) == [(3, 5), (6, 10)]
    assert devtrace.gaps(iv, 1, 2) == []
    assert devtrace.gaps([], 0, 4) == [(0, 4)]
    spans = [("outer", 0, 10), ("inner", 2.5, 5.5)]
    assert devtrace.label_gaps([(3, 5), (6, 10)], spans) == {
        "inner": 2, "outer": 4}
    assert devtrace.label_gaps([(11, 12)], spans) == {"none": 1}


def _reduction():
    red = Reduction(lo=0.0, hi=10e9)
    red.ops = {
        0: [Op("body.1", "tpu_custom_call", "jit_run", 0.0, 4e9),
            Op("fusion.2", "fusion:kLoop", "jit_run", 3e9, 5e9),
            Op("all-gather.3", "all-gather", "jit_dot", 7e9, 8e9)],
        1: [Op("body.1", "tpu_custom_call", "jit_run", 0.0, 2e9)]}
    red.spans = [("bench.job", 0.0, 8.5e9)]
    return red


def test_busy_idle_and_per_op():
    red = _reduction()
    assert red.window_s == 10.0
    assert red.busy_s(0) == 6.0  # [0, 5] and [7, 8]
    assert red.busy_s(1) == 2.0
    assert red.mean_busy_s() == 4.0
    assert red.idle_gaps(0) == [(5e9, 7e9), (8e9, 10e9)]
    assert red.op_seconds(0) == {"jit_run:tpu_custom_call:body.1": 4.0,
                                 "jit_run:fusion:kLoop:fusion.2": 2.0,
                                 "jit_dot:all-gather:all-gather.3": 1.0}
    bd = devtrace.breakdown(red)
    assert bd["device_ops"][0] == ["jit_run:tpu_custom_call:body.1", 3.0]
    # a gap is named by the span over its midpoint. Device 0: (5, 7) in
    # bench.job, (8, 10) after it; device 1: (2, 10), midpoint in it
    assert dict(bd["idle_gaps"]) == {"bench.job": 5.0, "none": 1.0}
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_idle_share_readers():
    ctx = SimpleNamespace(trace=_reduction())
    for name in ("device.idle_share.step", "device.idle_share.serve"):
        assert harness.load_reader(name).read(ctx) == 60.0


def test_hlo_text_parsing():
    """Op events are named by their HLO text on the chip."""
    assert devtrace.parse_op(
        '%body.6 = (f32[128,128]{1,0:T(8,128)S(1)}, f32[1,128]{1,0:T(1,128)'
        'S(1)}) custom-call(f32[1000448,128]{1,0:T(8,128)} %get-tuple-elem'
        'ent.157), custom_call_target="tpu_custom_call", operand_layout_co'
        'nstraints={f32[1000448,128]{1,0}}') == ("body.6", "tpu_custom_call")
    assert devtrace.parse_op(
        '%while.2 = (s32[]{:T(128)}, f32[1000000]{0:T(1024)S(1)}) while((s'
        '32[]{:T(128)}, f32[1000000]{0:T(1024)S(1)}) %tuple.16), condition'
        '=%c, body=%b') == ("while.2", "while")
    assert devtrace.parse_op(
        '%fusion.7 = f32[16498688]{0:T(1024)S(1)} fusion(f32[1000000]{0:T('
        '1024)S(1)} %g, s32[16498688]{0:T(1024)} %b), kind=kCustom, calls='
        '%f') == ("fusion.7", "fusion:kCustom")
    assert devtrace.parse_op(
        '%all-gather-start.1 = (f32[4096,4096]{1,0}, f32[8192,4096]{1,0}) '
        'all-gather-start(f32[4096,4096]{1,0} %p), replica_groups={{0,1}}'
    ) == ("all-gather-start.1", "all-gather-start")
    assert devtrace.module_name("jit_run(6287802397132685784)") == "jit_run"
    mods = [("jit_a", 0, 10), ("jit_b", 20, 30)]
    ops = devtrace.attach([("%x.1 = f32[] copy(f32[] %y)", 1, 2),
                           ("%x.2 = f32[] copy(f32[] %y)", 15, 16),
                           ("%x.3 = f32[] copy(f32[] %y)", 25, 26)], mods)
    assert [o.module for o in ops] == ["jit_a", "", "jit_b"]
    assert ops[0].kind == "copy" and ops[0].name == "x.1"


def _ctx(config, ops, steps):
    red = Reduction(lo=0.0, hi=1e12)
    red.ops = {0: ops}
    red.modules = {0: [("jit__pagerank_loop", 0.0, 1000e6)]}
    return SimpleNamespace(trace=red, steps=steps, config=config,
                           peak=V5E,
                           costs=lambda k: harness.load_module("costs", k))


def test_kmeans_roofline_by_hand():
    cfg = {"n": 1_000_000, "d": 128, "k": 64}
    w = harness.load_module("costs", "kmeans").lloyd_iteration(cfg)
    assert w["flops"] == 4 * 1_000_000 * 64 * 128
    assert w["bytes"] == 4 * 1_000_000 * 128 + 2 * 4 * 64 * 128
    # bytes bound: 512,065,536 B at 819 GB/s = 0.62523 ms; the kernel
    # takes 4 ms an iteration over 20 iterations
    ops = [Op("body.7", "tpu_custom_call", "jit_run", 0.0, 80e6),
           Op("fusion.1", "fusion:kLoop", "jit_run", 80e6, 90e6)]
    got = harness.load_module("metrics", "kmeans_roofline").read(
        _ctx(cfg, ops, 20))
    assert got == pytest.approx(100 * 512_065_536 / 819e9 / 4e-3)
    assert harness.load_module("metrics", "kmeans_roofline").read(
        _ctx(cfg, [], 20)) is None


def test_pagerank_roofline_by_hand():
    cfg = {"scale": 20, "edge_factor": 16, "undirected": True}
    w = harness.load_module("costs", "pagerank").power_iteration(cfg)
    # 33,554,432 entries of 12 bytes, and 2^20 ranks read and written
    assert w["bytes"] == 33_554_432 * 12 + 8 * 1_048_576
    cfg["undirected"] = False
    assert harness.load_module("costs", "pagerank").power_iteration(
        cfg)["flops"] == 2 * 16_777_216
    got = harness.load_module("metrics", "pagerank_roofline").read(
        _ctx(cfg, [], 10))
    assert got == pytest.approx(
        100 * (16_777_216 * 12 + 8 * 1_048_576) / 819e9 / 0.1)


def test_dot_roofline_and_collectives_by_hand():
    cfg = {"n": 8192, "mesh": [2, 2]}
    w = harness.load_module("costs", "dot").product_per_chip(cfg)
    assert w["flops"] == 2 * 8192 ** 3 / 4
    # compute bound: 274.9 GFLOP at 197 TFLOP/s = 1.3955 ms a step
    ops = [Op("fusion.1", "fusion:kOutput", "jit_traced", 0.0, 2e6),
           Op("fusion.9", "fusion:kLoop", "jit_traced", 2e6, 3e6),
           Op("all-gather-done.2", "all-gather-done", "jit_traced",
              4e6, 4.5e6)]
    ctx = _ctx(cfg, ops, 1)
    ctx.trace.async_ops = {0: [Op("all-gather-start.2", "all-gather-start",
                                  "jit_traced", 3e6, 4.2e6)]}
    got = harness.load_module("metrics", "dot_roofline").read(ctx)
    assert got == pytest.approx(100 * (2 * 8192 ** 3 / 4 / 197e12) / 2e-3)
    # union of [3, 4.2] and [4, 4.5] ms
    assert harness.load_module("metrics", "ici.collective_ms").read(
        ctx) == pytest.approx(1.5)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak("TPU v99 imaginary")


def test_recorded_chip_trace():
    """Numbers read off the capture by hand (trace viewer): 4 runs of
    jit__pagerank_loop, 5.0605 s in all; the gather fusion 4.7056 s and
    the windowed segment-sum kernel 0.3389 s of it."""
    red = devtrace.reduce(CHIP_TRACE)
    assert list(red.ops) == [0]
    assert red.window_s == pytest.approx(5.0779, abs=1e-4)
    assert red.busy_s(0) == pytest.approx(5.0606, abs=1e-4)
    assert red.module_seconds(0, "jit__pagerank_loop") == pytest.approx(
        5.0605, abs=1e-4)
    ops = red.op_seconds(0)
    assert ops["jit__pagerank_loop:fusion:kCustom:fusion.7"] == \
        pytest.approx(4.7056, abs=1e-4)
    assert ops["jit__pagerank_loop:tpu_custom_call:body.5"] == \
        pytest.approx(0.3389, abs=1e-4)
    assert not any(k.split(":")[1] == "while" for k in ops)
    # that run's graph: 16,000,000 entries over 1,000,000 nodes; the
    # nearest configuration in today's keys, 2^20 nodes and 2^24 entries
    ctx = SimpleNamespace(trace=red, steps=40, peak=V5E,
                          config={"scale": 20, "edge_factor": 16,
                                  "undirected": False},
                          costs=lambda k: harness.load_module("costs", k))
    share = harness.load_module("metrics", "pagerank_roofline").read(ctx)
    assert share == pytest.approx(
        100 * (16_777_216 * 12 + 8 * 1_048_576) / 819e9 / (5.0605 / 40),
        rel=1e-3)
    idle = harness.load_reader("device.idle_share.step").read(ctx)
    assert idle == pytest.approx(100 * (1 - 5.0606 / 5.0779), abs=0.01)
    bd = devtrace.breakdown(red)
    assert bd["device_ops"][0][0] == (
        "jit__pagerank_loop:fusion:kCustom:fusion.7")
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s(0), rel=1e-6)
