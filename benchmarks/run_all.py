"""Run all five BASELINE.json configs through spartan_tpu and print a
JSON report, graded against the committed regression thresholds
(benchmarks/thresholds.json — round-4 verdict Weak #2). Timings force
a result fetch.

Usage: python benchmarks/run_all.py [--small] [--update-thresholds]
  --update-thresholds  rewrite this platform's thresholds at 0.7x the
                       measured dispatch-amortized metrics (commit the
                       result); full-size runs only
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = "--small" in sys.argv


def _time(fn, iters=3, warmup=1):
    """Median of ``iters`` reps (median beats best-of for a committed
    artifact: robust to one load spike AND one lucky cache hit)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def config1_map_sum(st):
    """Elementwise map + global sum on 4096x4096 (BASELINE.json:7)."""
    n = 512 if SMALL else 4096
    rng = np.random.RandomState(0)
    x = st.from_numpy(rng.rand(n, n).astype(np.float32))
    y = st.from_numpy(rng.rand(n, n).astype(np.float32))

    def run():
        return float(((x + y) * 3.0 - x).sum().glom())

    t = _time(run)
    return {"seconds": t, "gflops": 4.0 * n * n / t / 1e9, "n": n}


def config2_dot(st):
    """Dense dot 8192x8192 (BASELINE.json:8)."""
    n = 512 if SMALL else 8192
    rng = np.random.RandomState(1)
    a = st.from_numpy(rng.rand(n, n).astype(np.float32))
    b = st.from_numpy(rng.rand(n, n).astype(np.float32))

    def run():
        return float((st.dot(a, b) * (4.0 / n)).sum().glom())

    t = _time(run)
    return {"seconds": t, "tflops": 2.0 * n ** 3 / t / 1e12, "n": n}


def config3_kmeans(st):
    """k-means 1M x 128, k=64 (BASELINE.json:9)."""
    import jax
    import jax.numpy as jnp

    from spartan_tpu.examples.kmeans import kmeans_step
    from spartan_tpu.expr.base import ValExpr
    from spartan_tpu.kernels import kmeans as kmeans_kernel

    n = 10_000 if SMALL else 1_000_000
    d, k = 128, 64
    rng = np.random.RandomState(2)
    pts_np = rng.rand(n, d).astype(np.float32)
    c_np = rng.rand(k, d).astype(np.float32)
    out = {"n": n, "d": d, "k": k}

    npad = -(-n // 1024) * 1024
    if kmeans_kernel.supports(npad, d, k):
        # fused Pallas iteration kernel, points resident on device
        pts_j = jnp.zeros((npad, d), jnp.float32)
        pts_j = pts_j.at[:n].set(pts_np)
        valid = n if npad != n else None
        state = {"c": jnp.asarray(c_np)}

        def run():
            state["c"] = kmeans_kernel.step(pts_j, state["c"], k,
                                            valid_rows=valid)
            np.asarray(jax.device_get(state["c"]))

        out["sec_per_iter"] = _time(run, iters=5)
        # all iterations in one dispatch (the production shape)
        c0 = jnp.asarray(c_np)

        def run_fused():
            np.asarray(jax.device_get(
                kmeans_kernel.run(pts_j, c0, k, jnp.int32(10),
                                  valid_rows=valid)))

        out["sec_per_iter_fused"] = _time(run_fused, iters=3) / 10
    else:
        pts = st.from_numpy(pts_np)
        state = {"c": ValExpr(st.as_expr(c_np).evaluate())}

        def run():
            state["c"] = ValExpr(
                kmeans_step(pts, state["c"], k).evaluate())
            state["c"].glom()

        out["sec_per_iter"] = _time(run, iters=5)
    out["iters_per_sec"] = 1.0 / out["sec_per_iter"]
    return out


def config4_logreg(st):
    """Logistic-regression SGD on synthetic 10M-row dense
    (BASELINE.json:10)."""
    from spartan_tpu.examples.regression import logistic_grad
    from spartan_tpu.expr.base import ValExpr

    n = 100_000 if SMALL else 10_000_000
    d = 32
    rng = np.random.RandomState(3)
    X = st.from_numpy(rng.rand(n, d).astype(np.float32))
    y = st.from_numpy((rng.rand(n) > 0.5).astype(np.float32))
    state = {"w": ValExpr(st.zeros((d,), np.float32).evaluate())}

    def run():
        g = logistic_grad(X, y, state["w"])
        state["w"] = ValExpr((state["w"] - 0.1 * g).evaluate())
        state["w"].glom()

    t = _time(run, iters=5)
    # whole SGD run as one st.loop program (the production shape)
    from spartan_tpu.examples.regression import logistic_regression

    t_fused = _time(lambda: logistic_regression(X, y, num_iter=10),
                    iters=3) / 10
    return {"sec_per_iter": t, "sec_per_iter_fused": t_fused,
            "iters_per_sec": 1.0 / t, "n": n, "d": d}


def config5_sparse(st):
    """Sparse PageRank + SSVD (BASELINE.json:11)."""
    from spartan_tpu.array.sparse import SparseDistArray
    from spartan_tpu.examples.pagerank import pagerank
    from spartan_tpu.examples.ssvd import ssvd

    n = 10_000 if SMALL else 1_000_000
    deg = 16
    rng = np.random.RandomState(4)
    rows = np.repeat(np.arange(n), deg)
    cols = rng.randint(0, n, n * deg)
    links = SparseDistArray.from_coo(rows, cols,
                                     np.ones(n * deg, np.float32), (n, n))
    pr_iter = _time(lambda: pagerank(links, num_iter=10), iters=3) / 10

    m_rows = 1024 if SMALL else 8192
    a = st.from_numpy(rng.rand(m_rows, 512).astype(np.float32))
    ssvd_t = _time(lambda: ssvd(a, rank=32), iters=3)
    # record which spmv path the default dispatch used, so the number is
    # attributable to the same code path the multi-chip tests exercise
    return {"pagerank_sec_per_iter": pr_iter, "pagerank_edges": n * deg,
            "pagerank_spmv_path": links.transition().default_impl(),
            "ssvd_seconds": ssvd_t, "ssvd_shape": [m_rows, 512]}


def dispatch_overhead(st):
    """Steady-state cached-evaluate() host overhead, plan cache on vs
    off (benchmarks/dispatch_overhead.py): the planner-elimination
    floor of the plan-cache PR."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import dispatch_overhead as do

    return do.measure(iters=20, n=512 if SMALL else 4096)


def verify_overhead(st):
    """Graph-sanitizer cost (benchmarks/verify_overhead.py): st.check
    on the k-means step DAG vs a cold evaluate (<10% floor), and the
    plan-cache-hit toll of FLAGS.verify_evaluate (~0 by construction:
    checking is wired into the miss path only)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import verify_overhead as vo

    return vo.measure(iters=20, n=512 if SMALL else 4096)


def obs_overhead(st):
    """Observability cost (benchmarks/obs_overhead.py): tracing on vs
    off on the steady-state k-means step; <=5% is the ISSUE-3 gate.
    Also carries the step's st.explain cost-analysis FLOPs."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import obs_overhead as oo

    return oo.measure(iters=30, n=512 if SMALL else 4096)


def numerics_overhead(st):
    """Numerics-sentinel cost (benchmarks/numerics_overhead.py):
    audit-OFF hooks vs a stubbed-out baseline on the steady-state
    k-means hit path; <=1% is the ISSUE-4 gate. Audit-ON is reported,
    not gated (a debugging mode)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import numerics_overhead as no

    return no.measure(iters=60, n=512 if SMALL else 4096)


def resilience_overhead(st):
    """Resilience-layer cost (benchmarks/resilience_overhead.py):
    chaos-OFF policy-engine wiring vs a stubbed-out baseline on the
    steady-state k-means hit path; <=1% is the ISSUE-5 gate (one
    module-attribute read per dispatch + one thread-local getattr per
    plan-key computation)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import resilience_overhead as ro

    return ro.measure(iters=60, n=512 if SMALL else 4096)


def elastic_overhead(st):
    """Elastic-recovery gates (benchmarks/elastic_recovery.py): the
    epoch machinery's off-path cost on the steady-state hit path
    (<=1% is the ISSUE-7 gate: one epoch compare in the memoized mesh
    key + one per-leaf epoch compare per dispatch) and time-to-resume
    (detect -> drain -> rebuild -> evict -> replan -> first
    post-recovery dispatch; reported, not gated)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import elastic_recovery as er

    return er.measure(iters=60, n=512 if SMALL else 4096)


def memgov_overhead(st):
    """Memory-governor gates (benchmarks/memory_governor.py): the
    hit-path cost with no budget known (<=1% is the ISSUE-8 gate:
    one _Plan.governed_rung slot read per dispatch; the estimator
    runs on misses only) plus the model's predicted-vs-XLA
    memory_analysis error report."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import memory_governor as mg

    return mg.measure(iters=60, n=512 if SMALL else 4096)


def calibration_overhead(st):
    """Prediction-loop gates (benchmarks/calibration_overhead.py):
    the cost ledger's hit-path toll with the feature DISABLED (<=1%
    is the ISSUE-9 gate: one flag read per dispatch) plus the
    ledger-on recording cost, reported unjudged (the production
    default's price: a dict update under the ledger lock per
    dispatch)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import calibration_overhead as co

    return co.measure(iters=60, n=512 if SMALL else 4096)


def redistribution_overhead(st):
    """Redistribution-planner gates (benchmarks/redistribution.py):
    the planner's off-path toll on the steady-state hit path (<=1% is
    the ISSUE-10 gate; the hooks are trace-time only, so the true
    difference is zero — lower-quartile paired-block estimator) plus
    the decomposed-vs-GSPMD bytes/latency A/B on the reshard-heavy
    transpose-chain + GEMM-layout-flip pipeline and the per-edge
    compiled-bytes matrix (reported unjudged on CPU; gated on the
    next TPU run)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import redistribution as rr

    return rr.measure(iters=60, n=512 if SMALL else 4096,
                      ab_n=128 if SMALL else 256)


def profile_overhead(st):
    """Device-time attribution gates (benchmarks/profile_overhead.py):
    the sampler's off-path toll on the steady-state hit path (<=1% is
    the ISSUE-11 gate: one flag read per dispatch) plus the
    sampled-on cost at profile_sample_every=4, reported unjudged (a
    sampled dispatch pays for its attribution replay by design)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import profile_overhead as po

    return po.measure(iters=60, n=512 if SMALL else 4096)


def incremental_overhead(st):
    """Delta-aware evaluation gates (benchmarks/incremental.py): the
    engine's off-path toll on the steady-state hit path with
    FLAGS.incremental off (the production default — one flag read;
    <=1% vs a null-shim build, cpu AND tpu) and the warm-step payoff:
    edge-insert PageRank with ~1% of the transition matrix's columns
    dirty per batch must serve the warm step >=5x faster than the
    full-recompute arm (cpu gate), bit-equal, with counter evidence
    (inc_steps_incremental / inc_fallbacks) riding the record."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import incremental as inc_bench

    if SMALL:
        return inc_bench.measure(iters=40, n=512, speedup_n=1024,
                                 speedup_iters=6)
    return inc_bench.measure()


def plan_audit_overhead(st):
    """Plan-auditor gates (benchmarks/plan_audit.py): golden audits of
    four canonical plans (dot / stencil halo / sample sort /
    incremental splice) flattened into exact collective-count and
    byte-total gates — the CI tripwire for communication regressions —
    plus the auditor's hit-path toll (<=1% is the ISSUE-17 gate: the
    audit is wired into the compile-miss path only, so verify-on and
    verify-off hit iterations run identical code)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import plan_audit as pa

    return pa.measure(iters=30, n=256 if SMALL else 512)


def serving_overhead(st):
    """Serving-engine gates (benchmarks/serving_latency.py): 16-client
    coalesced throughput vs a serial evaluate() loop (>=3x is the
    ISSUE-6 gate — one compile, one dispatch, N responses) and the
    off-path toll of the serve layer on plain evaluate() (<=1%)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serving_latency as sl

    if SMALL:
        return sl.measure(clients=16, per_client=8, reps=3, iters=48,
                          n=128)
    return sl.measure()


def skew_overhead(st):
    """Skew-observatory gates (benchmarks/skew_overhead.py): the
    shard-level skew layer's off-path toll on the steady-state hit
    path (<=1% is the ISSUE-19 gate; the observatory rides
    FLAGS.profile_sample_every's existing gate and adds ZERO reads of
    its own to dispatch — Q1 paired-block estimator vs a null-shim
    build, cpu AND tpu) plus the sampled (skew-on) ratio, reported
    unjudged (a sampled dispatch pays for its attribution + shard
    walks by design), with the last sample's worst imbalance ratio
    riding the record as evidence."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import skew_overhead as sk

    if SMALL:
        return sk.measure(iters=32, n=512)
    return sk.measure(iters=64, n=4096)


def integrity_overhead(st):
    """SDC-sentinel gates (benchmarks/integrity_overhead.py): the
    integrity layer's off-path toll on the steady-state hit path
    (<=1% is the ISSUE-20 gate; with FLAGS.integrity_check off the
    sentinel is ONE flag read per dispatch — Q1 paired-block
    estimator vs a null-shim build, cpu AND tpu) plus the checks-on
    ratio, reported unjudged (a screened dispatch pays its checksum
    walk + rotated redundant re-execution by design), with the
    sentinel's check/violation counters riding the record as
    evidence."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import integrity_overhead as ig

    if SMALL:
        return ig.measure(iters=32, n=512)
    return ig.measure(iters=64, n=4096)


def monitor_overhead(st):
    """Continuous-monitor gates (benchmarks/monitor_overhead.py): the
    closed-loop telemetry layer's toll on the serve hot path with
    FLAGS.monitor off (the production default — one memoized SLO-class
    lookup per submit, one slo.observe per resolve, one pricing flag
    read per pop; <=1% vs a null-shim build, cpu AND tpu, Q1 paired-
    block estimator) plus the daemon-on ratio and the directly-timed
    per-tick sample cost, both reported unjudged (the knob's price)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import monitor_overhead as mo

    if SMALL:
        return mo.measure(iters=32, n=128)
    return mo.measure(iters=60, n=512)


def _with_metrics(fn, st):
    """Run one benchmark config and attach the ``st.metrics()``
    snapshot it produced (phase p50/p95, plan-hit ratio, counters) to
    its record — from this PR on, BENCH_*.json trajectories carry
    per-phase data that can be compared across rounds. Each record
    also carries the non-default FLAGS in effect and the plan/compile
    cache sizes AFTER the config ran (r05 cold-start follow-up: a TPU
    regression must be attributable to PR 2-5 flag defaults vs
    compile-cache growth from the committed artifact alone — the full
    defaults snapshot rides the report top level)."""
    from spartan_tpu.expr import base as expr_base
    from spartan_tpu.utils import profiling

    profiling.reset_counters()
    rec = fn(st)
    snap = st.metrics()
    rec["metrics"] = {
        "plan_cache": snap["plan_cache"],
        "flags_nondefault": st.FLAGS.snapshot_nondefault(),
        "plan_cache_size": expr_base.plan_cache_size(),
        "compile_cache_size": expr_base.compile_cache_size(),
        "counters": snap["counters"],
        "phase_us": {
            name.split(":", 1)[1]: {
                "p50": round(h["p50"] * 1e6, 1),
                "p95": round(h["p95"] * 1e6, 1),
                "max": round(h["max"] * 1e6, 1),
                "sum": round(h["sum"] * 1e6, 1),
                "count": h["count"],
            }
            for name, h in snap["histograms"].items()
            if name.startswith("phase:")},
    }
    return rec


def guard_metrics(report) -> dict:
    """The dispatch-amortized metrics the regression guard grades —
    fused/looped forms chosen because per-dispatch timings swung ~2x
    run to run (docs/BENCH.md round-4 note) while amortized loops
    stayed stable. ``dispatch_overhead_speedup`` is
    host-side planning time, stable on any platform."""
    c3, c4, c5 = (report["config3_kmeans"], report["config4_logreg"],
                  report["config5_sparse"])
    km = c3.get("sec_per_iter_fused", c3["sec_per_iter"])
    return {
        "kmeans_iters_per_sec": 1.0 / km,
        "logreg_iters_per_sec": 1.0 / c4["sec_per_iter_fused"],
        "pagerank_iters_per_sec": 1.0 / c5["pagerank_sec_per_iter"],
        "ssvd_seconds": c5["ssvd_seconds"],
        "dispatch_overhead_speedup":
            report["dispatch_overhead"].get("speedup"),
        "verify_check_vs_cold_ratio":
            report["verify_overhead"].get("check_vs_cold_ratio"),
        "obs_overhead_ratio":
            report["obs_overhead"].get("obs_overhead_ratio"),
        "numerics_off_overhead_ratio":
            report["numerics_overhead"].get(
                "numerics_off_overhead_ratio"),
        "resilience_off_overhead_ratio":
            report["resilience_overhead"].get(
                "resilience_off_overhead_ratio"),
        "serve_coalesced_speedup":
            report["serving_overhead"].get("serve_coalesced_speedup"),
        "serve_off_overhead_ratio":
            report["serving_overhead"].get("serve_off_overhead_ratio"),
        "monitor_off_overhead_ratio":
            report["monitor_overhead"].get(
                "monitor_off_overhead_ratio"),
        "skew_off_overhead_ratio":
            report["skew_overhead"].get(
                "skew_off_overhead_ratio"),
        "integrity_off_overhead_ratio":
            report["integrity_overhead"].get(
                "integrity_off_overhead_ratio"),
        "elastic_off_overhead_ratio":
            report["elastic_overhead"].get(
                "elastic_off_overhead_ratio"),
        "memgov_off_overhead_ratio":
            report["memgov_overhead"].get(
                "memgov_off_overhead_ratio"),
        "calibration_off_overhead_ratio":
            report["calibration_overhead"].get(
                "calibration_off_overhead_ratio"),
        "redist_off_overhead_ratio":
            report["redistribution_overhead"].get(
                "redist_off_overhead_ratio"),
        "profile_off_overhead_ratio":
            report["profile_overhead"].get(
                "profile_off_overhead_ratio"),
        "incremental_off_overhead_ratio":
            report["incremental_overhead"].get(
                "incremental_off_overhead_ratio"),
        "incremental_warm_speedup_1pct":
            report["incremental_overhead"].get(
                "incremental_warm_speedup_1pct"),
        "audit_off_overhead_ratio":
            report["plan_audit_overhead"].get(
                "audit_off_overhead_ratio"),
        # golden plan audits (benchmarks/plan_audit.py): exact
        # collective counts + byte ceilings per canonical plan
        **{k: report["plan_audit_overhead"].get(k)
           for k in ("audit_dot_all_reduce", "audit_dot_all_gather",
                     "audit_dot_comm_kib", "audit_stencil_permute",
                     "audit_stencil_all_gather",
                     "audit_stencil_comm_kib",
                     "audit_sort_all_to_all", "audit_sort_all_reduce",
                     "audit_sort_comm_kib",
                     "audit_splice_full_gather_findings",
                     "audit_splice_comm_kib")},
    }


def main():
    import jax

    import spartan_tpu as st
    from spartan_tpu.utils import benchguard

    platform = jax.devices()[0].platform
    report = {
        "platform": platform,
        "device": str(jax.devices()[0]),
        "small": SMALL,
        "config1_map_sum": _with_metrics(config1_map_sum, st),
        "config2_dot": _with_metrics(config2_dot, st),
        "config3_kmeans": _with_metrics(config3_kmeans, st),
        "config4_logreg": _with_metrics(config4_logreg, st),
        "config5_sparse": _with_metrics(config5_sparse, st),
        "dispatch_overhead": _with_metrics(dispatch_overhead, st),
        "verify_overhead": _with_metrics(verify_overhead, st),
        "obs_overhead": _with_metrics(obs_overhead, st),
        "numerics_overhead": _with_metrics(numerics_overhead, st),
        "resilience_overhead": _with_metrics(resilience_overhead, st),
        "serving_overhead": _with_metrics(serving_overhead, st),
        "monitor_overhead": _with_metrics(monitor_overhead, st),
        "skew_overhead": _with_metrics(skew_overhead, st),
        "integrity_overhead": _with_metrics(integrity_overhead, st),
        "elastic_overhead": _with_metrics(elastic_overhead, st),
        "memgov_overhead": _with_metrics(memgov_overhead, st),
        "calibration_overhead": _with_metrics(calibration_overhead, st),
        "redistribution_overhead": _with_metrics(
            redistribution_overhead, st),
        "profile_overhead": _with_metrics(profile_overhead, st),
        "incremental_overhead": _with_metrics(incremental_overhead,
                                              st),
        "plan_audit_overhead": _with_metrics(plan_audit_overhead, st),
    }
    # full flag state once at report level (the per-record
    # flags_nondefault deltas are diffs against these defaults)
    report["flags"] = st.FLAGS.snapshot()
    metrics = guard_metrics(report)
    if not SMALL:
        # grade BEFORE any threshold rewrite: an --update-thresholds
        # run must still report regressions against the committed
        # floors, not against the floors it is about to write
        report["guard"] = benchguard.check(metrics, platform)
    if "--update-thresholds" in sys.argv and not SMALL:
        path = benchguard.THRESHOLDS_PATH
        try:
            with open(path) as f:
                table = json.load(f)
        except (OSError, ValueError):
            table = {"note": "Regression floors at 0.7x the committed "
                             "round's dispatch-amortized measurements "
                             "(run_all.py --update-thresholds)."}
        entry = {}
        # fixed acceptance gates (ISSUE gates, not floors derived from
        # the measurement): verify <10% of a cold evaluate, tracing
        # <=5% of a steady-state evaluate, numerics sentinel (audit
        # off) <=1% of a steady-state evaluate
        # serve_off carries 2% (not 1%): re-committed by the ISSUE-9
        # de-flake — the ratio measures a ~0 true difference and its
        # median-of-k interleaved estimate still wobbles ~1% on the
        # 1-core CPU box (see thresholds.json note_serving)
        fixed = {"verify_check_vs_cold_ratio": 0.1,
                 "obs_overhead_ratio": 0.05,
                 "numerics_off_overhead_ratio": 0.01,
                 "resilience_off_overhead_ratio": 0.01,
                 "serve_off_overhead_ratio": 0.02,
                 "monitor_off_overhead_ratio": 0.01,
                 "skew_off_overhead_ratio": 0.01,
                 "integrity_off_overhead_ratio": 0.01,
                 "elastic_off_overhead_ratio": 0.01,
                 "memgov_off_overhead_ratio": 0.01,
                 "calibration_off_overhead_ratio": 0.01,
                 "redist_off_overhead_ratio": 0.01,
                 "profile_off_overhead_ratio": 0.01,
                 "incremental_off_overhead_ratio": 0.01,
                 "audit_off_overhead_ratio": 0.01}
        # golden-audit gates: collective COUNTS commit exact
        # (min==max — a regression in either direction is a lowering
        # change worth a look), modeled byte totals commit a 1.25x
        # ceiling (benchmarks/plan_audit.py)
        audit_exact = {"audit_dot_all_reduce", "audit_dot_all_gather",
                       "audit_stencil_permute",
                       "audit_stencil_all_gather",
                       "audit_sort_all_to_all",
                       "audit_sort_all_reduce",
                       "audit_splice_full_gather_findings"}
        audit_ceiling = {"audit_dot_comm_kib",
                         "audit_stencil_comm_kib",
                         "audit_sort_comm_kib",
                         "audit_splice_comm_kib"}
        # fixed FLOORS (ISSUE gates on ratios that must stay high):
        # coalescing must amortize dispatch >=3x across 16 clients
        fixed_min = {"incremental_warm_speedup_1pct": 5.0,
                     "serve_coalesced_speedup": 3.0}
        for k, v in metrics.items():
            if (k == "incremental_warm_speedup_1pct"
                    and platform != "cpu"):
                # the >=5x warm-step gate is the ISSUE-16 CPU
                # acceptance; TPU carries only the off-path toll
                continue
            if k in fixed_min:
                entry[k] = {"min": fixed_min[k]}
            elif k in fixed:
                entry[k] = {"max": fixed[k]}
            elif k in audit_exact:
                entry[k] = {"min": v, "max": v}
            elif k in audit_ceiling:
                entry[k] = {"max": round(v * 1.25, 1)}
            elif k.endswith("seconds"):
                entry[k] = {"max": round(v / 0.7, 4)}
            else:
                entry[k] = {"min": round(v * 0.7, 4)}
        table[platform] = entry
        with open(path, "w") as f:
            json.dump(table, f, indent=2)
        report["thresholds_updated"] = path
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
