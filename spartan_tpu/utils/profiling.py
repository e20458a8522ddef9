"""Tracing / profiling / cost analysis.

Parity with the reference's FLAGS-gated profiling (SURVEY.md §5: cProfile
dumps, timer spans, per-expr error attribution), re-based on the TPU
stack: ``jax.profiler`` traces (TensorBoard/Perfetto), a timing
harness over work that ends in a fetch or ``block_until_ready``,
per-expr HLO cost from ``compiled.cost_analysis()``, and device memory
stats.

Profiling entry points (one funnel since the device-time attribution
PR): ``st.profile(expr)`` / ``FLAGS.profile_sample_every``
(``obs/profile.py``) are THE way to measure where device time goes —
the legacy ``FLAGS.profile`` whole-dispatch wrap is gone.
:func:`profile_trace` remains for explicit raw captures (a TensorBoard
session over a driver loop) and writes to ``FLAGS.profile_dir``; the
attribution tiers capture into throwaway temp dirs instead.

Since the observability PR this module is a thin facade over
``spartan_tpu/obs``: counters and per-phase timers live in the typed
metrics registry (``obs.metrics.REGISTRY``; snapshot via
``st.metrics()``), and :func:`phase` both feeds the per-phase
histograms AND emits a span into the trace ring buffer
(``st.trace_export``). The PR-1 API (``count`` / ``counters`` /
``record_phase`` / ``phase_seconds`` / ``reset_counters`` /
``plan_cache_stats``) is kept as shims so existing tests, benchmarks
and ``bench.py`` read identical shapes.

All wall-clock measurement in the package goes through this module or
``obs/`` (:func:`phase`, :func:`stopwatch`, ``obs.trace.span``) —
``tools/lint_repo.py`` forbids raw ``time.perf_counter()`` timing
anywhere else, so no timing escapes the trace.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from ..obs.metrics import METRICS_FLAG as _METRICS_FLAG
from ..obs.metrics import REGISTRY
from ..obs.trace import SpanCtx as _SpanCtx
from ..obs.trace import annotate as _obs_annotate
from ..obs.trace import device_profile as _obs_device_profile
from ..obs.trace import span as _obs_span
from .config import FLAGS
from .log import log_info

# re-exported so call sites can say ``prof.span(...)`` /
# ``prof.device_profile(...)`` without importing obs directly
# (obs.trace owns the one span implementation AND the one sanctioned
# jax.profiler entry points — lint rule 9)
span = _obs_span
device_profile = _obs_device_profile

# -- plan-cache counters and per-phase timers ----------------------------
#
# The evaluate() fast path (expr/base.py) is instrumented with named
# counters (plan_hits / plan_misses / compiles / donated_dispatches /
# evaluations) and per-phase wall-time histograms:
#
#   sign      structural signing (raw-DAG plan signature + optimized-DAG
#             compile signature)
#   optimize  the optimizer pass stack (plus per-pass ``pass:<name>``
#             and the smart-tiling ``tiling`` sub-phase)
#   compile   jit wrapper creation + the first call (trace + XLA compile)
#   dispatch  steady-state execution of an already-compiled program
#   build     Python-side assembly around dispatch: plan lookup, leaf
#             arg gathering, DistArray result wrapping
#   fetch     device -> host result transfer (DistArray.glom)
#
# Counters are process-global; tests and benchmarks bracket a region
# with reset_counters() and read counters() after.

_PHASE_PREFIX = "phase:"


def count(name: str, n: int = 1) -> None:
    if _METRICS_FLAG._value:
        REGISTRY.counter(name).inc(n)


# phase-name -> Histogram handle; registry reset() zeroes instruments
# in place (it never replaces them), so cached handles stay valid
_phase_hists: Dict[str, Any] = {}


def record_phase(name: str, seconds: float) -> None:
    if _METRICS_FLAG._value:
        h = _phase_hists.get(name)
        if h is None:
            h = REGISTRY.histogram(_PHASE_PREFIX + name)
            _phase_hists[name] = h
        h.observe(seconds)


class _PhaseCtx(_SpanCtx):
    """The span context of :func:`phase`: a SpanCtx (one allocation,
    two clock reads) whose measured ``.seconds`` also feeds the
    per-phase histogram on exit — the hot dispatch path runs several
    of these per evaluate."""

    __slots__ = ()

    def __init__(self, name: str):
        super().__init__(name, None)

    def __exit__(self, et, ev, tb) -> bool:
        r = super().__exit__(et, ev, tb)
        record_phase(self.name, self.seconds)
        return r


def phase(name: str) -> _PhaseCtx:
    """Time a named phase: a span in the trace ring (marked
    ``error=True`` with the exception type if the block raises — the
    elapsed time is recorded either way, so failed evaluates stay
    visible) plus an observation in the per-phase histogram. Yields
    the span; ``.seconds`` holds the elapsed time after exit."""
    return _PhaseCtx(name)


class Stopwatch:
    """Result of :func:`stopwatch`: ``.elapsed`` seconds after exit."""

    __slots__ = ("elapsed",)

    def __init__(self) -> None:
        self.elapsed = 0.0


@contextlib.contextmanager
def stopwatch() -> Iterator[Stopwatch]:
    """Bare timing context for measurement harnesses (calibration,
    benchmark loops): no span, no histogram — just ``.elapsed``. The
    sanctioned alternative to raw ``time.perf_counter()`` pairs, which
    the repo lint forbids outside ``obs/`` and this module."""
    sw = Stopwatch()
    t0 = time.perf_counter()
    try:
        yield sw
    finally:
        sw.elapsed = time.perf_counter() - t0


def counters() -> Dict[str, int]:
    """Snapshot of the named counters (plan_hits, plan_misses, ...);
    absent counters read as 0 via .get()."""
    return REGISTRY.counter_values()


def phase_seconds() -> Dict[str, float]:
    """Snapshot of accumulated per-phase wall time in seconds (the
    histograms' exact sums; p50/p95/max via ``st.metrics()``)."""
    snap = REGISTRY.snapshot()["histograms"]
    return {name[len(_PHASE_PREFIX):]: h["sum"]
            for name, h in snap.items()
            if name.startswith(_PHASE_PREFIX)}


def reset_counters() -> None:
    """Zero every instrument in the registry (registrations survive,
    so snapshots keep stable keys across a reset)."""
    REGISTRY.reset()


def plan_cache_stats() -> Dict[str, Any]:
    """Hit/miss view of the evaluate() plan cache, with the hit rate
    the acceptance gate asserts (None before any lookup)."""
    c = counters()
    hits = c.get("plan_hits", 0)
    misses = c.get("plan_misses", 0)
    total = hits + misses
    return {
        "plan_hits": hits,
        "plan_misses": misses,
        "compiles": c.get("compiles", 0),
        "donated_dispatches": c.get("donated_dispatches", 0),
        "hit_rate": (hits / total) if total else None,
    }


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str] = None) -> Iterator[None]:
    """Capture a device profiler trace (view in TensorBoard/Perfetto)
    via the sanctioned ``obs.trace.device_profile`` entry point."""
    trace_dir = trace_dir or FLAGS.profile_dir
    with _obs_device_profile(trace_dir):
        yield
    log_info("profiler trace written to %s", trace_dir)


def _compiled(expr):
    """Optimize + lower + compile an expr exactly the way ``evaluate``
    would, returning the jax Compiled object (for HLO inspection)."""
    from ..expr import base as expr_base
    from ..expr.optimize import optimize

    dag = optimize(expr)
    ctx = expr_base._SigCtx()
    ctx.of(dag)
    leaves = ctx.leaves
    leaf_ids = tuple(l._id for l in leaves)

    def traced(*args):
        env = dict(zip(leaf_ids, args))
        return dag.lower(env)

    lowered = jax.jit(traced).lower(
        *[expr_base._leaf_arg(l) for l in leaves])
    return lowered.compile()


def cost_analysis(expr) -> Dict[str, float]:
    """FLOPs / bytes-accessed estimate of an expr's compiled program
    (the per-expr HLO cost hook of SURVEY.md §5). The read-out goes
    through ``obs.explain.compiled_cost_analysis`` — the one
    sanctioned ``cost_analysis()`` call site (lint rule 9)."""
    from ..obs.explain import compiled_cost_analysis

    return compiled_cost_analysis(_compiled(expr))


def hlo_text(expr) -> str:
    """Compiled (post-SPMD-partitioning) HLO of an expr — lets tests
    and benchmarks count the collectives a plan actually emits."""
    return _compiled(expr).as_text()


def benchmark(fn: Callable[[], Any], iters: int = 5,
              warmup: int = 1) -> Dict[str, float]:
    """Timing harness. ``fn`` must wait for its result (``.glom()``, a
    scalar fetch or ``block_until_ready``): JAX returns before the
    device finishes, so a bare dispatch times only the enqueue."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        with stopwatch() as sw:
            fn()
        times.append(sw.elapsed)
    arr = np.asarray(times)
    return {"best": float(arr.min()), "mean": float(arr.mean()),
            "std": float(arr.std()), "iters": iters}


def device_memory_stats() -> Dict[str, Any]:
    """Per-key {max, sum} memory stats across ALL local devices —
    delegates to the sanctioned obs/metrics aggregate (lint rule 8
    keeps raw ``memory_stats()`` reads single-sourced)."""
    from ..obs.metrics import device_memory_aggregate

    return device_memory_aggregate()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named span visible in profiler traces (delegates to the
    sanctioned ``obs.trace.annotate``)."""
    with _obs_annotate(name):
        yield
