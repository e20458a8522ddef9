"""Span tracer: nested wall-time spans for the plan lifecycle.

Every ``evaluate()`` emits a span tree (build -> sign -> optimize ->
per-pass -> tiling -> compile -> dispatch -> fetch, with the wait for
the device inside fetch as ``fetch_wait``; see
``utils/profiling.phase``) carrying the plan-cache key, hit/miss
status and the user build site. Host time outside that tree has spans
too: host->device copies (``upload``), a served request's submit,
queue, linger and wake (``serve_*``, carrying its flight-recorder
``rid`` and dispatch id), and Python's garbage collections (``gc``).
Spans are ring-buffered in memory (``FLAGS.trace_ring``) and
exportable as Chrome trace-event JSON via ``st.trace_export(path)`` —
load the file at https://ui.perfetto.dev or chrome://tracing.
``FLAGS.trace`` toggles recording; the recording cost is one clock
pair + a lock-guarded deque append per span; off, nothing is appended
and no gc callback runs. What tracing costs end to end on the chip
(the same benchmark runs with ``FLAGS.trace`` on and off, no profiler
capture) is in PERF.md.

:func:`record` appends a span whose edges were stamped earlier (the
serve engine's request stamps), so building it reads no clock.

Device-side attribution is separate: ``Expr.lower`` wraps every node's
kernel body in ``jax.named_scope`` (``FLAGS.trace_annotations``) so
XLA/profiler traces map ops back to expr nodes, and
``utils/profiling.annotate`` exposes ``jax.profiler.TraceAnnotation``
for host ranges inside a ``jax.profiler.trace`` capture.

This module imports only the config layer — never the expr or array
layers — so every subsystem can emit spans without import cycles.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional

from ..utils.config import FLAGS

# define() returns the Flag object; the hot span path reads ._value
# directly (one attribute load) instead of FLAGS.__getattr__'s dict
# walk — FLAGS.trace = x still lands on the same Flag.
_TRACE_FLAG = FLAGS.define_bool(
    "trace", True,
    "Record host-side spans (evaluate/sign/optimize/per-pass/tiling/"
    "compile/dispatch/fetch, uploads, serve requests, garbage "
    "collections) into the in-memory ring buffer for st.trace_export. "
    "A clock pair + deque append per span when on (its end-to-end cost "
    "on the chip, on against off with no profiler capture: PERF.md); "
    "off, nothing is recorded and no gc callback runs.")
_RING_FLAG = FLAGS.define_int(
    "trace_ring", 4096,
    "Maximum spans retained in the in-memory trace ring buffer; older "
    "spans are dropped when it wraps (st.trace_export exports the "
    "surviving window).")


def now() -> float:
    """The tracer clock (seconds, monotonic). All span timestamps and
    the phase timers share it."""
    return time.perf_counter()


_EPOCH = now()  # process trace epoch: span .ts is microseconds since this


def epoch() -> float:
    """The process trace epoch on the tracer clock — lets other obs
    modules (flight recorder) report timestamps on the same axis as
    span ``ts`` values."""
    return _EPOCH


@contextlib.contextmanager
def device_profile(trace_dir: str) -> Iterator[None]:
    """The ONE sanctioned ``jax.profiler.trace`` entry point (lint
    rule 9: raw jax.profiler use outside obs/ escapes the ledger's
    book-keeping of what was measured when). Captures a device profile
    into ``trace_dir`` (view in TensorBoard / Perfetto)."""
    import jax

    with jax.profiler.trace(trace_dir):
        yield


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named host range visible inside a :func:`device_profile`
    capture (``jax.profiler.TraceAnnotation`` — same single-sourcing
    as :func:`device_profile`)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def named_scope(name: str) -> Iterator[None]:
    """Sanctioned trace-time ``jax.named_scope`` wrapper (lint rule 11:
    raw named scopes live only in ``expr/base.py`` — where the
    per-node digest-carrying scopes are emitted — and ``obs/``).
    For a fixed label inside a lowering, e.g. the ``st.loop`` body."""
    import jax

    with jax.named_scope(name):
        yield


class Span:
    """One completed (or in-flight) span. ``ts``/``dur`` are in
    microseconds since the process trace epoch, matching the Chrome
    trace-event ``ts``/``dur`` fields."""

    __slots__ = ("name", "ts", "dur", "tid", "depth", "args", "error",
                 "seconds")

    def __init__(self, name: str, ts: float, tid: int, depth: int):
        self.name = name
        self.ts = ts
        self.dur = 0.0
        self.tid = tid
        self.depth = depth
        self.args: Optional[Dict[str, Any]] = None
        self.error = False
        self.seconds = 0.0

    def set(self, **kw: Any) -> None:
        """Attach key/value annotations (exported under Chrome ``args``)."""
        if self.args is None:
            self.args = {}
        self.args.update(kw)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, ts={self.ts:.1f}us, "
                f"dur={self.dur:.1f}us, tid={self.tid}, "
                f"depth={self.depth}, error={self.error})")


class _NullSpan:
    """Sink yielded when tracing is off: same surface, records nothing."""

    __slots__ = ("seconds",)

    def __init__(self) -> None:
        self.seconds = 0.0

    def set(self, **kw: Any) -> None:
        pass


_NULL = _NullSpan()

_lock = threading.Lock()
_ring: Deque[Span] = deque(maxlen=max(1, FLAGS.trace_ring))
_tls = threading.local()
_tids: Dict[int, int] = {}  # threading ident -> small stable tid
_tid_seq = itertools.count()
# tid -> stack of OPEN spans (entered, not yet exited). The numerics
# watchdog (obs/numerics.py) reads this from its timer thread to dump
# the in-flight span tree of a hung dispatch — the ring only ever sees
# COMPLETED spans, which is exactly the wrong set during a hang.
_open: Dict[int, List[Span]] = {}


def _tid() -> int:
    """A small stable id for the calling thread. Lock-free (only this
    thread inserts its own ident), so the gc callback may call it while
    another frame of the same thread holds ``_lock``."""
    ident = threading.get_ident()
    tid = _tids.get(ident)
    if tid is None:
        tid = _tids.setdefault(ident, next(_tid_seq))
    return tid


def _depth(delta: int) -> int:
    d = getattr(_tls, "depth", 0)
    _tls.depth = d + delta
    return d


def _append(sp: Span) -> None:
    global _ring
    if not _gc_hooked:
        _hook_gc()
    with _lock:
        size = max(1, _RING_FLAG._value)
        if _ring.maxlen != size:
            _ring = deque(_ring, maxlen=size)
        _ring.append(sp)


class SpanCtx:
    """Hand-rolled context manager behind :func:`span` — the hot
    evaluate path enters ~5 of these per dispatch, so no generator
    frames and exactly two clock reads per span. ``.seconds`` on the
    ctx (and on the recorded span) carries the elapsed wall time after
    exit, including when tracing is off."""

    __slots__ = ("name", "init_args", "sp", "t0", "seconds")

    def __init__(self, name: str, args: Optional[Dict[str, Any]]):
        self.name = name
        self.init_args = args
        self.sp: Optional[Span] = None
        self.t0 = 0.0
        self.seconds = 0.0

    def __enter__(self) -> Any:
        self.t0 = now()
        if not _TRACE_FLAG._value:
            return _NULL
        sp = Span(self.name, (self.t0 - _EPOCH) * 1e6, _tid(),
                  _depth(+1))
        if self.init_args:
            sp.args = dict(self.init_args)
        self.sp = sp
        with _lock:
            _open.setdefault(sp.tid, []).append(sp)
        return sp

    def __exit__(self, et, ev, tb) -> bool:
        t1 = now()
        self.seconds = t1 - self.t0
        sp = self.sp
        if sp is None:
            _NULL.seconds = self.seconds
            return False
        if et is not None:
            # a raising block still records its span, marked as failed
            sp.error = True
            sp.set(exc=et.__name__)
        sp.dur = (t1 - _EPOCH) * 1e6 - sp.ts
        sp.seconds = self.seconds
        _depth(-1)
        with _lock:
            stack = _open.get(sp.tid)
            if stack and sp in stack:
                stack.remove(sp)  # usually the top; raise-paths may skip
        _append(sp)
        return False


def span(name: str, **args: Any) -> SpanCtx:
    """Record a nested span around the enclosed block.

    The yielded object supports ``.set(key=value)`` for annotations
    added mid-flight (e.g. plan-cache hit/miss once known). A raising
    block still records the span, marked ``error=True`` with the
    exception type under ``args["exc"]`` — failed evaluates stay
    visible in traces. ``.seconds`` carries the elapsed wall time
    after exit (also set when tracing is off, for callers that only
    want the measurement)."""
    return SpanCtx(name, args or None)


def events() -> List[Span]:
    """Snapshot of the ring buffer, oldest first (completion order)."""
    with _lock:
        return list(_ring)


def inflight() -> List[Dict[str, Any]]:
    """Snapshot of the OPEN spans, per thread, outermost first — the
    span tree a hung dispatch is stuck inside. Each entry carries the
    elapsed wall time so far (``elapsed_s``); the numerics watchdog
    serializes this into the crash dump."""
    t = now()
    out: List[Dict[str, Any]] = []
    with _lock:
        for tid, stack in sorted(_open.items()):
            for sp in stack:
                out.append({
                    "name": sp.name, "tid": tid, "depth": sp.depth,
                    "ts_us": sp.ts,
                    "elapsed_s": round(t - _EPOCH - sp.ts / 1e6, 6),
                    "args": dict(sp.args) if sp.args else {},
                })
    return out


def instant(name: str, error: bool = False, **args: Any) -> None:
    """Record a zero-duration marker span (health words, watchpoint
    checks). No-op when tracing is off."""
    if not _TRACE_FLAG._value:
        return
    sp = Span(name, (now() - _EPOCH) * 1e6, _tid(), 0)
    sp.error = error
    if args:
        sp.args = dict(args)
    _append(sp)


def _finished(name: str, t0: float, t1: float,
              args: Optional[Dict[str, Any]]) -> Span:
    sp = Span(name, (t0 - _EPOCH) * 1e6, _tid(), 0)
    sp.dur = (t1 - t0) * 1e6
    sp.seconds = t1 - t0
    sp.args = args or None
    return sp


def record(name: str, t0: float, t1: float, **args: Any) -> None:
    """Append a finished span with explicit edges ``t0``..``t1`` on the
    tracer clock (:func:`now`), stamped earlier by the caller: the
    serve engine builds its request spans from the stamps it already
    takes. One flag read when tracing is off."""
    if not _TRACE_FLAG._value:
        return
    _append(_finished(name, t0, t1, args))


# -- garbage collections ---------------------------------------------------
#
# One "gc" span per collection, from gc.callbacks. The callback is
# registered by the first span recorded with tracing on and takes
# itself out at the first collection it sees with tracing off, so an
# untraced process runs no callback per collection. It never takes
# _lock: a collection can start inside any allocation, also while this
# thread holds _lock (in _append or SpanCtx.__enter__), and the lock is
# not re-entrant. deque.append is atomic under the GIL.

_gc_lock = threading.Lock()
_gc_hooked = False
_gc_t0 = 0.0  # collections never overlap: one start stamp suffices


def _hook_gc() -> None:
    global _gc_hooked
    with _gc_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        _gc_hooked = True


def _unhook_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_hooked
    cbs = gc.callbacks
    if _on_gc not in cbs:
        return
    i = cbs.index(_on_gc)
    del cbs[i]
    _gc_hooked = False
    # CPython walks gc.callbacks by index over the live list: the
    # callback that moved into this slot would miss this phase
    if i < len(cbs):
        cbs[i](phase, info)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0
    if not _TRACE_FLAG._value:
        _unhook_gc(phase, info)
        return
    if phase == "start":
        _gc_t0 = now()
        return
    t0, _gc_t0 = _gc_t0, 0.0
    if t0:  # 0: hooked while this collection ran
        _ring.append(_finished("gc", t0, now(),
                               {"generation": info["generation"],
                                "collected": info["collected"]}))


def clear() -> None:
    with _lock:
        _ring.clear()
    _loop_prev.clear()


def export(path: Optional[str] = None, clear_after: bool = False) -> Dict:
    """Export the span ring as a Chrome trace-event JSON document
    (Perfetto / chrome://tracing loadable).

    Every span becomes one complete ('ph': 'X') event with ``ts`` /
    ``dur`` in microseconds; nesting is implicit from containment on
    the same ``tid``. Returns the document; also writes it to ``path``
    when given."""
    pid = os.getpid()
    evts = []
    for sp in sorted(events(), key=lambda s: (s.tid, s.ts, -s.dur)):
        args: Dict[str, Any] = {"depth": sp.depth}
        if sp.error:
            args["error"] = True
        if sp.args:
            args.update(sp.args)
        evts.append({
            "name": sp.name,
            "ph": "X",
            "ts": sp.ts,
            "dur": sp.dur,
            "pid": pid,
            "tid": sp.tid,
            "args": args,
        })
    doc = {"traceEvents": evts, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
        from ..utils.log import log_info  # lazy: log-free at import

        log_info(
            "trace: %d span(s) written to %s (load at "
            "https://ui.perfetto.dev)", len(evts), path)
    if clear_after:
        clear()
    return doc


# -- st.loop per-iteration visibility ------------------------------------
#
# A LoopExpr runs ALL its iterations inside one fori_loop dispatch, so
# host spans see one opaque blob. With FLAGS.trace_loop_steps the loop
# body emits a jax.debug.callback per iteration; arrival times on the
# host become consecutive "loop_step" spans carrying the step index —
# real per-step dispatch time, not an even split. (expr/loop.py wires
# the callback; the flag participates in the loop's structural
# signature so toggling it recompiles instead of reusing a
# callback-free executable.)

_loop_prev: Dict[str, float] = {}


def loop_steps_begin(label: str) -> None:
    """Anchor step 0 of ``label`` at the dispatch start."""
    with _lock:
        _loop_prev[label] = now()


def record_loop_step(label: str, step: Any) -> None:
    """Host callback target: close a span covering [previous mark, now]
    for iteration ``step`` of the loop ``label``."""
    if not _TRACE_FLAG._value:
        return
    t1 = now()
    with _lock:
        t0 = _loop_prev.get(label, t1)
        _loop_prev[label] = t1
    record("loop_step", t0, t1, loop=label, step=int(step))
