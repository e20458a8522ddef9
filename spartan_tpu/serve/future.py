"""Request futures + the serving error vocabulary.

An :class:`EvalFuture` is the handle ``evaluate_async`` returns: the
submitting thread gets it immediately, a serve worker resolves it after
the (possibly coalesced) dispatch. Resolution happens at dispatch
completion — JAX execution is asynchronous, so the resolved
``DistArray`` is an in-flight device handle and only a fetch
(``.glom()``) blocks on the actual computation; donated input buffers
are invalidated at the same resolution point (the serving analogue of
``evaluate()``'s dispatch epilogue).

Thread-safety: one ``threading.Event`` per future; ``_resolve`` /
``_reject`` are called exactly once by the owning worker (double
resolution is ignored, first writer wins), callbacks run on the
resolving thread.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional

from ..obs import flight as flight_mod
from ..obs import trace as trace_mod


class ServeError(RuntimeError):
    """Base class for serving-layer failures."""


class Backpressure(ServeError):
    """Admission control rejected the request: the submission queue is
    past its high-water mark. ``retry_after_s`` is the engine's
    estimate of when capacity frees up (queue depth x recent service
    time per worker) — the reject-with-retry-after contract clients
    are expected to honor instead of hammering the queue."""

    def __init__(self, depth: int, retry_after_s: float):
        super().__init__(
            f"serve queue full ({depth} request(s) queued); "
            f"retry after ~{retry_after_s:.3f}s")
        self.depth = depth
        self.retry_after_s = retry_after_s


class DeadlineExceeded(ServeError):
    """The request's deadline expired before its dispatch started (it
    was shed from the queue) or before its result resolved."""


class CommBudgetExceeded(ServeError):
    """Admission control rejected the request because its plan's
    audited communication total (analysis/plan_audit.py, cached on the
    plan report) exceeds ``FLAGS.comm_budget_bytes``. NOT retryable —
    resubmitting the same expression meets the same plan; restructure
    the computation (or raise the budget). The finding lands in the
    flight record (``st.flightrec``) with the modeled bytes."""

    def __init__(self, comm_bytes: float, budget_bytes: int,
                 detail: str = ""):
        super().__init__(
            f"plan's modeled communication ~{comm_bytes:.0f} bytes/chip "
            f"exceeds FLAGS.comm_budget_bytes={budget_bytes}"
            + (f" ({detail})" if detail else ""))
        self.comm_bytes = comm_bytes
        self.budget_bytes = budget_bytes


class MeshReconfiguring(ServeError):
    """The mesh is being rebuilt after persistent device/host loss
    (elastic recovery): this request was drained, or arrived during
    the drain, and was NOT dispatched. Retryable — resubmit after
    ``retry_after_s``; the rebuild is host-side work, so the engine is
    admitting again almost immediately, with plans re-built for the
    surviving devices. Inputs that lived on the dead mesh must be
    re-created (or ``.rehome()``d) before resubmitting — a stale
    resubmission fails with ``StaleMeshError`` naming them."""

    def __init__(self, retry_after_s: float, detail: str = ""):
        super().__init__(
            "mesh reconfiguring after device loss; retry after "
            f"~{retry_after_s:.3f}s" + (f" ({detail})" if detail else ""))
        self.retry_after_s = retry_after_s


class EvalFuture:
    """Resolution handle for one submitted evaluation.

    ``result(timeout)`` blocks until the worker resolves the future and
    returns the ``DistArray`` (or tuple, for ``TupleExpr`` roots) — or
    raises the failure the evaluation produced (after the resilience
    engine's retries ran their course). ``glom(timeout)`` additionally
    fetches to the host, which is where asynchronous device execution
    is actually awaited."""

    __slots__ = ("_event", "_result", "_exc", "_callbacks", "_lock",
                 "tenant", "coalesced", "t_submit", "t_resolved", "rid",
                 "dispatch_span", "_woken")

    def __init__(self, tenant: Optional[str] = None):
        self._event = threading.Event()
        self._result: Any = None
        self._exc: Optional[BaseException] = None
        self._callbacks: List[Callable[["EvalFuture"], None]] = []
        self._lock = threading.Lock()
        self.tenant = tenant
        # set by the worker: how many requests shared this dispatch
        # (1 = solo); observability for tests and clients
        self.coalesced = 0
        # engine-stamped tracer-clock timestamps (obs.trace.now):
        # t_resolved - t_submit is the request's serving latency
        self.t_submit: float = 0.0
        self.t_resolved: float = 0.0
        # flight-recorder request id (obs/flight.py), minted at submit
        # and shared with every event of this request's lifecycle;
        # 0 = not a recorded request (bare futures)
        self.rid: int = 0
        # the flight recorder's id of the dispatch that resolved it
        self.dispatch_span: int = 0
        self._woken = False  # serve_wake recorded (first return only)

    # -- caller side ----------------------------------------------------

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"EvalFuture.result timed out after {timeout}s")
        if self._exc is not None:
            raise self._exc
        if trace_mod._TRACE_FLAG._value and self.rid and not self._woken:
            # the serve_wake span: resolution on the worker -> this
            # caller running again
            self._woken = True
            trace_mod.record("serve_wake", self.t_resolved,
                             trace_mod.now(), rid=self.rid,
                             span=self.dispatch_span)
        return self._result

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"EvalFuture.exception timed out after {timeout}s")
        return self._exc

    def glom(self, timeout: Optional[float] = None) -> Any:
        """Resolve AND fetch: the one call that blocks on device
        execution (``result()`` returns an async array handle). The
        wall time of its one ``fetch`` span, a tuple result's too, is
        the last hop of this request's flight record (per-tenant
        ``serve_fetch_s`` histogram)."""
        from ..array.distarray import fetch_to_host

        out = self.result(timeout)
        host, seconds = fetch_to_host(
            tuple(o.jax_array for o in out) if isinstance(out, tuple)
            else out.jax_array)
        flight_mod.note_fetch(self.rid, self.tenant, seconds)
        return host

    def add_done_callback(self, fn: Callable[["EvalFuture"], None]
                          ) -> None:
        """Run ``fn(self)`` when the future resolves (immediately if it
        already has). Runs on the resolving worker thread; exceptions
        from callbacks are swallowed (a client callback must not kill
        a worker)."""
        run_now = False
        with self._lock:
            if self._event.is_set():
                run_now = True
            else:
                self._callbacks.append(fn)
        if run_now:
            try:
                fn(self)
            except Exception:
                pass

    # -- worker side ----------------------------------------------------

    def _fire_callbacks(self) -> None:
        with self._lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            try:
                fn(self)
            except Exception:
                pass  # client callbacks must not kill the worker

    def _stamp(self) -> None:
        self.t_resolved = trace_mod.now()

    def _resolve(self, result: Any) -> None:
        with self._lock:
            if self._event.is_set():
                return
            self._result = result
            self._stamp()
            self._event.set()
        self._fire_callbacks()

    def _reject(self, exc: BaseException) -> None:
        with self._lock:
            if self._event.is_set():
                return
            self._exc = exc
            self._stamp()
            self._event.set()
        self._fire_callbacks()
