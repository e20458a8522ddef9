"""Signature-level request coalescing: many clients, one program.

Requests whose one-traversal RAW-DAG signature matches (the plan-cache
key, ``expr/base.plan_signature``) within the batching window share one
cached plan and are batched along a NEW LEADING CLIENT AXIS at the
leaves — the DrJAX vmap-over-clients construction: one compile, one
dispatch, N responses. Two batching modes:

* ``vmap`` (default) — the plan's traced function is ``jax.vmap``-ed
  over the stacked leaves; XLA sees one batched program (elementwise
  chains become one wider kernel, matmuls one batched contraction) and
  GSPMD shards the per-client program exactly as the solo plan did.
* ``unroll`` — the traced function is replayed per client inside ONE
  jitted program (bit-identical to solo by construction). The
  automatic fallback when a plan's lowering cannot be vmapped (e.g. a
  ``shard_map`` kernel without a batching rule): a DETERMINISTIC
  failure of the vmap variant demotes the plan to ``unroll``, and a
  second deterministic failure disables coalescing for that plan.

Either way the batch is split back into per-client outputs INSIDE the
jitted program, so one dispatch produces N separate result buffers and
no per-client slice dispatches are paid on the host.

The batch size and mode are keyed into the compile cache
(``plan.key + ('serve', B, mode)``) so coalesced and solo executables
never collide, and the batch is recorded on the plan report — a
cache-hit ``st.explain`` names the coalesced batch.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..expr import base
from ..obs import numerics as numerics_mod
from ..obs.explain import key_hash
from ..obs.metrics import METRICS_FLAG as _METRICS_FLAG
from ..obs.metrics import REGISTRY
from ..resilience import classify as cls
from ..resilience import faults as faults_mod
from ..utils import profiling as prof
from ..utils.config import FLAGS

FLAGS.define_str(
    "serve_coalesce_mode", "vmap",
    "Leading-axis batching mode for coalesced requests: 'vmap' (one "
    "batched program; auto-demotes per plan to 'unroll' on a "
    "deterministic vmap failure) or 'unroll' (the traced function "
    "replayed per client inside one jitted program; bit-identical to "
    "solo by construction).")

# per-plan mode overrides learned from deterministic batch failures:
# plan.key -> 'unroll' | 'off'. Guarded by its own lock; never held
# while compiling or dispatching.
_mode_lock = threading.Lock()
_mode_override: Dict[Tuple, str] = {}


def reset_modes() -> None:
    """Forget learned per-plan demotions (test isolation)."""
    with _mode_lock:
        _mode_override.clear()


def mode_for(plan: Any) -> str:
    """'vmap' / 'unroll' / 'off' for this plan."""
    with _mode_lock:
        override = _mode_override.get(plan.key)
    if override is not None:
        return override
    mode = FLAGS.serve_coalesce_mode
    return mode if mode in ("vmap", "unroll") else "vmap"


def demote(plan: Any) -> str:
    """Walk the plan one rung down after a deterministic batched
    failure: vmap -> unroll -> off. Returns the new mode."""
    with _mode_lock:
        cur = _mode_override.get(plan.key)
        if cur is None and FLAGS.serve_coalesce_mode == "unroll":
            cur = "unroll"
        new = "unroll" if cur is None else "off"
        _mode_override[plan.key] = new
    return new


def _make_batched(traced: Callable, B: int, nargs: int, mode: str,
                  shared: Tuple[bool, ...]) -> Callable:
    """The batched traced function. ``shared[j]`` marks an argument
    position where every request passes the IDENTICAL buffer (common:
    requests over the same model/dataset arrays differing only in
    per-request inputs); those are passed ONCE and vmapped with
    ``in_axes=None`` — the flat argument list is position-major, one
    entry for a shared position, B entries otherwise. Deduplication is
    the difference between a batched call whose host-side argument
    processing costs B× the solo call (measured: jit-call overhead is
    linear in argument count) and one that amortizes; it also stops
    the program physically broadcasting a shared leaf into a B-times
    larger device buffer every dispatch. Returns a B-tuple of
    per-request outputs — the split happens inside the program, so the
    host sees N result buffers from one dispatch."""

    def columns(flat: Any) -> List[Any]:
        cols: List[Any] = []
        i = 0
        for j in range(nargs):
            if shared[j]:
                cols.append(flat[i])
                i += 1
            else:
                cols.append(list(flat[i:i + B]))
                i += B
        return cols

    if mode == "vmap":

        def batched(*flat: Any) -> Tuple[Any, ...]:
            cols = columns(flat)
            if all(shared):
                # degenerate batch: every request is the same
                # computation — run it once, share the result buffers
                outs = traced(*cols)
                return (outs,) * B
            in_axes = tuple(None if s else 0 for s in shared)
            stacked = [c if s else jnp.stack(c)
                       for s, c in zip(shared, cols)]
            outs = jax.vmap(traced, in_axes=in_axes)(*stacked)
            return tuple(
                jax.tree_util.tree_map(lambda o, i=i: o[i], outs)
                for i in range(B))

        return batched

    def unrolled(*flat: Any) -> Tuple[Any, ...]:
        cols = columns(flat)
        return tuple(
            traced(*[c if s else c[i]
                     for s, c in zip(shared, cols)])
            for i in range(B))

    return unrolled


def dispatch_batch(plan: Any, requests: List[Any], mesh,
                   span: int = 0) -> List[Any]:
    """One coalesced dispatch for ``requests`` (all sharing
    ``plan``'s signature): gather each request's leaves, run the
    batched executable, wrap each request's outputs and seed its
    expr's result cache. ``span`` is the flight recorder's dispatch
    id, carried by the ``serve_batch`` span. Raises on failure — the
    engine falls back to solo dispatches (where the resilience policy
    engine handles classification, per-tenant budgets and retries)."""
    B = len(requests)
    order = plan.arg_order
    nargs = len(order)
    mode = mode_for(plan)
    if mode == "off":
        raise RuntimeError("coalescing disabled for this plan")

    with prof.phase("build"):
        per_req: List[List[Any]] = []
        for r in requests:
            args, _darrs, dpos = base._gather_args(r.leaves, order, [])
            if dpos:  # engine routing bug: donating requests are solo
                raise RuntimeError(
                    "donating request reached the coalescer")
            per_req.append(args)
        first = per_req[0]
        shared = tuple(
            all(a[j] is first[j] for a in per_req[1:])
            for j in range(nargs))
        flat: List[Any] = []
        for j in range(nargs):
            if shared[j]:
                flat.append(first[j])
            else:
                flat.extend(a[j] for a in per_req)

    # the dedup pattern is part of the executable: a batch where a
    # position stops being shared compiles (and caches) its own variant
    ex = base.cached_executable(
        plan.key + ("serve", B, mode, shared),
        lambda: jax.jit(
            _make_batched(plan.traced, B, nargs, mode, shared)))

    fresh = not ex.warm
    phase_name = "compile" if fresh else "dispatch"
    with prof.span("serve_batch", batch=B, mode=mode,
                   plan=key_hash(plan.key), span=span):
        with prof.phase(phase_name):
            # same watchdog + chaos seams as expr/base._dispatch: a
            # hung batched dispatch dumps in-flight forensics, and an
            # installed chaos plan injects BEFORE the executable runs
            with numerics_mod.watchdog(phase_name, plan.report):
                if faults_mod._ACTIVE is not None:
                    faults_mod.fire(phase_name)
                # same launch serialization as base._dispatch: XLA:CPU
                # collectives deadlock under concurrent launches
                with base.launch_guard():
                    outs = ex.jitted(*flat)
    ex.warm = True

    with prof.phase("build"):  # ONE timed phase for the whole batch
        results = [base._wrap_result(r.expr, plan, o, [], [], mesh,
                                     timed=False)
                   for r, o in zip(requests, outs)]

    # metrics + plan-report annotation: coalesced requests count as
    # plan hits (the plan WAS reused) so hit-rate views stay honest
    prof.count("evaluations", B)
    prof.count("plan_hits", B)
    if _METRICS_FLAG._value:
        REGISTRY.counter(
            "serve_coalesced_requests",
            "requests served through a coalesced batch").inc(B)
        REGISTRY.counter(
            "serve_coalesced_batches",
            "coalesced batched dispatches").inc()
    if plan.report is not None:
        sv = plan.report.setdefault(
            "serve", {"batches": 0, "requests": 0, "last_batch": None,
                      "mode": mode})
        sv["batches"] += 1
        sv["requests"] += B
        sv["last_batch"] = B
        sv["mode"] = mode
    return results


def classify_batch_failure(exc: BaseException, plan: Any) -> str:
    """Engine hook after a failed batched dispatch: deterministic
    failures demote the plan's batching mode (a vmap that cannot trace
    will never trace); transient/oom/io leave the mode alone — the
    solo fallback's resilience engine owns those."""
    kind = cls.classify(exc)
    if kind == cls.DETERMINISTIC:
        new = demote(plan)
        if _METRICS_FLAG._value:
            REGISTRY.counter(
                "serve_mode_demotions",
                "plans demoted vmap->unroll->off after deterministic "
                "batched failures").inc()
        return new
    return mode_for(plan)
