"""AST-based custom lint for the spartan_tpu codebase itself.

Eighteen repo-specific rules that generic linters cannot know:

1. ``shard_map`` comes from ``jax`` itself (``from jax import
   shard_map``): ``jax.experimental.shard_map`` is the pre-0.9
   location, with the old ``check_rep`` spelling, and the installed
   JAX is the only one the code supports.

2. Every concrete ``Expr`` subclass must provide ``_sig`` and
   ``replace_children`` somewhere below the ``Expr`` base — a subclass
   relying on the base's ``NotImplementedError`` stubs silently breaks
   the structural compile/plan caches and the optimizer rewrite
   machinery the moment such a node lands in a DAG.

3. No raw wall-clock timing (``time.perf_counter()`` and friends)
   outside ``spartan_tpu/obs/`` and ``spartan_tpu/utils/profiling.py``
   (the observability PR): ALL in-package timing must ride the
   span/phase/stopwatch API so every measured interval lands in the
   trace ring and the metrics registry — a raw clock pair is
   invisible to ``st.trace_export``/``st.metrics`` and silently
   escapes the trace.

4. No raw ``jax.debug.callback`` / ``jax.debug.print`` outside
   ``spartan_tpu/obs/`` and ``spartan_tpu/expr/loop.py`` (the
   numerics-sentinel PR): ALL device->host telemetry must flow
   through the sentinel API (``obs/numerics.probe`` /
   ``guard_finite`` / ``record_loop_health``, ``obs/trace``'s
   loop-step marks) so it is session-collected, metrics-fed and
   trace-visible — a raw callback is invisible to ``st.audit`` and
   the crash-dump machinery, and its host cost escapes every
   overhead gate.

5. No broad exception handling (bare ``except:``, ``except
   Exception``, ``except RuntimeError``) around compile/dispatch
   calls (``evaluate`` / ``force`` / ``recompute`` / ``_dispatch`` /
   ``jit``) outside ``spartan_tpu/resilience/`` (the resilient-
   execution PR): ad-hoc catch-and-retry around the dispatch path is
   exactly the blind-retry bug class the classifier + policy engine
   replaced — it retries deterministic errors, bypasses the per-plan
   retry budget, and its failures are invisible to the
   ``resilience_*`` metrics and crash-dump forensics. The TWO
   sanctioned shapes outside ``resilience/`` are a handler that routes
   straight into the engine (calls ``handle_failure``) — how
   ``expr/base.evaluate`` wires the boundary — and a handler that
   hands the classified, already-retried failure to its caller
   through a serve future (calls ``_reject`` / ``set_exception``) —
   how ``serve/engine`` wires the worker boundary. Neither retries.

6. No direct access to the shared evaluation caches
   (``_plan_cache`` / ``_compile_cache`` / ``_cache_lock``) outside
   ``spartan_tpu/expr/base.py``, and none to the metrics registry's
   internal tables (``_counters`` / ``_gauges`` / ``_hists``) outside
   ``spartan_tpu/obs/metrics.py`` (the concurrent-serving PR): these
   are hot SHARED state with a documented locking discipline, and a
   bare dict poke from another module bypasses the lock, the LRU
   recency order and the eviction accounting. Go through the
   accessors (``lookup_plan`` / ``store_plan`` / ``cached_executable``
   / ``clear_*``; ``REGISTRY.counter()/gauge()/histogram()``).

7. No mesh object stored in module globals or class attributes
   outside ``spartan_tpu/parallel/`` (the elastic-recovery PR): a
   ``get_mesh()``/``build_mesh()``/``Mesh(...)`` result captured in a
   long-lived global outlives a ``rebuild_mesh`` — after device loss
   the mesh epoch advances and every cached mesh (and any sharding
   derived from it) points at dead devices, invisible to the
   epoch fence that protects ``get_mesh()`` callers. Flagged shapes:
   module-level and class-body assignments whose value calls one of
   those constructors, and function-body assignments to names
   declared ``global``. Instance attributes (a DistArray's birth
   mesh) are fine — they carry the birth EPOCH alongside, and
   cross-epoch use raises ``StaleMeshError``.

8. No direct ``.memory_stats()`` calls outside ``obs/metrics.py``,
   ``parallel/mesh.py`` and ``resilience/memory.py`` (the memory-
   governor PR): the HBM-budget auto-detect and every exported memory
   gauge must agree on ONE aggregated read-out across all local
   devices — a stray per-device read reintroduces the
   only-device-0 blind spot the governor PR fixed, and its numbers
   silently disagree with ``FLAGS.hbm_budget_bytes`` auto-detection
   and the ``device_*`` gauges. Go through
   ``obs.metrics.device_memory_aggregate()``.

9. No raw ``jax.profiler`` use outside ``obs/trace.py`` and
   ``obs/profile.py`` (tightened by the device-time attribution PR:
   the tracer owns the capture seam, the profiler is the ONE new
   sanctioned consumer), and no direct ``.cost_analysis()`` /
   ``.memory_analysis()`` calls outside ``obs/explain.py`` and
   ``resilience/memory.py`` (the cost-ledger PR): every device-time
   measurement and compiled-program introspection must flow through
   the sanctioned entry points (``obs.trace.device_profile`` /
   ``.annotate``, ``obs.profile.profile``,
   ``obs.explain.compiled_cost_analysis``,
   ``resilience.memory.validate_plan``) so the reading lands in the
   cost ledger next to the model's prediction — a stray profiler
   capture or cost read-out produces numbers the calibration loop
   never sees and cannot be compared against the committed gates.

10. No raw ``jax.lax.with_sharding_constraint`` outside
    ``parallel/redistribute.py`` and ``expr/base.py`` (the
    redistribution-planner PR): every sharding-constraint call site is
    a reshard edge the cost-modeled planner must see — a raw
    constraint is invisible to the planner (its edge is never priced,
    never eligible for the explicit collective lowering, and absent
    from ``st.explain``'s schedule report). Go through
    ``parallel.redistribute.constrain()`` (pass ``src=`` when the
    producing layout is known so the edge is plannable); the two
    allowed files are the planner itself and the ``Expr.lower`` /
    jit-output seam that defines the fallback.

11. No raw ``jax.named_scope`` outside ``expr/base.py`` and ``obs/``
    (the device-time attribution PR): the per-node scopes
    ``Expr.lower`` emits carry the structural-signature digest the
    profiler's trace-parse tier JOINS on (``obs/profile.scope_name``),
    and ``obs.trace.named_scope`` is the sanctioned wrapper for fixed
    labels — a raw scope elsewhere invents names the attribution
    report can never map back to an expr node.

12. No ``jax.experimental.pallas`` import (or ``pallas_call`` use)
    outside ``spartan_tpu/kernels/``: every Pallas kernel goes
    through the kernel layer so its grid derives from the committed
    tiling and its backend follows the platform (docs/KERNELS.md).

13. No JAX AOT executable-serialization use
    (``jax.experimental.serialize_executable`` — ``serialize`` /
    ``deserialize_and_load``) and no direct ``FLAGS.persist_cache_dir``
    reads outside ``spartan_tpu/persist/`` (the warm-start PR): the
    store owns the fingerprint rule, the CRC/atomic-write discipline,
    the lease-writer protocol and the degrade-to-recompile contract
    (docs/WARMSTART.md) — a stray serialize call produces bytes no
    fingerprint protects, and a stray dir read bypasses the store
    singleton's failure handling. Go through ``spartan_tpu.persist``
    (``active()`` / ``lookup()`` / ``maybe_store()`` / ``prewarm()``).

14. No stores to a DistArray's private buffer/lineage state
    (``._jax`` / ``._lineage`` / ``._version``) outside
    ``spartan_tpu/array/`` and the incremental seam
    (``spartan_tpu/expr/incremental.py``) — the delta-aware PR: the
    incremental result cache trusts the Lineage mutation log as the
    ONLY way data changes under a stable leaf identity
    (docs/INCREMENTAL.md); a stray buffer poke makes a dirty tile
    look clean and the cache serves stale results, bit-INequal to a
    recompute. Mutate through ``DistArray.update()`` / ``st.assign``.

15. No ``lax.dynamic_slice`` / ``lax.dynamic_update_slice`` outside
    the incremental seam (``spartan_tpu/expr/incremental.py``) — the
    plan-auditor PR: with traced start indices GSPMD cannot prove the
    slice stays inside one shard, so it ALL-GATHERS the full sharded
    operand onto every chip before slicing — the pathological
    communication class ``st.audit_plan`` exists to flag
    (analysis/plan_audit.py, finding kind ``full_gather``). The
    incremental engine's stash path is the ONE sanctioned
    construction site: it pays the gather knowingly, on the
    delta-sized stash, never the full operand (docs/INCREMENTAL.md).
    The static-bound forms (``dynamic_slice_in_dim`` on unsharded
    axes, ``lax.slice``) are fine and not flagged.

16. No background-thread construction (``threading.Thread`` /
    ``threading.Timer``) outside the three sanctioned concurrency
    seams — ``spartan_tpu/serve/`` (the worker pool),
    ``spartan_tpu/resilience/`` (recovery drills), and the named
    daemon files ``obs/monitor.py`` (the sampler),
    ``obs/numerics.py`` (the dispatch watchdog) and
    ``persist/__init__.py`` (store prewarm) — the closed-loop
    telemetry PR: every long-lived thread must be one the monitor's
    epoch fence, the serve drain barrier and the crash-dump span
    tree know about. A stray thread elsewhere dodges the mesh-epoch
    fence (it can dispatch on a dead-device mesh after
    ``rebuild_mesh``), never appears in ``st.status()``'s health
    section, and leaks past ``shutdown()``. Locks / Events /
    Conditions are fine everywhere — the rule is about threads of
    execution, not synchronization primitives.

17. No raw ``addressable_shards`` iteration outside the shard-walk
    seam (``obs/skew.local_shards`` / ``per_shard_stats``), the array
    layer that owns the buffers, and the checkpoint serializer — the
    skew-observatory PR: every per-tile read-out must agree on device
    labels, index formatting and host-fetch behavior, or straggler
    attribution, tile health and checkpoints disagree about which
    shard is which.

18. No per-shard checksum walks or shard-buffer bit surgery
    (``shard_checksums`` / ``flip_bit``) outside the integrity seam —
    ``resilience/integrity.py`` (the SDC sentinel that owns both) and
    ``resilience/faults.py`` (the chaos injector that delegates its
    ``sdc`` corruption to it) — the SDC-sentinel PR: a checksum
    computed elsewhere drifts on shard ordering and byte layout, so
    its verdicts stop matching the sentinel's detect/attribute
    pipeline, and a buffer flip outside the seam is silent data
    corruption the sentinel cannot distinguish from the real thing.

Run stand-alone (``python tools/lint_repo.py``; exit 1 on findings;
``--json`` emits the findings as a JSON array for CI tooling) or as a
module (``python -m tools.lint_repo``) or through the tier-1 suite
(tests/test_lint_repo.py).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "spartan_tpu")

# abstract Expr layers that intentionally leave the hooks to subclasses
_ABSTRACT_EXPRS = {"Expr"}

# the only places allowed to read the raw wall clock (rule 3): the
# observability layer itself and the profiling facade over it
_TIMING_ALLOWED_DIRS = (os.path.join("spartan_tpu", "obs") + os.sep,)
_TIMING_ALLOWED_FILES = {os.path.join("spartan_tpu", "utils",
                                      "profiling.py")}
_CLOCK_FNS = {"perf_counter", "perf_counter_ns", "monotonic",
              "monotonic_ns"}

# the only places allowed to emit raw device->host debug callbacks
# (rule 4): the sentinel/tracer themselves, and the loop lowering that
# wires the per-iteration marks into them
_DEBUG_CB_ALLOWED_DIRS = (os.path.join("spartan_tpu", "obs") + os.sep,)
_DEBUG_CB_ALLOWED_FILES = {os.path.join("spartan_tpu", "expr",
                                        "loop.py")}
_DEBUG_CB_FNS = {"callback", "print"}

# rule 5: the only place allowed to catch broadly around the
# compile/dispatch path is the resilience subsystem itself
_RECOVERY_ALLOWED_DIRS = (os.path.join("spartan_tpu", "resilience")
                          + os.sep,)
_BROAD_HANDLERS = {"Exception", "BaseException", "RuntimeError"}
_DISPATCH_CALLS = {"evaluate", "force", "recompute", "_dispatch", "jit"}
# a handler that immediately routes into the policy engine
# (expr/base.evaluate) or hands the terminal failure to the caller
# through a serve future (serve/engine workers) is a sanctioned
# boundary shape — neither retries
_ENGINE_ROUTES = {"handle_failure", "_handle_failure",
                  "_reject", "set_exception",
                  # the incremental engine's honest-fallback seam
                  # (expr/incremental.py): the handler records the
                  # reason and returns NOT_HANDLED so the ordinary
                  # full dispatch runs — whose failures DO route
                  # through the policy engine. It never retries.
                  "degrade_to_full"}

# rule 6: owners of the hot shared state; everyone else goes through
# the accessors so locking/LRU/eviction stay in one place
_CACHE_NAMES = {"_plan_cache", "_compile_cache", "_cache_lock"}
_CACHE_OWNER = os.path.join("spartan_tpu", "expr", "base.py")
_REGISTRY_INTERNALS = {"_counters", "_gauges", "_hists"}
_METRICS_OWNER = os.path.join("spartan_tpu", "obs", "metrics.py")

# rule 8: the only modules allowed to read device memory_stats
# directly — budget auto-detect and memory gauges stay single-sourced
_MEMSTATS_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "obs", "metrics.py"),
    os.path.join("spartan_tpu", "parallel", "mesh.py"),
    os.path.join("spartan_tpu", "resilience", "memory.py"),
}

# rule 9: device-time instrumentation single-sourcing, per entry
# point. Raw jax.profiler use lives in the tracer's capture seam plus
# the attribution profiler (its ONE sanctioned new consumer); compiled
# cost/memory introspection lives with explain's normalizer and the
# memory governor's validate_plan — so every reading can land in the
# cost ledger
_PROFILER_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "obs", "trace.py"),
    os.path.join("spartan_tpu", "obs", "profile.py"),
}
_ANALYSIS_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "obs", "explain.py"),
    os.path.join("spartan_tpu", "resilience", "memory.py"),
}
_ANALYSIS_CALLS = {"cost_analysis", "memory_analysis"}

# rule 11: raw jax.named_scope sites — the digest-carrying per-node
# scopes (expr/base.Expr.lower via obs/profile.scope_name) and the
# obs layer's own wrapper; everyone else goes through
# obs.trace.named_scope so scope names stay joinable by the profiler
_NAMED_SCOPE_ALLOWED_DIRS = (os.path.join("spartan_tpu", "obs")
                             + os.sep,)
_NAMED_SCOPE_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "expr", "base.py"),
}

# rule 10: the only places allowed to call with_sharding_constraint
# directly — the redistribution planner (which decides explicit
# schedule vs GSPMD fallback per edge) and the expr/base lowering seam
# that routes through it
_WSC_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "parallel", "redistribute.py"),
    os.path.join("spartan_tpu", "expr", "base.py"),
}

# rule 7: mesh constructors whose results must not live in module
# globals / class attributes outside the owning package — a captured
# mesh outlives rebuild_mesh and dodges the epoch fence
_MESH_MAKERS = {"get_mesh", "build_mesh", "rebuild_mesh", "Mesh"}
_MESH_ALLOWED_DIRS = (os.path.join("spartan_tpu", "parallel") + os.sep,)

# rule 13: the warm-start store (spartan_tpu/persist) is the only
# owner of JAX AOT executable serialization and of the persist
# directory itself — everyone else goes through the persist API so
# fingerprints, CRCs, leases and degrade-to-recompile stay in one
# place
_PERSIST_ALLOWED_DIRS = (os.path.join("spartan_tpu", "persist")
                         + os.sep,)
_PERSIST_SERIALIZE_NAMES = {"serialize_executable",
                            "deserialize_and_load"}

# rule 12: Pallas is the kernel layer's private dependency. A raw
# pallas_call outside spartan_tpu/kernels/ bypasses the selection
# policy (kernels.registry.select), the tiling->grid derivation and
# interpret mode off the chip (docs/KERNELS.md) — exactly the
# single-device dead ends the seed's ops/kmeans.py and ops/segment.py
# kernels were.
_PALLAS_ALLOWED_DIRS = (os.path.join("spartan_tpu", "kernels")
                        + os.sep,)

# rule 14: a DistArray's buffer/lineage state (_jax, _lineage,
# _version) is the incremental engine's ground truth — a write from
# anywhere but the array layer or the incremental seam silently
# detaches the mutation log from the data, and the result cache then
# serves stale tiles as "clean" (docs/INCREMENTAL.md).
_MUTATION_ALLOWED_DIRS = (os.path.join("spartan_tpu", "array")
                          + os.sep,)
_MUTATION_ALLOWED_FILES = (
    os.path.join("spartan_tpu", "expr", "incremental.py"),)
_MUTATION_ATTRS = {"_jax", "_lineage", "_version"}

# rule 15: a traced-start dynamic slice on a sharded operand lowers
# to a FULL all-gather of that operand (GSPMD cannot bound traced
# indices to one shard) — the worst communication shape the plan
# auditor flags (analysis/plan_audit.py). Only the incremental
# engine's stash path may construct one, and only on delta-sized
# data (docs/INCREMENTAL.md). Exact-name match: the *_in_dim
# helpers and lax.slice have static bounds and are fine.
_DYNSLICE_ALLOWED_FILES = (
    os.path.join("spartan_tpu", "expr", "incremental.py"),)
_DYNSLICE_ATTRS = {"dynamic_slice", "dynamic_update_slice"}

# rule 16: the sanctioned concurrency seams — every background thread
# in the package is one the monitor's epoch fence, the serve drain
# barrier and the crash-dump span tree account for. Thread/Timer
# CONSTRUCTION only; Lock/Event/Condition are synchronization, not
# threads of execution, and are fine everywhere.
_THREAD_ALLOWED_DIRS = (
    os.path.join("spartan_tpu", "serve") + os.sep,
    os.path.join("spartan_tpu", "resilience") + os.sep,
)
_THREAD_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "obs", "monitor.py"),
    os.path.join("spartan_tpu", "obs", "numerics.py"),
    os.path.join("spartan_tpu", "persist", "__init__.py"),
}
_THREAD_CTORS = {"Thread", "Timer"}

# rule 17: raw ``addressable_shards`` iteration is the shard-walk
# seam — every per-tile read-out must agree on device labels, index
# formatting and host-fetch behavior, or the skew observatory's
# imbalance attribution, numerics tile-health and checkpointing
# disagree about which shard is which. One sanctioned walk
# (obs/skew.local_shards / per_shard_stats), the array layer that
# owns the buffers, and the checkpoint serialization seam.
_SHARDS_ALLOWED_DIRS = (os.path.join("spartan_tpu", "array") + os.sep,)
_SHARDS_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "obs", "skew.py"),
    os.path.join("spartan_tpu", "utils", "checkpoint.py"),
}

# rule 18: per-shard checksum walks and shard-buffer bit surgery are
# the integrity seam — the SDC sentinel owns both ends (detect AND
# inject), so checksums never drift on shard ordering/byte layout and
# every deliberate flip is one the sentinel can account for
_CHECKSUM_ALLOWED_FILES = {
    os.path.join("spartan_tpu", "resilience", "integrity.py"),
    os.path.join("spartan_tpu", "resilience", "faults.py"),
}
_CHECKSUM_NAMES = {"shard_checksums", "flip_bit"}


class Finding:
    def __init__(self, path: str, line: int, rule: str, message: str):
        self.path = os.path.relpath(path, REPO)
        self.line = line
        self.rule = rule
        self.message = message

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    __repr__ = __str__


def _iter_py_files(root: str = PACKAGE) -> List[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        out.extend(os.path.join(dirpath, f) for f in filenames
                   if f.endswith(".py"))
    return sorted(out)


def lint_shard_map_imports(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 1: no ``jax.experimental.shard_map`` (import or attribute)."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.Attribute) and node.attr == "shard_map":
            names = [ast.unparse(node)]
        else:
            continue
        for name in names:
            if "experimental.shard_map" in name:
                findings.append(Finding(
                    path, node.lineno, "experimental-shard-map",
                    f"{name}: use jax.shard_map (from jax import "
                    "shard_map)"))
    return findings


def lint_raw_timing(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 3: no raw wall-clock timing outside obs/ + the profiling
    facade — timing that bypasses the span/phase/stopwatch API never
    reaches the trace ring or the metrics registry."""
    rel = os.path.relpath(path, REPO)
    if rel in _TIMING_ALLOWED_FILES or any(
            rel.startswith(d) for d in _TIMING_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "raw-timing",
            f"{what}: time all in-package work through the span/phase "
            "API (utils/profiling.phase / .stopwatch / obs.trace.span) "
            "so it lands in the trace ring and metrics registry"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _CLOCK_FNS:
            root = node.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in ("time", "_time"):
                flag(node, f"raw {root.id}.{node.attr}() timing")
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "") == "time":
                for a in node.names:
                    if a.name in _CLOCK_FNS:
                        flag(node, f"binds time.{a.name} directly")
    return findings


def lint_debug_callbacks(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 4: no raw jax.debug.callback / jax.debug.print outside
    obs/ + expr/loop.py — device->host telemetry that bypasses the
    sentinel API is invisible to st.audit, the metrics registry and
    the crash-dump machinery."""
    rel = os.path.relpath(path, REPO)
    if rel in _DEBUG_CB_ALLOWED_FILES or any(
            rel.startswith(d) for d in _DEBUG_CB_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "raw-debug-callback",
            f"{what}: route device->host telemetry through the "
            "numerics sentinel (obs/numerics.probe / guard_finite / "
            "record_loop_health) so it is audit-collected, "
            "metrics-fed and crash-dump-visible"))

    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr in _DEBUG_CB_FNS
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "debug"):
            root = node.value.value
            if isinstance(root, ast.Name) and root.id == "jax":
                flag(node, f"raw jax.debug.{node.attr}")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax.debug"):
                flag(node, f"import from {mod!r}")
            elif mod == "jax" and any(
                    a.name == "debug" for a in node.names):
                flag(node, "binds jax.debug directly")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.debug"):
                    flag(node, f"import {a.name}")
    return findings


def _call_names(nodes) -> Set[str]:
    """Function names called anywhere under ``nodes`` (Name or the
    final Attribute segment: ``jax.jit`` -> ``jit``)."""
    out: Set[str] = set()
    for stmt in nodes:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Name):
                    out.add(fn.id)
                elif isinstance(fn, ast.Attribute):
                    out.add(fn.attr)
    return out


def lint_bare_recovery(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 5: no broad except around compile/dispatch calls outside
    resilience/ — blind catch-and-retry bypasses the classifier, the
    retry budget and the resilience metrics/forensics."""
    rel = os.path.relpath(path, REPO)
    if any(rel.startswith(d) for d in _RECOVERY_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        guarded = _call_names(node.body) & _DISPATCH_CALLS
        if not guarded:
            continue
        for handler in node.handlers:
            t = handler.type
            if t is None:
                caught = {"<bare>"}
            else:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                caught = set()
                for e in elts:
                    if isinstance(e, ast.Name):
                        caught.add(e.id)
                    elif isinstance(e, ast.Attribute):
                        caught.add(e.attr)
            broad = ({"<bare>"} & caught) or (caught & _BROAD_HANDLERS)
            if not broad:
                continue
            if _call_names(handler.body) & _ENGINE_ROUTES:
                continue  # routes into the policy engine: sanctioned
            findings.append(Finding(
                path, handler.lineno, "bare-recovery",
                f"broad except ({', '.join(sorted(broad))}) around "
                f"{'/'.join(sorted(guarded))}: recovery decisions "
                "belong to spartan_tpu/resilience (classifier + "
                "policy engine) — catch a specific exception, or "
                "route the failure into "
                "resilience.engine.handle_failure"))
    return findings


def lint_shared_state(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 6: the plan/compile caches and the metrics registry's
    internal tables are touched only by their owning modules — any
    other access bypasses the locking discipline, the LRU recency
    order and the eviction accounting the serving engine relies on."""
    rel = os.path.relpath(path, REPO)
    cache_owner = rel == _CACHE_OWNER
    metrics_owner = rel == _METRICS_OWNER
    findings: List[Finding] = []

    def check(node: ast.AST, name: str) -> None:
        if name in _CACHE_NAMES and not cache_owner:
            findings.append(Finding(
                path, getattr(node, "lineno", 0), "shared-state",
                f"direct access to {name}: the plan/compile caches "
                "are shared hot state owned by expr/base.py — go "
                "through lookup_plan / store_plan / cached_executable "
                "/ clear_plan_cache / clear_compile_cache so the "
                "locking discipline, LRU order and eviction "
                "accounting hold"))
        elif name in _REGISTRY_INTERNALS and not metrics_owner:
            findings.append(Finding(
                path, getattr(node, "lineno", 0), "shared-state",
                f"direct access to registry internals ({name}): use "
                "REGISTRY.counter()/gauge()/histogram()/snapshot() — "
                "the instrument tables are lock-guarded shared state "
                "owned by obs/metrics.py"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            check(node, node.attr)
        elif isinstance(node, ast.Name):
            check(node, node.id)
    return findings


def _calls_mesh_maker(value: ast.AST) -> Optional[str]:
    """The mesh-constructor name called anywhere under ``value``, or
    None. Matches ``get_mesh()``, ``mesh_mod.build_mesh(...)``,
    ``Mesh(arr, axes)`` — by the final name segment."""
    for node in ast.walk(value):
        if isinstance(node, ast.Call):
            fn = node.func
            name = (fn.id if isinstance(fn, ast.Name)
                    else fn.attr if isinstance(fn, ast.Attribute)
                    else None)
            if name in _MESH_MAKERS:
                return name
    return None


def lint_mesh_capture(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 7: no mesh object captured in module globals or class
    attributes outside parallel/ — a stored mesh outlives
    rebuild_mesh and dodges the epoch fence (elastic recovery)."""
    rel = os.path.relpath(path, REPO)
    if any(rel.startswith(d) for d in _MESH_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, maker: str, where: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "mesh-capture",
            f"{maker}() result stored in a {where}: a captured mesh "
            "outlives rebuild_mesh (device loss bumps the mesh epoch "
            "and the stored mesh points at dead devices). Call "
            "get_mesh() at use time, or store the mesh on an instance "
            "TOGETHER with its birth epoch (as DistArray does)"))

    def scan_block(body, where: str) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = stmt.value
                if value is None:
                    continue
                maker = _calls_mesh_maker(value)
                if maker:
                    flag(stmt, maker, where)

    scan_block(tree.body, "module global")
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            scan_block(node.body, "class attribute")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            declared_global = {
                n for stmt in ast.walk(node)
                if isinstance(stmt, ast.Global) for n in stmt.names}
            if not declared_global:
                continue
            for stmt in ast.walk(node):
                if not isinstance(stmt, ast.Assign):
                    continue
                targets = {t.id for t in stmt.targets
                           if isinstance(t, ast.Name)}
                if targets & declared_global:
                    maker = _calls_mesh_maker(stmt.value)
                    if maker:
                        flag(stmt, maker, "module global (via "
                             "`global` declaration)")
    return findings


def lint_raw_memory_stats(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 8: no direct ``.memory_stats()`` calls outside the three
    sanctioned modules — the budget auto-detect and the device gauges
    must read ONE aggregated source across all local devices."""
    rel = os.path.relpath(path, REPO)
    if rel in _MEMSTATS_ALLOWED_FILES:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "memory_stats"):
            findings.append(Finding(
                path, node.lineno, "raw-memory-stats",
                "direct .memory_stats() call: device memory read-outs "
                "are single-sourced (obs/metrics.py, parallel/mesh.py, "
                "resilience/memory.py) so the HBM budget auto-detect "
                "and the device_* gauges agree — use "
                "obs.metrics.device_memory_aggregate() (all local "
                "devices, max+sum), not a per-device probe"))
    return findings


def lint_dynamic_slices(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 15: no ``dynamic_slice`` / ``dynamic_update_slice``
    outside the incremental engine's stash seam — with traced starts
    on a sharded operand the lowering is a full all-gather, the
    communication class the plan auditor flags as ``full_gather``."""
    rel = os.path.relpath(path, REPO)
    if rel in _DYNSLICE_ALLOWED_FILES:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        attr = None
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _DYNSLICE_ATTRS):
            attr = node.func.attr
        elif (isinstance(node, (ast.ImportFrom, ast.Import))):
            names = {a.name for a in node.names}
            hit = names & _DYNSLICE_ATTRS
            if hit and getattr(node, "module", "") in (
                    "jax.lax", "jax", "lax"):
                attr = sorted(hit)[0]
        if attr is not None:
            findings.append(Finding(
                path, node.lineno, "traced-start-slice",
                f"{attr} outside the incremental seam: a traced-start "
                "slice of a sharded operand lowers to a FULL "
                "all-gather of that operand (st.audit_plan flags it "
                "as full_gather) — only expr/incremental.py's "
                "delta-sized stash path may pay that knowingly "
                "(docs/INCREMENTAL.md); use static-bound slicing "
                "(lax.slice / dynamic_slice_in_dim on unsharded "
                "axes) or the incremental API instead"))
    return findings


def lint_shard_walks(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 17: no raw ``addressable_shards`` access outside the
    shard-walk seam (obs/skew.py), the array layer and the checkpoint
    serializer — per-tile read-outs that bypass
    ``obs.skew.per_shard_stats`` / ``local_shards`` drift on device
    labels and fetch behavior, and the skew observatory's straggler
    attribution stops matching what the other surfaces report."""
    rel = os.path.relpath(path, REPO)
    if rel in _SHARDS_ALLOWED_FILES or any(
            rel.startswith(d) for d in _SHARDS_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr == "addressable_shards"):
            findings.append(Finding(
                path, node.lineno, "shard-walk",
                "raw .addressable_shards access outside the shard-walk "
                "seam: per-tile reads are single-sourced through "
                "obs.skew.per_shard_stats(arr) / local_shards(jarr) "
                "(plus the array layer and utils/checkpoint.py's "
                "serializer) so device labels, shard indices and "
                "host-fetch behavior agree across the skew "
                "observatory, tile health and checkpoints — use those "
                "helpers instead (docs/OBSERVABILITY.md)"))
    return findings


def lint_checksum_walks(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 18: no ``shard_checksums`` / ``flip_bit`` references
    outside the integrity seam (resilience/integrity.py owns both, the
    chaos injector in resilience/faults.py delegates to it) — a
    checksum walk elsewhere drifts on shard ordering and byte layout
    so its verdicts stop matching the SDC sentinel's, and bit surgery
    outside the seam is corruption the sentinel cannot attribute."""
    rel = os.path.relpath(path, REPO)
    if rel in _CHECKSUM_ALLOWED_FILES:
        return []
    findings: List[Finding] = []
    for node in ast.walk(tree):
        name = None
        if isinstance(node, ast.Attribute) and node.attr in _CHECKSUM_NAMES:
            name = node.attr
        elif isinstance(node, ast.Name) and node.id in _CHECKSUM_NAMES:
            name = node.id
        if name is not None:
            findings.append(Finding(
                path, node.lineno, "checksum-walk",
                f"{name} outside the integrity seam: per-shard "
                "checksums and shard-buffer bit surgery are "
                "single-sourced in resilience/integrity.py (the SDC "
                "sentinel) with resilience/faults.py's chaos injector "
                "as the one delegating caller — route detection "
                "through integrity.maybe_check and injection through "
                "the sdc chaos kind (docs/RESILIENCE.md)"))
    return findings


def lint_raw_profiling(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 9: no raw jax.profiler use outside obs/trace.py +
    obs/profile.py, and no direct cost_analysis / memory_analysis
    calls outside obs/explain.py + resilience/memory.py — a
    measurement that bypasses the sanctioned entry points never
    reaches the cost ledger, so it can't be compared against the
    models it should be validating."""
    rel = os.path.relpath(path, REPO)
    profiler_ok = rel in _PROFILER_ALLOWED_FILES
    analysis_ok = rel in _ANALYSIS_ALLOWED_FILES
    if profiler_ok and analysis_ok:
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "raw-profiling",
            f"{what}: device-time measurement and compiled-program "
            "introspection are single-sourced so readings land in the "
            "cost ledger — use obs.trace.device_profile/.annotate, "
            "obs.profile.profile (the attribution profiler), "
            "obs.explain.compiled_cost_analysis, or "
            "resilience.memory.validate_plan"))

    for node in ast.walk(tree):
        if profiler_ok:
            pass
        elif isinstance(node, ast.Attribute) and node.attr == "profiler":
            root = node.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id == "jax":
                flag(node, "raw jax.profiler use")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax.profiler"):
                flag(node, f"import from {mod!r}")
            elif mod == "jax" and any(a.name == "profiler"
                                      for a in node.names):
                flag(node, "binds jax.profiler directly")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.profiler"):
                    flag(node, f"import {a.name}")
        if (not analysis_ok and isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _ANALYSIS_CALLS):
            flag(node, f"direct .{node.func.attr}() call")
    return findings


def lint_named_scopes(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 11: no raw jax.named_scope outside expr/base.py + obs/ —
    scope names are the profiler's join key (the digest-carrying
    per-node scopes), so an ad-hoc scope elsewhere is a device-trace
    name the attribution report can never map to an expr node."""
    rel = os.path.relpath(path, REPO)
    if rel in _NAMED_SCOPE_ALLOWED_FILES or any(
            rel.startswith(d) for d in _NAMED_SCOPE_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "raw-named-scope",
            f"{what}: trace-time scope names are the device-time "
            "profiler's join key — use obs.trace.named_scope for a "
            "fixed label (expr/base.Expr.lower owns the per-node "
            "digest-carrying scopes)"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and node.attr == "named_scope":
            root = node.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id == "jax":
                flag(node, "raw jax.named_scope use")
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").startswith("jax") and any(
                    a.name == "named_scope"
                    or a.asname == "named_scope" for a in node.names):
                flag(node, "binds jax.named_scope directly")
    return findings


def lint_sharding_constraints(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 10: no raw ``with_sharding_constraint`` outside the
    redistribution planner and the expr/base lowering seam — a raw
    constraint is a reshard edge the cost-modeled planner never sees
    (not priced, never explicit, absent from st.explain's schedule
    report)."""
    rel = os.path.relpath(path, REPO)
    if rel in _WSC_ALLOWED_FILES:
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "raw-sharding-constraint",
            f"{what}: sharding-constraint seams belong to the "
            "redistribution planner — call "
            "parallel.redistribute.constrain() (pass src= when the "
            "producing layout is known) so the edge is priced, "
            "eligible for the explicit collective lowering, and "
            "visible in st.explain's schedule report"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and node.attr == "with_sharding_constraint":
            flag(node, "raw with_sharding_constraint use")
        elif isinstance(node, ast.ImportFrom):
            if any(a.name == "with_sharding_constraint"
                   or a.asname == "with_sharding_constraint"
                   for a in node.names):
                flag(node, "binds with_sharding_constraint directly")
    return findings


def lint_pallas_imports(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 12: no ``jax.experimental.pallas`` import (or
    ``pallas_call`` use) outside ``spartan_tpu/kernels/`` — every
    Pallas kernel goes through the kernel layer so its grid derives
    from the committed tiling and its backend follows the platform."""
    rel = os.path.relpath(path, REPO)
    if any(rel.startswith(d) for d in _PALLAS_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "pallas-outside-kernels",
            f"{what}: Pallas kernels live in spartan_tpu/kernels/ "
            "(docs/KERNELS.md) — add the kernel there, derive its "
            "grid from the committed Tiling (kernels.registry.derive) "
            "and route callers through kernels.registry.select"))

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if "pallas" in mod.split("."):
                flag(node, f"import from {mod!r}")
            elif any(a.name == "pallas" or a.name.startswith("pallas.")
                     for a in node.names):
                flag(node, "binds the pallas module")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if "pallas" in a.name.split("."):
                    flag(node, f"import {a.name}")
        elif isinstance(node, ast.Attribute) \
                and node.attr == "pallas_call":
            # pl.pallas_call / pallas.pallas_call — the call seam
            flag(node, "pallas_call use")
        elif isinstance(node, ast.Attribute) and node.attr == "pallas":
            # jax.experimental.pallas attribute chains (not arbitrary
            # objects with a .pallas property, e.g. kernels.Selection)
            root = node.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id == "jax":
                flag(node, "attribute access on jax's pallas")
    return findings


def lint_persist_seam(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 13: JAX AOT executable serialization
    (``jax.experimental.serialize_executable``) and direct
    ``persist_cache_dir`` flag access only inside
    ``spartan_tpu/persist/`` — the store owns the fingerprint /
    CRC / lease / degrade contract (docs/WARMSTART.md)."""
    rel = os.path.relpath(path, REPO)
    if any(rel.startswith(d) for d in _PERSIST_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "persist-seam",
            f"{what}: the warm-start store (spartan_tpu/persist, "
            "docs/WARMSTART.md) owns AOT serialization and the "
            "persist directory — go through spartan_tpu.persist "
            "(active()/lookup()/maybe_store()/prewarm())"))

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if "serialize_executable" in mod.split("."):
                flag(node, f"import from {mod!r}")
            elif any(a.name in _PERSIST_SERIALIZE_NAMES
                     for a in node.names):
                flag(node, "binds the AOT serialization API")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if "serialize_executable" in a.name.split("."):
                    flag(node, f"import {a.name}")
        elif isinstance(node, ast.Attribute) \
                and node.attr in _PERSIST_SERIALIZE_NAMES:
            flag(node, f"attribute use of {node.attr}")
        elif isinstance(node, ast.Attribute) \
                and node.attr == "persist_cache_dir":
            # FLAGS.persist_cache_dir reads/writes outside the store:
            # the path must be resolved through persist.active() so a
            # broken directory degrades instead of erroring ad hoc
            flag(node, "direct persist_cache_dir access")
    return findings


def lint_buffer_mutation(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 14: no stores to a DistArray's private buffer/lineage
    slots (``._jax`` / ``._lineage`` / ``._version``) outside
    ``spartan_tpu/array/`` and the incremental seam
    (``spartan_tpu/expr/incremental.py``) — every mutation must go
    through ``DistArray.update()`` / ``st.assign`` so the Lineage log
    stays truthful and the incremental result cache can never serve a
    silently-mutated buffer as clean (docs/INCREMENTAL.md)."""
    rel = os.path.relpath(path, REPO)
    if (any(rel.startswith(d) for d in _MUTATION_ALLOWED_DIRS)
            or rel in _MUTATION_ALLOWED_FILES):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, attr: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "buffer-mutation",
            f"store to DistArray private state '.{attr}' outside the "
            "array layer / incremental seam: mutate through "
            "DistArray.update() or st.assign so the lineage log "
            "(docs/INCREMENTAL.md) records the delta"))

    def targets(node: ast.AST) -> List[ast.expr]:
        if isinstance(node, ast.Assign):
            return list(node.targets)
        if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            return [node.target]
        if isinstance(node, ast.Delete):
            return list(node.targets)
        return []

    for node in ast.walk(tree):
        for t in targets(node):
            for sub in ast.walk(t):
                if (isinstance(sub, ast.Attribute)
                        and sub.attr in _MUTATION_ATTRS):
                    flag(node, sub.attr)
    return findings


def lint_background_threads(path: str, tree: ast.AST) -> List[Finding]:
    """Rule 16: no ``threading.Thread`` / ``threading.Timer``
    construction outside the sanctioned concurrency seams (serve/,
    resilience/, the monitor sampler, the dispatch watchdog, the
    persist prewarm) — a stray background thread dodges the
    mesh-epoch fence, is invisible to st.status()'s health section
    and leaks past shutdown()."""
    rel = os.path.relpath(path, REPO)
    if rel in _THREAD_ALLOWED_FILES or any(
            rel.startswith(d) for d in _THREAD_ALLOWED_DIRS):
        return []
    findings: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        findings.append(Finding(
            path, getattr(node, "lineno", 0), "background-thread",
            f"{what}: background threads live in the sanctioned "
            "concurrency seams (serve/ worker pool, resilience/, "
            "obs/monitor.py sampler, obs/numerics.py watchdog, "
            "persist prewarm) where the epoch fence, the drain "
            "barrier and the crash-dump span tree account for them — "
            "run the work on an existing seam (serve workers, the "
            "monitor's tick) instead of spawning a thread"))

    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _THREAD_CTORS):
            root = node.func.value
            if isinstance(root, ast.Name) and root.id == "threading":
                flag(node, f"threading.{node.func.attr}(...) "
                     "construction")
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "") == "threading":
                for a in node.names:
                    if a.name in _THREAD_CTORS:
                        flag(node, f"binds threading.{a.name} "
                             "directly")
    return findings


def _collect_classes(files: List[str]
                     ) -> Dict[str, Tuple[List[str], Set[str], str, int]]:
    """name -> (base names, methods defined in the body, path, line).

    Simple-name resolution: class names are unique across the package
    (enforced here — a duplicate would make the lint ambiguous)."""
    table: Dict[str, Tuple[List[str], Set[str], str, int]] = {}
    for path in files:
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            bases = []
            for b in node.bases:
                if isinstance(b, ast.Name):
                    bases.append(b.id)
                elif isinstance(b, ast.Attribute):
                    bases.append(b.attr)
            methods = {n.name for n in node.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            if node.name not in table:
                table[node.name] = (bases, methods, path, node.lineno)
    return table


def lint_expr_subclasses(files: List[str]) -> List[Finding]:
    """Rule 2: every Expr subclass defines _sig and replace_children
    somewhere in its chain below the Expr base."""
    table = _collect_classes(files)

    def is_expr(name: str, seen: Optional[Set[str]] = None) -> bool:
        if name in _ABSTRACT_EXPRS:
            return True
        if name not in table:
            return False
        seen = seen or set()
        if name in seen:
            return False
        seen.add(name)
        return any(is_expr(b, seen) for b in table[name][0])

    def defines(name: str, method: str) -> bool:
        """Defined in `name` or any ancestor below the Expr base."""
        if name in _ABSTRACT_EXPRS or name not in table:
            return False
        bases, methods, _, _ = table[name]
        if method in methods:
            return True
        return any(defines(b, method) for b in bases)

    findings: List[Finding] = []
    for name, (bases, methods, path, line) in sorted(table.items()):
        if name in _ABSTRACT_EXPRS or not is_expr(name):
            continue
        for hook in ("_sig", "replace_children"):
            if not defines(name, hook):
                findings.append(Finding(
                    path, line, "expr-subclass-hooks",
                    f"Expr subclass {name} never defines {hook}; the "
                    "base stub raises NotImplementedError and breaks "
                    "the structural caches / optimizer rewrites"))
    return findings


def run_lint(root: str = PACKAGE) -> List[Finding]:
    files = _iter_py_files(root)
    findings: List[Finding] = []
    for path in files:
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError as e:
                findings.append(Finding(path, e.lineno or 0, "syntax",
                                        str(e)))
                continue
        findings.extend(lint_shard_map_imports(path, tree))
        findings.extend(lint_raw_timing(path, tree))
        findings.extend(lint_debug_callbacks(path, tree))
        findings.extend(lint_bare_recovery(path, tree))
        findings.extend(lint_shared_state(path, tree))
        findings.extend(lint_mesh_capture(path, tree))
        findings.extend(lint_raw_memory_stats(path, tree))
        findings.extend(lint_raw_profiling(path, tree))
        findings.extend(lint_named_scopes(path, tree))
        findings.extend(lint_sharding_constraints(path, tree))
        findings.extend(lint_pallas_imports(path, tree))
        findings.extend(lint_persist_seam(path, tree))
        findings.extend(lint_buffer_mutation(path, tree))
        findings.extend(lint_dynamic_slices(path, tree))
        findings.extend(lint_background_threads(path, tree))
        findings.extend(lint_shard_walks(path, tree))
        findings.extend(lint_checksum_walks(path, tree))
    findings.extend(lint_expr_subclasses(files))
    return findings


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    findings = run_lint()
    if "--json" in argv:
        import json
        print(json.dumps([{"path": f.path, "line": f.line,
                           "rule": f.rule, "message": f.message}
                          for f in findings], indent=2))
        return 1 if findings else 0
    for f in findings:
        print(f)
    if findings:
        print(f"{len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("lint_repo: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
