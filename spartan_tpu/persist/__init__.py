"""Warm-start persistence: the crash-safe plan & executable store.

A serving replica restart used to recompile the world — fatal for
rolling restarts of a fleet.
This package makes the plan cache and the compiled executables
DURABLE: ``evaluate()``'s miss path consults the store before the
optimizer runs, pre-seeds the compile cache with the deserialized AOT
executable on a hit (zero XLA recompiles, bit-equal results), and
persists freshly-compiled plans after the compile; ``ServeEngine.
prewarm(manifest)`` restores a configured plan set at startup off the
request path.

Addressing & safety (fingerprint.py / store.py):

* entries are keyed by a process-stable digest of the SAME raw-DAG
  plan key ``evaluate()`` computes, extended with a full environment
  fingerprint (python/jax/jaxlib versions, platform, device count,
  mesh shape + epoch, ``_opt_flags_key``) —
  stale or foreign entries can never alias;
* writes are atomic temp-dir + ``os.replace`` with per-file CRC32
  manifests (the PR-5 checkpoint discipline); concurrent replicas
  sharing one directory are lock-free-reader / lease-writer;
* loads validate version + fingerprint + CRC, and EVERY failure —
  corruption, skew, ``io`` chaos, deserialize errors — degrades to a
  normal recompile with the reason surfaced in the ``persist_*``
  metrics family and ``st.explain``. Persistence can never make
  ``evaluate()`` less available than it is with the store off.

``FLAGS.persist_cache_dir`` (default "" = off) turns it on; with it
off the hit path is UNTOUCHED and the miss path pays one flag read
(benchmarks/warm_start.py gates ``warmstart_off_overhead_ratio``).
See docs/WARMSTART.md for the layout, the invalidation matrix and the
rolling-restart runbook.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Tuple, Union

from ..obs.metrics import METRICS_FLAG as _METRICS_FLAG
from ..obs.metrics import REGISTRY, labeled
from ..utils.config import FLAGS
from ..utils.log import log_debug, log_warn
from .fingerprint import (UnstableKeyError, entry_digest, env_fingerprint,
                          stable_digest)
from .store import Entry, PersistRejected, PersistStore

__all__ = [
    "PersistStore", "PersistRejected", "Entry", "UnstableKeyError",
    "active", "lookup", "maybe_store", "maybe_gc", "evict_stale",
    "prewarm", "write_manifest", "stats", "reset",
]

_DIR_FLAG = FLAGS.define_str(
    "persist_cache_dir", "",
    "Crash-safe on-disk store for plans + compiled executables "
    "(spartan_tpu/persist, docs/WARMSTART.md): evaluate()'s miss path "
    "consults it before optimizing and persists after compile, so a "
    "process restart serves its plan set with zero recompiles. "
    "Entries are fingerprint-keyed (jax/platform/mesh/flags) and "
    "CRC-verified; any mismatch or corruption degrades to a normal "
    "recompile. Empty = off (the default: zero hot-path change).")
FLAGS.define_float(
    "persist_lease_ttl_s", 60.0,
    "Writer-lease time-to-live for a shared persist_cache_dir: a "
    "lease file older than this is considered abandoned (writer "
    "crashed mid-persist) and may be broken by another replica.")
FLAGS.define_float(
    "persist_prewarm_timeout_s", 30.0,
    "Per-entry timeout for ServeEngine.prewarm: one slow or hostile "
    "entry cannot stall the rest of the prewarm set (the load keeps "
    "running in the background and is adopted if it finishes).")
FLAGS.define_int(
    "persist_max_bytes", 0,
    "Size bound on the persist store (long-lived fleets): after each "
    "persisted entry, least-recently-USED entries (manifest mtime — "
    "refreshed on every load) are evicted until the store fits. "
    "0 = unbounded (the default; entries then persist until "
    "fingerprint rotation or dead-epoch eviction).")
FLAGS.define_float(
    "persist_ttl_s", 0.0,
    "Age bound on persist-store entries: an entry not used (loaded) "
    "for longer than this is evicted by the post-store GC sweep. "
    "0 = no TTL.")

# -- process-level store singleton ---------------------------------------

_lock = threading.Lock()
_store: Optional[PersistStore] = None
_store_dir: Optional[str] = None
_failed_dir: Optional[str] = None

# plan_key -> (digest | None) memo: signing is per-request; digesting
# (a full SHA walk of the key) is per-PLAN. Bounded; cleared on reset.
_digest_memo: Dict[Tuple, Optional[str]] = {}
_DIGEST_MEMO_MAX = 1024

# what the last _build_plan on THIS thread did (disk hit vs compile):
# the serve worker stamps it onto the request's flight record
_TLS = threading.local()


def _count(name: str, n: int = 1, **labels: str) -> None:
    if _METRICS_FLAG._value and n:
        full = labeled(name, **labels) if labels else name
        REGISTRY.counter(full, "persistent plan/executable store "
                         "(spartan_tpu/persist)").inc(n)


def active() -> Optional[PersistStore]:
    """The process's store for FLAGS.persist_cache_dir, or None when
    persistence is off (one flag read). A directory that cannot be
    created disables the store for that path (warn once) — an
    unusable disk must not fail evaluations."""
    global _store, _store_dir, _failed_dir
    d = _DIR_FLAG._value
    if not d:
        return None
    if _store is not None and _store_dir == d:
        return _store
    if _failed_dir == d:
        return None
    with _lock:
        if _store is not None and _store_dir == d:
            return _store
        try:
            _store = PersistStore(d)
            _store_dir = d
            _failed_dir = None
        except OSError as e:
            log_warn("persist: cannot open cache dir %r (%s); "
                     "persistence disabled for this path", d, e)
            _count("persist_store_errors", reason="open")
            _failed_dir = d
            _store = None
            _store_dir = None
    return _store


def reset() -> None:
    """Forget the store singleton, digest memo and prewarm table (test
    isolation; the on-disk contents are untouched)."""
    global _store, _store_dir, _failed_dir
    with _lock:
        _store = None
        _store_dir = None
        _failed_dir = None
        _digest_memo.clear()
    _TLS.__dict__.clear()


def digest_for(plan_key: Tuple, mesh: Any) -> Optional[str]:
    """Process-stable on-disk address for one plan key (memoized), or
    None when the key has no stable representation (counted, plan
    simply not persistable)."""
    hit = _digest_memo.get(plan_key, "")
    if hit != "":
        return hit
    try:
        digest = entry_digest(plan_key, env_fingerprint(mesh))
    except UnstableKeyError as e:
        log_debug("persist: unstable plan key (%s)", e)
        _count("persist_unstable_keys")
        digest = None
    if len(_digest_memo) >= _DIGEST_MEMO_MAX:
        _digest_memo.clear()
    _digest_memo[plan_key] = digest
    return digest


# -- evaluate() seams -----------------------------------------------------


def note_build(source: str, digest: Optional[str] = None,
               reason: Optional[str] = None) -> None:
    _TLS.last = {"source": source, "digest": digest, "reason": reason}


def take_build_source() -> Optional[Dict[str, Any]]:
    """Pop this thread's last persist outcome (disk vs compile) — the
    serve worker stamps it onto the request's flight record."""
    last = getattr(_TLS, "last", None)
    _TLS.last = None
    return last


def lookup(plan_key: Optional[Tuple], mesh: Any
           ) -> Tuple[Optional[Entry], Optional[str], Optional[str]]:
    """Consult the store for one plan key (the miss path's first act,
    BEFORE the optimizer). Returns ``(entry, digest, reject_reason)``;
    entry None means recompile (clean miss, store off, unstable key,
    or a rejected/hostile entry — the reason says which, and lands in
    metrics + the plan report)."""
    store = active()
    if store is None or plan_key is None:
        return None, None, None
    digest = digest_for(plan_key, mesh)
    if digest is None:
        return None, None, "unstable_key"
    try:
        entry = store.load(digest, env_fingerprint(mesh))
    except PersistRejected as e:
        log_warn("persist: entry %s rejected (%s); recompiling",
                 digest[:12], e)
        _count("persist_load_errors", reason=e.reason)
        return None, digest, e.reason
    except (OSError, UnstableKeyError) as e:
        log_warn("persist: load failed for %s (%s: %s); recompiling",
                 digest[:12], type(e).__name__, e)
        _count("persist_load_errors", reason="io")
        return None, digest, "io"
    if entry is None:
        _count("persist_misses")
        return None, digest, None
    # the hit is counted by note_hit() once expr.base's belt checks
    # accept the entry (a metadata mismatch flips it to a rejection)
    return entry, digest, None


def note_hit() -> None:
    _count("persist_hits")


def reject_entry(entry: Entry, reason: str) -> None:
    """An entry survived fingerprint + CRC but failed the plan-level
    belt checks: count the reason, purge it (it can never load) and
    recompile."""
    log_warn("persist: entry %s rejected (%s); recompiling and "
             "purging", entry.digest[:12], reason)
    _count("persist_load_errors", reason=reason)
    store = active()
    if store is not None:
        store.purge(entry.digest)


def guarded_callable(entry: Entry, fallback_factory: Any) -> Any:
    """Wrap a restored executable so an argument/sharding mismatch at
    call time (a digest collision, or metadata the belt checks could
    not see) degrades to a fresh jit compile instead of failing the
    dispatch: availability over reuse, always."""
    holder: List[Any] = []

    def run(*args: Any) -> Any:
        if holder:
            return holder[0](*args)
        try:
            return entry.compiled(*args)
        except (TypeError, ValueError) as e:
            # aval / sharding / layout mismatch: this entry does not
            # fit the args this process actually gathers
            log_warn("persist: restored executable %s does not fit "
                     "(%s: %s); recompiling and purging the entry",
                     entry.digest[:12], type(e).__name__,
                     str(e)[:120])
            _count("persist_call_fallbacks")
            store = active()
            if store is not None:
                store.purge(entry.digest)
            holder.append(fallback_factory())
            return holder[0](*args)

    return run


def aot_compile(traced: Any, args: Tuple[Any, ...]) -> Any:
    """Build the base-variant executable ahead-of-time (lower over the
    concrete gathered args, compile once): the resulting
    ``jax.stages.Compiled`` is both the dispatchable executable and
    the serializable artifact — persistence never pays a second XLA
    compile. Only used when the store is active; donation and serve
    batch variants keep the plain ``jax.jit`` path."""
    import jax

    return jax.jit(traced).lower(*args).compile()


def serializable(executable: Any) -> bool:
    import jax

    return isinstance(executable, jax.stages.Compiled)


def maybe_store(plan: Any, executable: Any, mesh: Any) -> bool:
    """Persist a freshly-compiled plan (called by ``_dispatch`` right
    after the first compile+run). No-raise: a failed persist is
    counted, never propagated into the evaluation that produced the
    plan."""
    store = active()
    digest = getattr(plan, "persist_digest", None)
    if store is None or digest is None:
        return False
    if not serializable(executable):
        _count("persist_store_skipped", reason="not_aot")
        return False
    # the raw->optimized arg order is the process-stable calling
    # convention (plan.arg_order is the identity variant's on the very
    # first dispatch); uncacheable plans never get here
    arg_order = (plan.report or {}).get("arg_order")
    if arg_order is None:
        _count("persist_store_skipped", reason="uncacheable")
        return False
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(executable)
        plan_meta = {
            "out_tilings": [[list(ax) if isinstance(ax, tuple) else ax
                             for ax in t.axes]
                            for t in plan.out_tilings],
            "is_tuple": plan.is_tuple,
            "arg_order": list(arg_order),
            "nargs": len(arg_order),
        }
        # the plan-audit verdict (analysis/plan_audit.py) rides the
        # entry when one was computed: a warm restart restores it with
        # the executable and never re-lowers for the audit. JSON-safe
        # by construction (PlanAudit.to_dict).
        verdict = (plan.report or {}).get("audit")
        if verdict is not None:
            plan_meta["audit"] = verdict
        landed = store.save(digest, env_fingerprint(mesh), plan_meta,
                            payload, (in_tree, out_tree))
    except Exception as e:  # noqa: BLE001 - persistence is best-effort
        # by contract: IO errors, chaos faults, unserializable
        # backends all degrade to "this plan is simply not persisted"
        log_warn("persist: store failed for %s (%s: %s)",
                 digest[:12], type(e).__name__, str(e)[:120])
        _count("persist_store_errors", reason="io")
        return False
    if landed:
        _count("persist_stores")
        if plan.report is not None and plan.report.get("persist"):
            plan.report["persist"]["stored"] = True
        maybe_gc(protect=(digest,))
    return landed


def maybe_gc(protect: Tuple[str, ...] = ()) -> int:
    """Apply the store's size/TTL bounds (``FLAGS.persist_max_bytes``
    / ``persist_ttl_s``, LRU-by-mtime) after a store landed. No-raise,
    two flag reads when unbounded; evictions land in the
    ``persist_evictions`` counter."""
    max_bytes = int(FLAGS.persist_max_bytes or 0)
    ttl_s = float(FLAGS.persist_ttl_s or 0.0)
    if not max_bytes and not ttl_s:
        return 0
    store = active()
    if store is None:
        return 0
    try:
        n = store.gc(max_bytes, ttl_s, protect=tuple(protect))
    except Exception as e:  # noqa: BLE001 - GC is hygiene, never a
        # reason to fail the evaluation that triggered it
        log_warn("persist: GC sweep failed (%s: %s)",
                 type(e).__name__, str(e)[:120])
        return 0
    if n:
        _count("persist_evictions", n)
    return n


# -- eviction -------------------------------------------------------------

_last_evicted = 0


def evict_stale() -> int:
    """Purge on-disk entries persisted under a dead mesh epoch; the
    disk half of ``expr.base.evict_stale_plans`` (elastic recovery).
    No-raise; returns entries purged."""
    global _last_evicted
    store = active()
    if store is None:
        _last_evicted = 0
        return 0
    from ..parallel import mesh as mesh_mod

    try:
        n = store.evict_epochs_before(mesh_mod._EPOCH)
    except OSError as e:
        log_warn("persist: eviction scan failed (%s)", e)
        n = 0
    _count("persist_evicted", n)
    _last_evicted = n
    return n


def last_evicted() -> int:
    return _last_evicted


# -- prewarm --------------------------------------------------------------


def _manifest_digests(manifest: Union[str, Dict[str, Any], List[str]],
                      store: PersistStore) -> List[str]:
    if manifest == "all":
        return store.digests()
    if isinstance(manifest, str):
        with open(manifest) as f:
            manifest = json.load(f)
    if isinstance(manifest, dict):
        return [str(d) for d in manifest.get("entries", [])]
    return [str(d) for d in manifest]


def prewarm(manifest: Union[str, Dict[str, Any], List[str]] = "all",
            timeout_s: Optional[float] = None) -> Dict[str, Any]:
    """Restore a configured plan set into the in-memory prewarm table
    (``ServeEngine.prewarm`` calls this at startup, off the request
    path). ``manifest``: a path to a JSON ``{"entries": [digest,...]}``
    file, the dict/list itself, or ``"all"`` (every entry in the
    store). Per-entry timeout + error isolation: one hostile, missing
    or slow entry is counted and skipped, never crashing or stalling
    the rest — each entry loads on its OWN daemon thread, so a load
    that outlives its timeout keeps running in the background (it is
    adopted into the table if it eventually finishes) but can neither
    delay the next entry nor block process exit. Returns
    ``{loaded, missing, errors, skipped, total}``."""
    from ..obs import trace as trace_mod

    stats = {"loaded": 0, "missing": 0, "errors": 0, "skipped": 0,
             "total": 0}
    store = active()
    if store is None:
        stats["skipped"] = -1  # store off: nothing to prewarm
        return stats
    if timeout_s is None:
        timeout_s = FLAGS.persist_prewarm_timeout_s
    try:
        digests = _manifest_digests(manifest, store)
    except (OSError, ValueError) as e:
        log_warn("persist: unreadable prewarm manifest (%s)", e)
        _count("persist_prewarm_errors", reason="manifest")
        stats["errors"] += 1
        return stats
    stats["total"] = len(digests)
    try:
        from ..parallel import mesh as mesh_mod

        fp = env_fingerprint(mesh_mod.get_mesh())
    except Exception as e:  # noqa: BLE001 - an unfingerprintable
        # environment disables the whole prewarm, never the process
        log_warn("persist: prewarm fingerprint failed (%s: %s)",
                 type(e).__name__, e)
        _count("persist_prewarm_errors", reason="fingerprint")
        stats["errors"] = len(digests)
        return stats
    with trace_mod.span("prewarm", entries=len(digests)):
        for digest in digests:
            outcome: Dict[str, Any] = {}

            def _load(digest=digest, outcome=outcome):
                try:
                    outcome["found"] = store.preload(digest, fp)
                except Exception as e:  # noqa: BLE001 - per-entry
                    # isolation: a hostile entry must not sink the set
                    outcome["error"] = e

            t = threading.Thread(target=_load, daemon=True,
                                 name="spartan-prewarm")
            t.start()
            t.join(timeout_s)
            if t.is_alive():
                stats["errors"] += 1
                _count("persist_prewarm_errors", reason="timeout")
                log_warn("persist: prewarm entry %s timed out after "
                         "%.1fs; skipped (its load continues in the "
                         "background)", str(digest)[:12], timeout_s)
            elif "error" in outcome:
                e = outcome["error"]
                stats["errors"] += 1
                _count("persist_prewarm_errors",
                       reason=getattr(e, "reason", "io"))
                log_warn("persist: prewarm entry %s failed (%s: %s); "
                         "skipped", str(digest)[:12],
                         type(e).__name__, str(e)[:120])
            elif outcome.get("found"):
                stats["loaded"] += 1
                _count("persist_prewarm_loaded")
            else:
                stats["missing"] += 1
                _count("persist_prewarm_missing")
                log_warn("persist: prewarm entry %s not in store; "
                         "skipped", str(digest)[:12])
    return stats


def write_manifest(path: str,
                   digests: Optional[List[str]] = None) -> int:
    """Write a prewarm manifest for the current store contents (the
    rolling-restart runbook's capture step). Returns entries listed;
    0 with the store off."""
    store = active()
    if store is None:
        return 0
    return store.write_manifest(path, digests)


def stats() -> Dict[str, Any]:
    """Store-side observability: directory, entry count, prewarm table
    size (the persist_* counters live in st.metrics())."""
    store = active()
    if store is None:
        return {"enabled": False}
    digests = store.digests()
    return {"enabled": True, "dir": store.root,
            "entries": len(digests),
            "preloaded": store.preloaded_count()}
