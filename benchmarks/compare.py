"""Diff two benchmark record files — make the BENCH trajectory
machine-comparable (ISSUE 11 satellite; docs/BENCH.md).

Accepts either committed record shape:

* a ``benchmarks/run_all.py`` full report (``config1_map_sum`` /
  ``dispatch_overhead`` / ... keys, ``platform`` at top level), or
* a ``bench.py`` flat record (``BENCH_r0x.json`` /
  ``bench_r5_validated.json``: ``kmeans_iters_per_sec``,
  ``pagerank_iters_per_sec``, ``gflops_f32_highest``, ...).

For every metric present in both files it reports old, new, the
new/old ratio and a better/worse/flat verdict (orientation-aware:
``*seconds`` / ``*_ratio`` / ``*sec_per_iter`` are lower-is-better,
everything else higher-is-better). Three regression conditions, each
producing a NONZERO exit:

1. a metric moved the wrong way by more than ``--tolerance``
   (default 0.2 — per-dispatch timings swing run to run; see
   thresholds.json note);
2. the NEW file's metrics fail the committed thresholds
   (``benchmarks/thresholds.json`` via ``utils/benchguard.check`` —
   the same re-check ``run_all.py`` grades with);
3. the two records ran on different platforms (a CPU number is never
   graded against a TPU one — a trajectory comparison must flag
   that, not average over it).
   ``--allow-platform-change`` downgrades this to a warning.

Prints ONE JSON document. Exit 0 = comparable and no regression,
1 = regression(s) found, 2 = usage/input error.

Usage:
  python benchmarks/compare.py OLD.json NEW.json
      [--tolerance 0.2] [--thresholds PATH] [--allow-platform-change]
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# metric-name suffixes where smaller is the improvement. "_us" covers
# the elastic-recovery breakdown columns (drain/rebuild/evict/migrate)
_LOWER_BETTER = ("seconds", "_ratio", "sec_per_iter", "_s", "_us")

# informational columns with no orientation: byte/count volumes (a
# bigger migration moved more state, neither better nor worse) — their
# deltas are reported flat, never as a regression. "_samples" /
# "_shards" / "_plans" cover the skew-observatory evidence counts
# (how many plans/shards a run happened to sample says nothing about
# quality); the skew_*_ratio columns stay lower-is-better via the
# "_ratio" suffix above (less imbalance, less overhead)
_NEUTRAL = ("_bytes", "_arrays", "devices_before", "devices_after",
            "_samples", "_shards", "_plans")


def _lower_better(name: str) -> bool:
    return any(name.endswith(sfx) for sfx in _LOWER_BETTER)


def _neutral(name: str) -> bool:
    return any(name.endswith(sfx) for sfx in _NEUTRAL)


def _num(v: Any) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _from_run_all(doc: Dict[str, Any]) -> Dict[str, float]:
    """Guard-metrics extraction from a run_all.py report, tolerant of
    rounds that predate some configs/metrics."""
    out: Dict[str, float] = {}

    def get(*path: str) -> Optional[float]:
        cur: Any = doc
        for p in path:
            if not isinstance(cur, dict):
                return None
            cur = cur.get(p)
        return _num(cur)

    c3 = doc.get("config3_kmeans") or {}
    km = _num(c3.get("sec_per_iter_fused")) or _num(c3.get("sec_per_iter"))
    if km:
        out["kmeans_iters_per_sec"] = 1.0 / km
    lg = get("config4_logreg", "sec_per_iter_fused")
    if lg:
        out["logreg_iters_per_sec"] = 1.0 / lg
    pr = get("config5_sparse", "pagerank_sec_per_iter")
    if pr:
        out["pagerank_iters_per_sec"] = 1.0 / pr
    for name, path in (
            ("ssvd_seconds", ("config5_sparse", "ssvd_seconds")),
            ("map_sum_gflops", ("config1_map_sum", "gflops")),
            ("dot_tflops", ("config2_dot", "tflops")),
            ("dispatch_overhead_speedup",
             ("dispatch_overhead", "speedup")),
            ("verify_check_vs_cold_ratio",
             ("verify_overhead", "check_vs_cold_ratio")),
            ("obs_overhead_ratio", ("obs_overhead",
                                    "obs_overhead_ratio")),
            ("numerics_off_overhead_ratio",
             ("numerics_overhead", "numerics_off_overhead_ratio")),
            ("resilience_off_overhead_ratio",
             ("resilience_overhead", "resilience_off_overhead_ratio")),
            ("serve_coalesced_speedup",
             ("serving_overhead", "serve_coalesced_speedup")),
            ("serve_off_overhead_ratio",
             ("serving_overhead", "serve_off_overhead_ratio")),
            ("elastic_off_overhead_ratio",
             ("elastic_overhead", "elastic_off_overhead_ratio")),
            ("memgov_off_overhead_ratio",
             ("memgov_overhead", "memgov_off_overhead_ratio")),
            ("calibration_off_overhead_ratio",
             ("calibration_overhead", "calibration_off_overhead_ratio")),
            ("redist_off_overhead_ratio",
             ("redistribution_overhead", "redist_off_overhead_ratio")),
            ("profile_off_overhead_ratio",
             ("profile_overhead", "profile_off_overhead_ratio")),
            ("skew_off_overhead_ratio",
             ("skew_overhead", "skew_off_overhead_ratio")),
            ("skew_on_overhead_ratio",
             ("skew_overhead", "skew_on_overhead_ratio")),
            ("skew_worst_imbalance_ratio",
             ("skew_overhead", "skew_worst_imbalance_ratio")),
            ("skew_sampled_plans",
             ("skew_overhead", "skew_sampled_plans")),
    ):
        v = get(*path)
        if v is not None:
            out[name] = v
    return out


# flat bench.py-record metric names, taken verbatim when numeric
_FLAT_KEYS = (
    "kmeans_iters_per_sec", "pagerank_iters_per_sec",
    "logreg_iters_per_sec", "ssvd_seconds", "gflops_f32_highest",
    "value",
)


def _from_flat(doc: Dict[str, Any]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k in _FLAT_KEYS:
        v = _num(doc.get(k))
        if v is None:
            continue
        if k == "value":
            # bench.py's headline metric, named by its 'metric' field
            name = str(doc.get("metric") or "value")
            unit = str(doc.get("unit") or "").strip()
            out[f"{name}_{unit}" if unit else name] = v
        else:
            out[k] = v
    return out


def extract(doc: Dict[str, Any]) -> Tuple[Dict[str, float],
                                          Optional[str], str]:
    """(metrics, platform, kind) from either record shape."""
    if isinstance(doc.get("parsed"), dict):
        # the committed BENCH_r0x.json artifacts wrap the parsed
        # bench.py record in driver bookkeeping (cmd/rc/tail)
        doc = doc["parsed"]
    if any(k.startswith("config") for k in doc):
        return (_from_run_all(doc), doc.get("platform"), "run_all")
    platform = doc.get("platform") or doc.get("kmeans_platform")
    return (_from_flat(doc), platform, "bench")


def compare(old_doc: Dict[str, Any], new_doc: Dict[str, Any],
            tolerance: float = 0.2,
            thresholds_path: Optional[str] = None,
            allow_platform_change: bool = False) -> Dict[str, Any]:
    from spartan_tpu.utils import benchguard

    old_m, old_plat, old_kind = extract(old_doc)
    new_m, new_plat, new_kind = extract(new_doc)

    metrics: Dict[str, Any] = {}
    regressions = []
    for name in sorted(set(old_m) & set(new_m)):
        o, n = old_m[name], new_m[name]
        entry: Dict[str, Any] = {"old": o, "new": n}
        if _neutral(name):
            entry["verdict"] = "info"  # volume column: no orientation
        elif o > 0:
            ratio = n / o
            entry["ratio"] = round(ratio, 4)
            lower = _lower_better(name)
            worse_by = (ratio - 1.0) if lower else (1.0 - ratio)
            if worse_by > tolerance:
                entry["verdict"] = "regressed"
                regressions.append(
                    f"{name}: {o:.6g} -> {n:.6g} "
                    f"({'+' if lower else '-'}{abs(worse_by) * 100:.1f}% "
                    f"worse, tolerance {tolerance * 100:.0f}%)")
            elif worse_by < -tolerance:
                entry["verdict"] = "improved"
            else:
                entry["verdict"] = "flat"
        else:
            entry["verdict"] = "incomparable"
        metrics[name] = entry
    only_old = sorted(set(old_m) - set(new_m))
    only_new = sorted(set(new_m) - set(old_m))

    # the committed-threshold re-check grades the NEW record exactly
    # the way run_all.py would have
    guard = None
    if new_plat:
        guard = benchguard.check(new_m, new_plat, thresholds_path)
        if not guard["pass"]:
            failed = [k for k, r in guard["results"].items()
                      if r.get("pass") is False]
            regressions.append(
                f"threshold re-check failed on {new_plat}: "
                + ", ".join(failed))

    platform_change = bool(old_plat and new_plat
                           and old_plat != new_plat)
    if platform_change and not allow_platform_change:
        regressions.append(
            f"platform changed {old_plat} -> {new_plat}: the records "
            "are not comparable; pass "
            "--allow-platform-change to downgrade to a warning")

    return {
        "old": {"platform": old_plat, "kind": old_kind,
                "metrics": len(old_m)},
        "new": {"platform": new_plat, "kind": new_kind,
                "metrics": len(new_m)},
        "platform_change": platform_change,
        "tolerance": tolerance,
        "metrics": metrics,
        "only_in_old": only_old,
        "only_in_new": only_new,
        "guard": guard,
        "regressions": regressions,
        "pass": not regressions,
    }


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    tolerance = 0.2
    thresholds = None
    allow_plat = "--allow-platform-change" in argv
    if allow_plat:
        argv.remove("--allow-platform-change")
    if "--tolerance" in argv:
        i = argv.index("--tolerance")
        tolerance = float(argv[i + 1])
        del argv[i:i + 2]
    if "--thresholds" in argv:
        i = argv.index("--thresholds")
        thresholds = argv[i + 1]
        del argv[i:i + 2]
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        with open(argv[0]) as f:
            old_doc = json.load(f)
        with open(argv[1]) as f:
            new_doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"compare: cannot read records: {e}", file=sys.stderr)
        return 2
    report = compare(old_doc, new_doc, tolerance=tolerance,
                     thresholds_path=thresholds,
                     allow_platform_change=allow_plat)
    print(json.dumps(report, indent=2))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
