"""Typed global FLAGS registry.

Capability parity with the reference's flag/config system (SURVEY.md §2.1:
``[U] spartan/config.py`` — global ``FLAGS``, typed flags, per-subsystem
registration, per-optimizer-pass toggles). Re-designed for the TPU build:
no cluster-topology flags (there is no master/worker runtime); instead the
flags gate optimizer passes, mesh construction and profiling, which is what
the benchmark ablations need (SURVEY.md §5 "Config / flag system").
"""

from __future__ import annotations

import argparse
import os
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional


# Global flag-mutation counter: bumped on every value change (set /
# parse / reset) so hot paths can memoize flag-derived keys (e.g.
# expr/base._opt_flags_key) and invalidate on ANY flag write instead
# of re-reading the registry per call. Monotonic; reads are unlocked
# (a stale read just recomputes once).
_mutations = 0


def mutation_count() -> int:
    return _mutations


def _bump() -> None:
    global _mutations
    _mutations += 1


class Flag:
    """A single typed flag with a default and an env-var override."""

    def __init__(self, name: str, default: Any, help: str = "",
                 parser: Callable[[str], Any] = str):
        self.name = name
        self.default = default
        self.help = help
        self.parser = parser
        self._value = default
        env = os.environ.get("SPARTAN_TPU_" + name.upper())
        if env is not None:
            self._value = parser(env)
        # reset() restores the value as configured at definition time
        # (env override included), not the compiled-in default.
        self._initial = self._value

    @property
    def value(self) -> Any:
        return self._value

    @value.setter
    def value(self, v: Any) -> None:
        self._value = v
        _bump()

    def parse(self, text: str) -> None:
        self._value = self.parser(text)
        _bump()

    def reset(self) -> None:
        self._value = self._initial
        _bump()


def _parse_bool(text: str) -> bool:
    return text.lower() in ("1", "true", "yes", "on")


def _parse_int_list(text: str) -> List[int]:
    return [int(x) for x in text.split(",") if x]


class FlagRegistry:
    """Global registry; modules register flags at import time.

    Access as attributes: ``FLAGS.opt_map_fusion``.
    """

    def __init__(self) -> None:
        object.__setattr__(self, "_flags", {})
        object.__setattr__(self, "_lock", threading.Lock())

    def define(self, name: str, default: Any, help: str = "",
               parser: Optional[Callable[[str], Any]] = None) -> Flag:
        with self._lock:
            if name in self._flags:
                return self._flags[name]
            if parser is None:
                if isinstance(default, bool):
                    parser = _parse_bool
                elif isinstance(default, int):
                    parser = int
                elif isinstance(default, float):
                    parser = float
                else:
                    parser = str
            flag = Flag(name, default, help, parser)
            self._flags[name] = flag
            return flag

    def define_bool(self, name: str, default: bool, help: str = "") -> Flag:
        return self.define(name, default, help, _parse_bool)

    def define_int(self, name: str, default: int, help: str = "") -> Flag:
        return self.define(name, default, help, int)

    def define_float(self, name: str, default: float, help: str = "") -> Flag:
        return self.define(name, default, help, float)

    def define_str(self, name: str, default: str, help: str = "") -> Flag:
        return self.define(name, default, help, str)

    def define_int_list(self, name: str, default: List[int],
                        help: str = "") -> Flag:
        return self.define(name, default, help, _parse_int_list)

    def __getattr__(self, name: str) -> Any:
        flags: Dict[str, Flag] = object.__getattribute__(self, "_flags")
        if name in flags:
            return flags[name].value
        raise AttributeError(f"undefined flag: {name}")

    def __setattr__(self, name: str, value: Any) -> None:
        flags: Dict[str, Flag] = object.__getattribute__(self, "_flags")
        if name not in flags:
            raise AttributeError(
                f"undefined flag: {name}; call FLAGS.define() first")
        flags[name].value = value

    def __contains__(self, name: str) -> bool:
        return name in self._flags

    def __iter__(self) -> Iterator[Flag]:
        return iter(self._flags.values())

    def parse_args(self, argv: Optional[List[str]] = None) -> List[str]:
        """Parse ``--flag=value`` / ``--flag value`` CLI args; returns leftovers."""
        parser = argparse.ArgumentParser(add_help=False)
        for flag in self._flags.values():
            parser.add_argument("--" + flag.name, type=str, default=None,
                                help=flag.help)
        ns, rest = parser.parse_known_args(argv)
        for flag in self._flags.values():
            text = getattr(ns, flag.name, None)
            if text is not None:
                flag.parse(text)
        return rest

    def reset_all(self) -> None:
        for flag in self._flags.values():
            flag.reset()

    def snapshot(self) -> Dict[str, Any]:
        return {f.name: f.value for f in self._flags.values()}

    def snapshot_nondefault(self) -> Dict[str, Any]:
        """Flags whose value differs from the compiled-in default —
        the compact attribution record every committed benchmark
        carries (a regression must be attributable to flag state vs
        compile-cache growth without rerunning)."""
        return {f.name: f.value for f in self._flags.values()
                if f.value != f.default}


FLAGS = FlagRegistry()

# Core flags, registered up front so every subsystem can rely on them.
FLAGS.define_bool("opt_map_fusion", True,
                  "Fuse chained elementwise map exprs into one kernel.")
FLAGS.define_bool("opt_reduce_fusion", True,
                  "Fuse a map producer into a consuming reduce.")
FLAGS.define_bool("opt_collapse_cached", True,
                  "Collapse already-evaluated sub-DAGs into leaves.")
FLAGS.define_bool("opt_auto_tiling", True,
                  "Smart-tiling pass: pick shardings via the cost model.")
FLAGS.define_bool(
    "plan_cache", True,
    "Cache the complete evaluation plan (leaf order, out tilings, "
    "compiled executable) keyed on the RAW DAG's structural signature, "
    "so steady-state evaluate() skips the optimizer stack and "
    "re-signing entirely (one traversal + dispatch).")
FLAGS.define_float(
    "tiling_compute_weight", 0.0,
    "Bytes-priced compute weight for NON-contraction nodes in the "
    "smart-tiling cost model (0 = built-in default).")
FLAGS.define_float(
    "tiling_flop_weight", 0.0,
    "Bytes-equivalent cost of one contraction FLOP in the smart-tiling "
    "cost model (0 = per-platform default; calibrate with "
    "tiling_cost.calibrate_flop_weight).")
FLAGS.define_float(
    "tiling_operand_move_weight", 0.0,
    "Weight on GEMM operand-reshard bytes vs output-psum bytes in the "
    "smart-tiling cost model (0 = built-in calibrated default).")
FLAGS.define_float(
    "tiling_memory_weight", 0.0,
    "Soft memory term in the smart-tiling cost model: each candidate "
    "tiling's cost gains weight x its per-chip OUTPUT bytes, so plans "
    "near the HBM budget prefer finer (more parallel) tilings before "
    "the memory governor has to force a full degradation rung. 0 = "
    "off (pure speed). Part of the plan/compile cache keys. See "
    "docs/MEMORY.md.")
FLAGS.define_bool("opt_fold_slices", True,
                  "Fold slice-of-slice and slice-of-map expressions.")
FLAGS.define_int("log_level", 2, "0=debug 1=info 2=warn 3=error")
# The legacy FLAGS.profile whole-dispatch jax.profiler wrap is gone:
# profiling is one entry point now — st.profile(expr) for one-shot
# attribution and FLAGS.profile_sample_every (obs/profile.py) for
# sampled continuous profiling in production; ad-hoc captures go
# through utils/profiling.profile_trace (obs.trace.device_profile).
# The observability layer's own switches (spartan_tpu/obs/) are defined
# where they are consumed and documented here for discoverability:
#   trace                (obs/trace.py, default True)  — record host spans
#       (evaluate/sign/optimize/per-pass/tiling/compile/dispatch/fetch)
#       into the in-memory ring for st.trace_export; <=5% overhead on a
#       steady-state evaluate (benchmarks/obs_overhead.py gate).
#   trace_ring           (obs/trace.py, default 4096)  — max spans kept;
#       older spans drop when the ring wraps.
#   metrics              (obs/metrics.py, default True) — feed the typed
#       counter/gauge/histogram registry behind st.metrics().
#   metrics_hist_window  (obs/metrics.py, default 2048) — samples per
#       histogram for the p50/p95 estimates.
#   audit_numerics       (obs/numerics.py, default False) — compile
#       device-side health words + host callbacks into every node's
#       lowering (st.audit first-bad-node attribution); part of the
#       plan/compile cache keys; zero callbacks compiled when off
#       (benchmarks/numerics_overhead.py <=1% off-path gate).
#   dispatch_timeout_s   (obs/numerics.py, default 0)  — dispatch
#       watchdog: a run exceeding this dumps the in-flight span tree +
#       plan report + last health word to crash_dump_path.
#   crash_dump_path      (obs/numerics.py, default "") — crash-report
#       destination (empty = spartan_tpu_crash_<pid>.json in tmp).
#   cost_ledger          (obs/ledger.py, default True) — record
#       predicted-vs-measured cost per plan (st.ledger); disabled it
#       costs one flag read per dispatch (calibration_overhead gate).
#   cost_ledger_max / calibration_drift_tol (obs/ledger.py, defaults
#       256 / log 2) — ledger entry bound; drift tolerance on
#       |log(pred/actual)| per model before the drift counter bumps.
#   cost_calibration     (obs/ledger.py, default False) — multiply the
#       active profile's per-op-class factors into the tiling DP;
#       cost_calibration_fingerprint (set by st.load_profile) keys
#       calibrated plans apart in the plan/compile caches.
#   flightrec / flightrec_ring (obs/flight.py, defaults True / 4096)
#       — per-request serve-path flight recorder (st.flightrec):
#       submit -> queue -> coalesce -> dispatch -> resolve -> fetch
#       events, ring-bounded, no new locks on the hot paths.
#   profile_sample_every (obs/profile.py, default 0) — sampled
#       continuous device-time profiling: every Nth warm dispatch of a
#       plan is attributed per expr node and folded into the ledger's
#       device columns / plan report / flight recorder; 0 = off (one
#       flag read per dispatch; benchmarks/profile_overhead.py gate).
#   profile_tier (obs/profile.py, default "auto") — attribution tier:
#       auto (XPlane capture-parse, replay fallback) | xplane | replay.
#   profile_max_nodes (obs/profile.py, default 128) — replay-tier
#       node budget per plan.
#   serve_slo_classes / serve_slo_tenants / serve_slo_window
#       (obs/slo.py, defaults "" / "" / 256) — per-tenant latency SLO
#       classes ('name=target_s@objective[:queue_share]'), the tenant
#       -> class map, and the per-class violation window behind the
#       slo_burn_rate gauges + serve SLO-share admission
#       (docs/SERVING.md).
#   monitor / monitor_interval_s / monitor_window (obs/monitor.py,
#       defaults False / 1.0 / 512) — the continuous sampler thread,
#       its cadence, and the bounded time-series store
#       (benchmarks/monitor_overhead.py <=1% off-path gate).
#   monitor_autotune / monitor_drift_patience / monitor_swap_margin /
#       monitor_cooldown_s (obs/monitor.py, defaults False / 3 / 0.05
#       / 30.0) — the closed-loop re-calibration daemon: sustained-
#       drift patience, the modeled-win hysteresis a refitted profile
#       must clear to hot-swap, and the post-attempt cooldown
#       (docs/OBSERVABILITY.md).
#   monitor_burn_threshold / monitor_fallback_rate (obs/monitor.py,
#       defaults 1.0 / 5.0) — detector thresholds for SLO burn and
#       fallback-counter spikes.
#   monitor_fleet_dir    (obs/monitor.py, default "") — rank-snapshot
#       directory behind st.fleet_status() (atomic per-rank files,
#       rank-0 merge).
#   skew_warn_ratio      (obs/skew.py, default 1.5) — shard-imbalance
#       ratio (hottest shard / mesh mean, per node) above which
#       st.skew prints the advisory re-tiling suggestion and the
#       monitor's sustained-imbalance detector counts a breach; the
#       skew observatory itself rides profile_sample_every
#       (benchmarks/skew_overhead.py <=1% off-path gate).
#   serve_model_pricing  (serve/engine.py, default True) — price
#       deadline shedding + the ledger's service rows with the
#       calibrated cost model instead of the raw queue EMA (falls
#       back per request until the DP scale warms).
# The resilience layer's switches (spartan_tpu/resilience/) likewise
# live with their consumers (docs/RESILIENCE.md):
#   resilience           (engine.py, default True)  — master switch for
#       the in-evaluate policy engine (classify + retry + OOM degrade).
#   retry_max / retry_backoff_s / retry_backoff_max_s / retry_budget
#       (engine.py, defaults 3 / 0.05 / 2.0 / 32) — transient-retry
#       policy: attempts per episode, jittered exponential backoff,
#       lifetime budget per plan.
#   oom_degrade          (degrade.py, default True)  — walk the
#       finer-tiling -> fusion-off -> chunked ladder on OOM; each rung
#       keyed into the plan/compile caches.
#   degrade_chunks       (degrade.py, default 0)     — row blocks for
#       the chunked rung (0 = one per mesh device).
#   fault_inject / fault_seed (faults.py, defaults "" / 0) — seeded
#       chaos spec ('transient@2,oom@4x3,slow@1=0.5,io@0'), installed
#       by st.initialize() or st.chaos().
#   hbm_budget_bytes / memory_governor (memory.py, defaults 0 / True)
#       — predictive memory governor (docs/MEMORY.md): per-plan
#       peak-HBM model, ladder rung chosen BEFORE the first dispatch
#       when the prediction exceeds the budget, serve reservation
#       ledger. 0 = auto-detect from device memory_stats (None on
#       CPU: governor inert unless set explicitly).
#   loop_restore_max     (loop_ckpt.py, default 3)   — checkpoint
#       restores per checkpointed st.loop before the failure escapes.
#   integrity_check      (integrity.py, default False) — the SDC
#       sentinel: sampled per-shard checksum + redundant re-execution
#       on a rotated device assignment (rides profile_sample_every);
#       a disagreement discards the result (class 'sdc') and strikes
#       the implicated devices (benchmarks/integrity_overhead.py <=1%
#       off-path gate).
#   sdc_quarantine_strikes (integrity.py, default 3) — in-window
#       strikes that confirm a suspect device and trigger its planned
#       quarantine (rebuild_mesh exclusion + planner-priced rehome).
FLAGS.define_bool(
    "trace_annotations", True,
    "Wrap every expr node's kernel body in jax.named_scope during "
    "tracing, so device profiles (jax.profiler / Perfetto) attribute "
    "XLA ops back to expr nodes. Trace-time-only cost; turn off to "
    "shave cold-compile time.")
FLAGS.define_bool(
    "trace_loop_steps", False,
    "Emit one host callback per st.loop iteration (jax.debug.callback "
    "on the step index): the trace ring gains per-step 'loop_step' "
    "spans with REAL per-iteration dispatch times instead of one "
    "opaque fori_loop blob. Changes the lowered program (the flag is "
    "part of the loop's structural signature), so toggling recompiles; "
    "off by default — per-step callbacks serialize device->host.")
FLAGS.define_str(
    "profile_dir", "/tmp/spartan_tpu_profile",
    "Default destination for EXPLICIT device-profile captures "
    "(utils/profiling.profile_trace -> obs.trace.device_profile; view "
    "in TensorBoard/Perfetto). st.profile's XPlane tier and the "
    "profile_sample_every sampler capture into throwaway temp dirs — "
    "they parse and delete, never writing here.")
FLAGS.define_int("default_mesh_1d", 0,
                 "If >0, force the default mesh to this many devices.")
FLAGS.define_str("placement", "auto",
                 "Tile placement strategy: auto|row|col|block|replicated")
FLAGS.define_bool("check_determinism", False,
                  "Debug mode: evaluate twice and assert bitwise equality.")
FLAGS.define_bool("use_cpp_extent", True,
                  "Use the C++ extent-algebra extension when built.")
_verify_passes_flag = FLAGS.define_bool(
    "verify_passes", False,
    "Bracket every optimizer pass with the invariant checker "
    "(analysis/passes.py): shape/dtype/leaf preservation + DAG "
    "well-formedness, failures naming the offending pass. Runs only "
    "on plan-cache misses. Also honored via SPARTAN_VERIFY_PASSES=1; "
    "the test suite enables it by default.")
FLAGS.define_bool(
    "verify_evaluate", False,
    "Run st.check (DAG verifier + plan-time lints: use-after-donate, "
    "double-donation, tiling consistency) on evaluate()'s plan-cache "
    "MISS path, before the optimizer. Hits stay dispatch-bound.")

# The documented switch is SPARTAN_VERIFY_PASSES (no package prefix);
# honor it with the same precedence as the prefixed env var, and make
# it survive FLAGS.reset_all() like any definition-time override.
_env = os.environ.get("SPARTAN_VERIFY_PASSES")
if _env is not None and "SPARTAN_TPU_VERIFY_PASSES" not in os.environ:
    _verify_passes_flag._value = _parse_bool(_env)
    _verify_passes_flag._initial = _verify_passes_flag._value
del _verify_passes_flag, _env
