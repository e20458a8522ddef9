"""Cold-vs-warm restart benchmark + the warm-start layer's cost gate
(ISSUE 13: the rolling-restart contract for the fleet story).

Two measurements, one JSON line:

* **Process-restart harness** (``measure_restart``): a child process
  builds a small plan set (map+reduce, dot, an ``st.loop`` k-means
  chain), evaluates it against a shared ``persist_cache_dir``, and
  reports time-to-first-result, XLA compiles and result bytes. The
  parent runs it COLD (empty store) then WARM (fresh process, populated
  store): the warm child must serve the set with **zero recompiles**
  and **bit-equal** results — ``warm_recompiles`` / ``bit_equal`` are
  the acceptance facts, ``recompiles_avoided`` and the
  cold/warm time-to-first-result pair are the fleet-story numbers.
  TTFR is measured from child interpreter start (imports + backend
  init included — that is what a rolling restart actually waits for).

* **Off-path cost** (``measure_overhead``): steady-state k-means-step
  hit path with the real ``expr.base`` persist hooks present but
  ``persist_cache_dir`` unset (the production default: hits never
  touch the layer at all; the miss path pays one flag read) vs a null
  shim with the hooks swapped out. ``warmstart_off_overhead_ratio`` =
  off/base - 1 is the committed <=0.01 gate
  (benchmarks/thresholds.json) for cpu AND tpu — leaving warm-start
  off must be free. The persist-ON arm's store/load costs are the
  knob's price (reported via the restart harness, not gated).

Usage: python benchmarks/warm_start.py [--small] [--iters N]
       python benchmarks/warm_start.py --child <cache_dir> <n>
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T0 = time.perf_counter()  # child mode: interpreter-start anchor


class _NullPersist:
    """What expr/base.py looks like with no warm-start layer compiled
    in: the store is never consulted, nothing is ever persisted."""

    class _Null:
        pass

    @staticmethod
    def active():
        return None

    @staticmethod
    def lookup(plan_key, mesh):
        return None, None, None

    @staticmethod
    def maybe_store(plan, executable, mesh):
        return False

    @staticmethod
    def evict_stale():
        return 0

    @staticmethod
    def note_build(*a, **k):
        return None

    @staticmethod
    def take_build_source():
        return None


def measure_overhead(iters: int = 100, n: int = 4096, d: int = 32,
                     k: int = 16) -> dict:
    import spartan_tpu as st
    from spartan_tpu.examples.kmeans import kmeans_step
    from spartan_tpu.expr import base as expr_base
    from spartan_tpu.expr.base import ValExpr
    from spartan_tpu.utils import profiling

    rng = np.random.RandomState(0)
    pts = st.from_numpy(rng.rand(n, d).astype(np.float32))
    c = st.as_expr(rng.rand(k, d).astype(np.float32)).evaluate()

    real = expr_base.persist_mod

    def step(cur):
        return kmeans_step(pts, ValExpr(cur), k).evaluate()

    c = step(step(c))  # warm the plan: every measured iter is a hit

    # ABBA-interleaved block pairs + LOWER-QUARTILE of pairwise
    # block-median ratios (the redistribution-gate estimator): on the
    # hit path the two arms run provably identical code — hits never
    # consult the persist layer — so the true ratio is exactly 0 and
    # the estimator only needs to reject the 1-core box's one-sided
    # timesharing bursts (which only ADD time to whichever block they
    # hit) while still tripping on a systematic shift, which moves
    # every pair.
    block = 5
    pairs = max(12, iters // block)
    blocks = {"base": [], "off": []}
    try:
        for i in range(pairs):
            order = (("base", "off") if i % 2 == 0
                     else ("off", "base"))
            for arm in order:
                expr_base.persist_mod = (_NullPersist if arm == "base"
                                         else real)
                walls = []
                for _ in range(block):
                    with profiling.stopwatch() as sw:
                        c = step(c)
                        c.glom()
                    walls.append(sw.elapsed)
                blocks[arm].append(float(np.median(walls)))
    finally:
        expr_base.persist_mod = real

    t_base = float(np.median(blocks["base"]))
    t_off = float(np.median(blocks["off"]))
    ratios = [o / b for o, b in zip(blocks["off"], blocks["base"])]
    return {
        "iters": pairs * block,
        "shape": [n, d, k],
        "wall_us_per_iter_base": round(t_base * 1e6, 1),
        "wall_us_per_iter_persist_off": round(t_off * 1e6, 1),
        "warmstart_off_overhead_ratio": round(
            max(0.0, float(np.percentile(ratios, 25)) - 1.0), 4),
        "warmstart_off_overhead_ratio_median": round(
            max(0.0, float(np.median(ratios)) - 1.0), 4),
    }


# -- the process-restart harness -----------------------------------------


def child(cache_dir: str, n: int) -> None:
    """One 'replica': build + serve the benchmark plan set against the
    shared store; print the restart facts as one JSON line."""
    import spartan_tpu as st
    from spartan_tpu.examples.kmeans import kmeans_step
    from spartan_tpu.utils import profiling

    st.FLAGS.persist_cache_dir = cache_dir
    rng = np.random.RandomState(0)
    x = st.from_numpy(rng.rand(n, n).astype(np.float32))
    y = st.from_numpy(rng.rand(n, n).astype(np.float32))
    pts = st.from_numpy(rng.rand(4 * n, 16).astype(np.float32))
    c0 = rng.rand(8, 16).astype(np.float32)

    exprs = [
        lambda: ((x + y) * 3.0 - x).sum(),
        lambda: st.dot(x, y).sum(axis=0),
        lambda: st.loop(3, lambda c: kmeans_step(pts, c, 8),
                        st.as_expr(c0)),
    ]
    results = []
    ttfr = None
    for build in exprs:
        out = np.asarray(build().evaluate().glom())
        if ttfr is None:
            # time-to-FIRST-result, from interpreter start: what a
            # restarted replica's first client actually waits
            ttfr = time.perf_counter() - _T0
        results.append(out)
    counters = st.metrics()["counters"]
    print(json.dumps({
        "ttfr_s": round(ttfr, 4),
        "wall_s": round(time.perf_counter() - _T0, 4),
        "compiles": profiling.counters().get("compiles", 0),
        "persist_hits": counters.get("persist_hits", 0),
        "persist_stores": counters.get("persist_stores", 0),
        "results_hex": [np.ascontiguousarray(r).tobytes().hex()[:64]
                        for r in results],
        "plans": len(exprs),
    }), flush=True)


def _run_child(cache_dir: str, n: int, timeout: float = 600) -> dict:
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         cache_dir, str(n)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"warm_start child failed rc={out.returncode}: "
            f"{out.stderr.strip()[-400:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def measure_restart(n: int = 256) -> dict:
    """Cold child (empty store) then warm child (fresh process, same
    store): the rolling-restart acceptance measurement."""
    with tempfile.TemporaryDirectory() as tmp:
        cache = os.path.join(tmp, "persist")
        cold = _run_child(cache, n)
        warm = _run_child(cache, n)
    return {
        "plans": cold["plans"],
        "cold_ttfr_s": cold["ttfr_s"],
        "warm_ttfr_s": warm["ttfr_s"],
        "cold_wall_s": cold["wall_s"],
        "warm_wall_s": warm["wall_s"],
        "warm_restart_speedup": round(
            cold["wall_s"] / max(warm["wall_s"], 1e-9), 3),
        "cold_compiles": cold["compiles"],
        "warm_recompiles": warm["compiles"],  # MUST be 0
        "recompiles_avoided": warm["persist_hits"],
        "cold_persist_stores": cold["persist_stores"],
        "bit_equal": cold["results_hex"] == warm["results_hex"],
    }


def measure(iters: int = 100, n: int = 4096,
            restart_n: int = 256) -> dict:
    rec = {"metric": "warm_start"}
    # children first: until measure_overhead imports JAX this process
    # holds no device, so each child can take the chip
    rec["restart"] = measure_restart(n=restart_n)
    rec.update(measure_overhead(iters=iters, n=n))
    # gate-visible aliases (utils/benchguard grades flat keys)
    rec["warm_recompiles"] = rec["restart"]["warm_recompiles"]
    rec["warm_restart_bit_equal"] = rec["restart"]["bit_equal"]
    return rec


def main() -> None:
    if len(sys.argv) >= 4 and sys.argv[1] == "--child":
        child(sys.argv[2], int(sys.argv[3]))
        return
    iters = 100
    if "--iters" in sys.argv:
        iters = int(sys.argv[sys.argv.index("--iters") + 1])
    small = "--small" in sys.argv
    out = measure(iters=iters, n=512 if small else 4096,
                  restart_n=128 if small else 256)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
