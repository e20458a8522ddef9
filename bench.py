"""Benchmark runner: prints ONE JSON line for the driver.

North-star metric (BASELINE.json:2): sustained GFLOPS/chip on dense
4096x4096 dot through the spartan_tpu expr stack PLUS k-means
iterations/sec (1M x 128, k=64 — config 3, BASELINE.json:9), on the
TPU. The dot chain runs as ONE on-device ``st.loop`` (lax.fori_loop)
of K matmuls with a single result fetch, so the reported time includes
one dispatch and one fetch in the denominator (a lower bound on device
throughput).  Each hop renormalizes by the running max so hundreds of
iterations stay finite.

Precision is PINNED AND REPORTED (round-3 verdict Weak #5): the
headline number runs at the platform default — on TPU that multiplies
in bf16 with f32 accumulation — and a second stage measures
``precision=HIGHEST`` (full-f32 6-pass) so the number is honest against
either peak.  The emitted line carries ``precision`` plus the
``_f32_highest`` variant alongside.

``vs_baseline`` divides by the measured 8-process CPU
Spartan-equivalent denominator (baselines/cpu_baseline.json, from
baselines/spartan_cpu_baseline.py per SURVEY.md §6) — the >=10x target
of BASELINE.json:5.  ``kmeans_vs_baseline`` does the same for
iters/sec against the baseline's extrapolated 1M-row figure.

Each stage runs in a child process the parent can kill at its
timebox; the parent never imports JAX, so one child at a time holds
the chip. A probe stage first proves the default platform is a TPU:
without one the run fails (non-zero exit, diagnostic JSON line) —
there is no CPU fallback. The parent prints the merged JSON line, or a
diagnostic JSON line (never a raw traceback) if every stage dies.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

N = 4096
KM_N, KM_D, KM_K, KM_ITERS = 1_000_000, 128, 64, 20

# (K, reps, per-stage timeout seconds): the small stage lands a number
# early; K=512 is the headline measurement. Timeboxes are generous for
# first compiles (~20-40 s).
STAGES = [(1, 1, 420), (512, 3, 600)]
# Fail-fast probe: at most this long to prove the default platform is
# a TPU that can compile + run + fetch a trivial jit, before any real
# timebox starts.
STAGE_PROBE_TIMEOUT = 90
# HIGHEST-precision stage: ~6 f32 passes per MXU matmul, so a shorter
# chain keeps the stage a few seconds of device time.
STAGE_HIGHEST = (64, 3, 420)
STAGE_KMEANS_TIMEOUT = 420


def _build(st, ea, eb, k, precision):
    # The renorm keeps the chain finite; it is pure HBM overhead next
    # to the MXU matmuls, so amortize it: with |entries| <= 1 after a
    # renorm, 8 unnormalized hops grow magnitudes at most N^8 = 2^96
    # (f32 max 2^127) — renormalizing every 8th hop is the same honest
    # finite computation with 1/8th the renorm passes (measured ~30%
    # of chain time at every-hop renorm on v5e).
    def renorm(c):
        return c / st.absolute(c).max()

    if k % 8 == 0:
        def body8(c):
            for _ in range(8):
                c = st.dot(c, eb, precision=precision)
            return renorm(c)

        return st.loop(k // 8, body8, ea).sum()

    def body(c):
        return renorm(st.dot(c, eb, precision=precision))

    return st.loop(k, body, ea).sum()


def _baseline(*path_keys):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baselines", "cpu_baseline.json")
    if os.path.exists(path):
        with open(path) as f:
            node = json.load(f)
        for key in path_keys:
            node = node.get(key, {}) if isinstance(node, dict) else None
            if node is None:
                return None
        return node if isinstance(node, (int, float)) else None
    return None


def _crash_path(stage: str) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"bench_crash_{stage}.json")


def _arm_stage_forensics(stage: str) -> None:
    """Worker-side crash forensics (call AFTER spartan_tpu imports).

    Two layers, both writing ``bench_crash_<stage>.json``:

    * a SIGTERM handler — the parent now SIGTERMs a timed-out stage
      (grace period) before SIGKILL, so the child exports its partial
      Chrome trace, ``st.metrics()`` snapshot, in-flight span tree and
      last health word before dying: a hung stage leaves forensics
      instead of nothing. Since the
      prediction-loop PR the dump also folds in the flight recorder's
      per-request timelines (which serve requests were in flight, with
      their latency decomposition) and the cost ledger's
      predicted-vs-measured state (dump_crash does this for every
      caller);
    * the numerics dispatch watchdog (``FLAGS.dispatch_timeout_s``,
      armed by the parent via SPARTAN_TPU_DISPATCH_TIMEOUT_S) — fires
      from INSIDE a hung dispatch with the in-flight tree, before the
      parent's timebox is even reached.
    """
    import signal

    from spartan_tpu.obs import numerics
    from spartan_tpu.utils.config import FLAGS

    path = _crash_path(stage)
    if not FLAGS.crash_dump_path:
        FLAGS.crash_dump_path = path

    def _dump(signum, frame):
        try:
            numerics.dump_crash(
                path, reason=f"stage {stage}: SIGTERM (parent timebox)",
                chrome_trace=True)
        except Exception:
            pass
        finally:
            os._exit(75)

    signal.signal(signal.SIGTERM, _dump)


def _env_diag() -> dict:
    """Active-FLAGS snapshot (non-default values only) + plan/compile
    cache sizes at stage end. Rides every stage's JSON line into
    ``stage_diags`` (ROADMAP 'Perf trajectory' follow-up: the r05 TPU
    cold-start timeouts can't be attributed to PR 2-5 flag defaults vs
    compile-cache growth because no round recorded either — from this
    round on the committed artifact carries both)."""
    from spartan_tpu.expr import base as expr_base
    from spartan_tpu.utils.config import FLAGS

    return {"flags_nondefault": FLAGS.snapshot_nondefault(),
            "plan_cache_size": expr_base.plan_cache_size(),
            "compile_cache_size": expr_base.compile_cache_size()}


def _plan_diag() -> dict:
    """Plan-cache hit/miss counters and per-phase host timers for the
    stage's JSON line + a stderr diagnostic (utils/profiling): a
    steady-state stage must show hit_rate ~1.0 and near-zero optimize
    time — the dispatch-bound contract of the plan cache."""
    from spartan_tpu import obs
    from spartan_tpu.utils import profiling

    stats = profiling.plan_cache_stats()
    phases = {name: round(sec * 1e3, 2)
              for name, sec in sorted(profiling.phase_seconds().items())}
    # per-phase p95 from the obs histograms (st.metrics()): tail
    # latency per evaluate, where the cumulative sums above can't
    # separate one slow dispatch from many fast ones
    p95_ms = {name.split(":", 1)[1]: round(h["p95"] * 1e3, 3)
              for name, h in sorted(obs.metrics()["histograms"].items())
              if name.startswith("phase:")}
    print(f"[bench] plan cache: hits={stats['plan_hits']} "
          f"misses={stats['plan_misses']} compiles={stats['compiles']} "
          f"phase_ms={phases}", file=sys.stderr)
    return {"hits": stats["plan_hits"], "misses": stats["plan_misses"],
            "compiles": stats["compiles"], "phase_ms": phases,
            "phase_p95_ms": p95_ms}


def worker_probe() -> None:
    """Tiny jit probe on the default platform: device enumeration ->
    compile -> run -> fetch of a 256x256 dot. Prints one JSON line with
    per-phase seconds so a timeout's LAST line (if any) names the phase
    that died; the parent fails the run unless the platform is a TPU
    and every phase finished."""
    import numpy as np

    phases = {}
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    phases["init_s"] = round(time.perf_counter() - t0, 3)
    print(f"[probe] devices ok: {platform}", file=sys.stderr, flush=True)
    a = jnp.asarray(np.random.RandomState(0).rand(256, 256)
                    .astype(np.float32))
    t1 = time.perf_counter()
    f = jax.jit(lambda x: (x @ x).sum())
    out = f(a)
    out.block_until_ready()
    phases["compile_run_s"] = round(time.perf_counter() - t1, 3)
    t2 = time.perf_counter()
    val = float(out)
    phases["fetch_s"] = round(time.perf_counter() - t2, 3)
    assert np.isfinite(val)
    print(json.dumps({
        "metric": "jit_probe", "probe": "ok", "platform": platform,
        "seconds": round(time.perf_counter() - t0, 3), **phases,
    }), flush=True)


def worker_dot(k: int, reps: int, precision: str | None) -> None:
    """Measure the dot chain at loop length k; print one JSON line."""
    import numpy as np

    import jax
    platform = jax.devices()[0].platform
    import spartan_tpu as st

    _arm_stage_forensics(
        f"dot_k{k}" + ("_highest" if precision == "highest" else ""))
    rng = np.random.RandomState(0)
    ea = st.from_numpy(rng.rand(N, N).astype(np.float32))
    eb = st.from_numpy(rng.rand(N, N).astype(np.float32))

    def run(kk: int) -> float:
        t0 = time.perf_counter()
        val = float(_build(st, ea, eb, kk, precision).glom())
        assert np.isfinite(val)
        return time.perf_counter() - t0

    run(k)  # warmup at the same k: compiles once; reps hit the cache
    best = min(run(k) for _ in range(reps))
    gflops = 2.0 * N * N * N * k / best / 1e9
    plan = _plan_diag()
    prec_label = ("f32_highest" if precision == "highest"
                  else "default_bf16_multiply_f32_accum")
    print(json.dumps({
        "metric": "dense_dot_4096_gflops_per_chip",
        "value": round(gflops, 2),
        "unit": "GFLOPS",
        "vs_baseline": None,
        "platform": platform,
        "precision": prec_label,
        "loop_k": k,
        "plan_cache": plan,
        "env": _env_diag(),
    }), flush=True)


def worker_kmeans(iters: int, reps: int) -> None:
    """Measure k-means iters/sec at 1M x 128, k=64 (config 3)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    from spartan_tpu.kernels import kmeans as kk

    _arm_stage_forensics("kmeans")
    n, d, k = KM_N, KM_D, KM_K
    rng = np.random.RandomState(0)
    pts_np = rng.rand(n, d).astype(np.float32)
    centers0 = jnp.asarray(pts_np[:k].copy())
    block = kk._BLOCK  # pad to the kernel's block so supports() holds
    npad = -(-n // block) * block
    if kk.supports(npad, d, k):
        # fused Pallas iteration kernel (kernels/kmeans.py): one VMEM pass
        # per iteration, all iterations in one dispatch
        pts = jnp.concatenate(
            [jnp.asarray(pts_np), jnp.zeros((npad - n, d), jnp.float32)])
        valid = n if npad != n else None

        def run_iters(m):
            return kk.run(pts, centers0, k, jnp.int32(m), valid_rows=valid)
    else:
        # expr path (kernel unsupported here): the framework's own
        # distributed iteration (examples/kmeans.py kmeans_step — map2
        # argmin + segment-sum + all-reduce), all iterations as one
        # st.loop dispatch — this measures the product under test, not
        # a hand-rolled jnp stand-in
        import spartan_tpu as st
        from spartan_tpu.examples.kmeans import kmeans_step

        points_e = st.from_numpy(pts_np)

        def run_iters(m):
            return st.loop(int(m),
                           lambda c: kmeans_step(points_e, c, k),
                           st.as_expr(np.asarray(centers0))).glom()

    def run(m) -> float:
        t0 = time.perf_counter()
        out = np.asarray(run_iters(m))
        assert np.isfinite(out).all()
        return time.perf_counter() - t0

    run(iters)  # warmup/compile at the measured loop length
    best = min(run(iters) for _ in range(reps))
    ips = iters / best
    print(json.dumps({
        "metric": "kmeans_1m_iters_per_sec",
        "value": round(ips, 3),
        "unit": "iters/s",
        "platform": platform,
        "iters": iters,
        "plan_cache": _plan_diag(),
        "env": _env_diag(),
    }), flush=True)


def worker_aux(reps: int) -> None:
    """Guard metrics for configs 4-5 (pagerank / logreg / ssvd) at full
    BASELINE sizes; one JSON line of dispatch-amortized medians. The
    parent grades them against benchmarks/thresholds.json (round-4
    verdict Weak #2: these paths had no machine-checked floor)."""
    import numpy as np

    import jax
    platform = jax.devices()[0].platform
    import spartan_tpu as st
    from spartan_tpu.array.sparse import SparseDistArray
    from spartan_tpu.examples.pagerank import pagerank
    from spartan_tpu.examples.regression import logistic_regression
    from spartan_tpu.examples.ssvd import ssvd

    _arm_stage_forensics("aux")

    def med(fn):
        fn()  # warmup/compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    rng = np.random.RandomState(4)
    n, deg = 1_000_000, 16
    rows = np.repeat(np.arange(n), deg)
    cols = rng.randint(0, n, n * deg)
    links = SparseDistArray.from_coo(
        rows, cols, np.ones(n * deg, np.float32), (n, n))
    pr = med(lambda: pagerank(links, num_iter=10)) / 10

    nl, d = 10_000_000, 32
    X = st.from_numpy(rng.rand(nl, d).astype(np.float32))
    yv = st.from_numpy((rng.rand(nl) > 0.5).astype(np.float32))
    lg = med(lambda: logistic_regression(X, yv, num_iter=10)) / 10

    a = st.from_numpy(rng.rand(8192, 512).astype(np.float32))
    sv = med(lambda: ssvd(a, rank=32))

    print(json.dumps({
        "pagerank_iters_per_sec": round(1.0 / pr, 3),
        "logreg_iters_per_sec": round(1.0 / lg, 3),
        "ssvd_seconds": round(sv, 4),
        "platform": platform,
        "env": _env_diag(),
    }), flush=True)


def worker_chaos(iters: int, seed: int) -> None:
    """Opt-in chaos stage (``bench.py --chaos``): run the k-means loop
    as a checkpointed ``st.loop`` with seeded transient faults
    injected at real dispatch seams (spartan_tpu/resilience), and
    report what the policy engine recovered. Prints one JSON line;
    forensics ride the same SIGTERM/watchdog path as every other
    stage (``_arm_stage_forensics``)."""
    import numpy as np
    import tempfile

    import jax
    platform = jax.devices()[0].platform
    import spartan_tpu as st
    from spartan_tpu.examples.kmeans import kmeans_step

    _arm_stage_forensics("chaos")
    n, d, k = 100_000, 32, 16
    rng = np.random.RandomState(seed)
    pts_np = rng.rand(n, d).astype(np.float32)
    c0 = pts_np[:k].copy()
    points = st.from_numpy(pts_np)
    every = max(1, iters // 4)

    def run(ckpt_dir):
        return np.asarray(st.loop(
            iters, lambda c: kmeans_step(points, c, k),
            st.as_expr(c0), checkpoint_every=every,
            checkpoint_path=ckpt_dir).glom())

    with tempfile.TemporaryDirectory() as tmp:
        clean = run(os.path.join(tmp, "clean"))  # fault-free reference
        st.FLAGS.retry_backoff_s = 0.01
        t0 = time.perf_counter()
        # a transient fault on the first segment dispatch and a
        # synthetic OOM on the third (each segment is one dispatch)
        with st.chaos("transient@0,oom@2", seed=seed):
            faulted = run(os.path.join(tmp, "chaos"))
        wall = time.perf_counter() - t0
    counters = st.metrics()["counters"]
    print(json.dumps({
        "metric": "chaos_recovery",
        "iters": iters,
        "recovered_iterations": int(iters),
        "matches_fault_free": bool(np.allclose(clean, faulted,
                                               rtol=1e-5, atol=1e-6)),
        "max_abs_diff": float(np.max(np.abs(clean - faulted))),
        "faults_injected": counters.get("resilience_faults_injected", 0),
        "retries": counters.get("resilience_retries", 0),
        "degrades": counters.get("resilience_degrades", 0),
        "loop_checkpoints": counters.get(
            "resilience_loop_checkpoints", 0),
        "seconds": round(wall, 3),
        "platform": platform,
        "env": _env_diag(),
    }), flush=True)


def worker_serve(clients: int, per_client: int) -> None:
    """Opt-in serving stage (``bench.py --serve``): open-loop
    many-client load through ``spartan_tpu/serve`` vs a serial
    ``evaluate()`` loop (benchmarks/serving_latency.py) on the default
    platform. One JSON line: p50/p99 request latency, throughput,
    coalescing hit ratio, the >=3x coalesced-speedup gate and the
    <=1% serve-off overhead gate (graded by the parent against
    benchmarks/thresholds.json)."""
    import jax
    platform = jax.devices()[0].platform
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import serving_latency as sl

    _arm_stage_forensics("serve")
    rec = sl.measure(clients=clients, per_client=per_client)
    rec["platform"] = platform
    rec["env"] = _env_diag()
    print(json.dumps(rec), flush=True)


def _benchguard():
    """Load the guard module by file path — the parent process never
    imports spartan_tpu/jax (a hung PJRT init must stay killable)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "spartan_tpu", "utils", "benchguard.py")
    spec = importlib.util.spec_from_file_location("_benchguard", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_stage(mode, args, timeout):
    """Run one worker stage with a hard timebox the child cannot defeat.

    subprocess.run's TimeoutExpired path calls communicate() with no
    timeout after kill() — if the child blocks un-killably inside PJRT
    init (D-state) or forked helpers hold the pipes, the parent hangs
    forever.  So: own session (killpg reaches helpers), SIGTERM first
    with a bounded grace period (the worker's forensics handler exports
    its partial Chrome trace + metrics to bench_crash_<stage>.json —
    see _arm_stage_forensics), then SIGKILL, bounded reap, and if the
    group still won't die, abandon it and move on.  The numerics
    dispatch watchdog is armed at 0.8x the timebox via env so a hang
    INSIDE one dispatch dumps its in-flight span tree before any
    signal arrives.  Returns (stdout, stderr, rc) with rc=None on
    timeout.
    """
    import signal

    env = dict(os.environ)
    env.setdefault("SPARTAN_TPU_DISPATCH_TIMEOUT_S",
                   str(round(0.8 * timeout, 1)))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), mode]
        + [str(a) for a in args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True, env=env)
    try:
        out, err = proc.communicate(timeout=timeout)
        return out, err, proc.returncode
    except subprocess.TimeoutExpired:
        out = err = ""
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            # grace period: the forensics handler writes the crash
            # file then _exits; a child hung un-interruptibly inside
            # PJRT never runs it, hence the bounded wait
            out, err = proc.communicate(timeout=20)
            return out, err, None
        except subprocess.TimeoutExpired:
            pass
        except (ProcessLookupError, PermissionError):
            pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            # keep whatever the child managed to print — it is the only
            # diagnostic of WHY the stage had to be killed
            out, err = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            pass  # un-reapable: abandon the group, keep the bench alive
        return out, err, None


def _parse_stage(out):
    line = out.strip().splitlines()[-1] if out and out.strip() else ""
    try:
        return json.loads(line)
    except (json.JSONDecodeError, ValueError):
        return None


def _diag(stage, reason, rc=None, err="", note=None):
    """One structured stage diagnostic (round-5 follow-up: stage_diags
    used to be a concatenated string the driver could not parse)."""
    d = {"stage": stage, "reason": reason, "rc": rc}
    tail = (err or "").strip().splitlines()[-3:]
    if tail:
        d["stderr_tail"] = tail
    if note:
        d["note"] = note
    crash = _crash_path(stage)
    if os.path.exists(crash):
        d["crash_file"] = os.path.basename(crash)
    return d


def _ok_diag(stage_name, stage):
    """Success diagnostic carrying the worker's ``env`` record (active
    non-default FLAGS + plan/compile-cache sizes, ``_env_diag``) — so
    every stage in ``stage_diags``, not just the failures, leaves the
    state the r05 cold-start postmortem was missing. Pops ``env`` off
    the stage record: it lives in the diags, not the headline line."""
    d = {"stage": stage_name, "reason": "ok"}
    if isinstance(stage, dict):
        d.update(stage.pop("env", None) or {})
    return d


def main() -> None:
    result = None
    diags = []
    stages = ()
    # fail-fast probe: prove the default platform is a TPU that can
    # finish ONE tiny jit before committing the 420/600 s stages to it
    t0 = time.perf_counter()
    out, err, rc = _run_stage("--worker-probe", [], STAGE_PROBE_TIMEOUT)
    probe = _parse_stage(out)
    if rc is None or probe is None or probe.get("probe") != "ok" \
            or probe.get("platform") != "tpu":
        reason = (f"killed after {STAGE_PROBE_TIMEOUT}s timeout"
                  if rc is None else "no JSON output" if probe is None
                  else f"platform {probe.get('platform')!r} is not tpu")
        diags.append(_diag("probe", reason, rc=rc, err=err))
        print(f"[bench] jit probe failed ({reason})", file=sys.stderr)
    else:
        diags.append({"stage": "probe", "reason": "ok", **{
            k: probe[k] for k in ("platform", "seconds", "init_s",
                                  "compile_run_s", "fetch_s")
            if k in probe}})
        print(f"[bench] jit probe ok on {probe.get('platform')} in "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        stages = STAGES
    for k, reps, timeout in stages:
        t0 = time.perf_counter()
        out, err, rc = _run_stage("--worker-dot", [k, reps, "default"],
                                  timeout)
        if rc is None:
            diags.append(_diag(f"dot_k{k}",
                               f"killed after {timeout}s timeout",
                               err=err))
            print(f"[bench] stage K={k} timed out", file=sys.stderr)
            continue
        stage = _parse_stage(out)
        if stage is None:
            diags.append(_diag(f"dot_k{k}", "no JSON output", rc=rc,
                               err=err))
            print(f"[bench] stage K={k} failed rc={rc}", file=sys.stderr)
            continue
        result = stage
        diags.append(_ok_diag(f"dot_k{k}", stage))
        print(f"[bench] stage K={k} ok in {time.perf_counter() - t0:.1f}s:"
              f" {stage['value']} {stage['unit']}", file=sys.stderr)
    if result is not None:
        cpu_dot = _baseline("dot_4096", "gflops")
        if cpu_dot:
            result["vs_baseline"] = round(result["value"] / cpu_dot, 2)

        # HIGHEST-precision variant (skip when even the default-precision
        # chain was too slow to refine)
        kh, rh, th = STAGE_HIGHEST
        per_dot = 2.0 * N * N * N / (result["value"] * 1e9)
        if per_dot * 6 * kh * (rh + 1) > 0.8 * th:
            diags.append(_diag(
                f"dot_k{kh}_highest", "skipped",
                note=f"predicted {per_dot * 6 * kh * (rh + 1):.0f}s > "
                     f"{th}s box"))
        else:
            out, err, rc = _run_stage("--worker-dot", [kh, rh, "highest"],
                                      th)
            hi = _parse_stage(out)
            if hi is not None:
                result["gflops_f32_highest"] = hi["value"]
                diags.append(_ok_diag(f"dot_k{kh}_highest", hi))
                print(f"[bench] highest-precision stage: {hi['value']} "
                      f"GFLOPS", file=sys.stderr)
            else:
                diags.append(_diag(f"dot_k{kh}_highest",
                                   "no JSON output", rc=rc, err=err))
                print("[bench] highest-precision stage failed",
                      file=sys.stderr)

        # k-means stage (the other half of the north-star metric)
        out, err, km_rc = _run_stage("--worker-kmeans", [KM_ITERS, 2],
                                     STAGE_KMEANS_TIMEOUT)
        km = _parse_stage(out)
        if km is not None:
            diags.append(_ok_diag("kmeans", km))
            result["kmeans_iters_per_sec"] = km["value"]
            result["kmeans_platform"] = km.get("platform")
            cpu_km = _baseline("kmeans_1m", "iters_per_sec_1m")
            if cpu_km:
                result["kmeans_vs_baseline"] = round(km["value"] / cpu_km, 1)
                # the denominator's provenance rides the artifact,
                # derived from the baseline file so it cannot go stale
                # if the baseline is re-measured (round-4 Weak #3)
                n_meas = _baseline("kmeans_1m", "n_measured")
                n_tgt = _baseline("kmeans_1m", "target_n")
                if n_meas and n_tgt and n_meas != n_tgt:
                    result["kmeans_baseline_note"] = (
                        f"CPU denominator extrapolated linearly from a "
                        f"{n_meas:,}-row measurement to {n_tgt:,} rows "
                        f"(baselines/cpu_baseline.json; docs/BENCH.md)")
            print(f"[bench] kmeans stage: {km['value']} iters/s",
                  file=sys.stderr)
        else:
            diags.append(_diag("kmeans", "no JSON output",
                               rc=km_rc, err=err))
            print("[bench] kmeans stage failed", file=sys.stderr)

        # aux guard stage: configs 4-5 at full size, graded against the
        # committed per-platform regression floors; absent metrics grade
        # as unchecked.
        out, err, aux_rc = _run_stage("--worker-aux", [3], 540)
        aux = _parse_stage(out)
        if aux is not None:
            diags.append(_ok_diag("aux", aux))
            metrics = {k: aux.get(k) for k in (
                "pagerank_iters_per_sec", "logreg_iters_per_sec",
                "ssvd_seconds")}
            if km is not None:
                metrics["kmeans_iters_per_sec"] = km["value"]
            result.update(
                {k: v for k, v in metrics.items() if v is not None})
            g = _benchguard().check(
                metrics, aux.get("platform", ""))
            result["guard_pass"] = g["pass"] if g["checked"] else None
            result["guard"] = g["results"]
            print(f"[bench] aux guard: pass={result['guard_pass']}",
                  file=sys.stderr)
        else:
            diags.append(_diag("aux", "no JSON output", rc=aux_rc,
                               err=err))
            print("[bench] aux stage failed", file=sys.stderr)
        # chaos stage (opt-in with --chaos): seeded transient + OOM
        # faults during a checkpointed k-means loop; recovery counts
        # land in stage_diags so the driver sees what was survived
        if "--chaos" in sys.argv:
            out, err, ch_rc = _run_stage("--worker-chaos", [20, 0], 420)
            ch = _parse_stage(out)
            if ch is not None:
                d = _ok_diag("chaos", ch)
                d.update({
                    "rc": ch_rc,
                    "recovered_iterations": ch["recovered_iterations"],
                    "matches_fault_free": ch["matches_fault_free"],
                    "faults_injected": ch["faults_injected"],
                    "retries": ch["retries"],
                    "degrades": ch["degrades"],
                })
                diags.append(d)
                result["chaos"] = ch
                print(f"[bench] chaos stage: {ch['faults_injected']} "
                      f"fault(s) injected, {ch['retries']} retry(ies), "
                      f"{ch['degrades']} degrade(s), matches="
                      f"{ch['matches_fault_free']}", file=sys.stderr)
            else:
                diags.append(_diag("chaos", "no JSON output", rc=ch_rc,
                                   err=err))
                print("[bench] chaos stage failed", file=sys.stderr)
        # serving stage (opt-in with --serve): many-client open-loop
        # load through spartan_tpu/serve — p50/p99 latency, throughput
        # and the coalescing gates, graded against thresholds.json
        if "--serve" in sys.argv:
            out, err, sv_rc = _run_stage("--worker-serve", [16, 30], 540)
            sv = _parse_stage(out)
            if sv is not None:
                diags.append(_ok_diag("serve", sv))
                g = _benchguard().check(
                    {"serve_coalesced_speedup":
                         sv.get("serve_coalesced_speedup"),
                     "serve_off_overhead_ratio":
                         sv.get("serve_off_overhead_ratio")},
                    sv.get("platform", ""))
                sv["guard_pass"] = g["pass"] if g["checked"] else None
                result["serving"] = sv
                print(f"[bench] serve stage: "
                      f"{sv['serve_coalesced_speedup']}x coalesced, "
                      f"p99={sv['latency_p99_ms']}ms, off-path "
                      f"{sv['serve_off_overhead_ratio']}, guard_pass="
                      f"{sv['guard_pass']}", file=sys.stderr)
            else:
                diags.append(_diag("serve", "no JSON output", rc=sv_rc,
                                   err=err))
                print("[bench] serve stage failed", file=sys.stderr)
        if diags:
            # structured list (stage/reason/rc/stderr_tail/crash_file),
            # not the old concatenated string
            result["stage_diags"] = diags
        print(json.dumps(result), flush=True)
        return

    # Every stage failed: one diagnostic JSON line, never a traceback.
    print(json.dumps({
        "metric": "dense_dot_4096_gflops_per_chip",
        "value": 0.0,
        "unit": "GFLOPS",
        "vs_baseline": None,
        "error": ("; ".join(f"{d['stage']}: {d['reason']}" for d in diags)
                  or "no stage produced output"),
        "stage_diags": diags,
    }), flush=True)
    sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--worker-probe":
        worker_probe()
    elif len(sys.argv) >= 5 and sys.argv[1] == "--worker-dot":
        prec = None if sys.argv[4] == "default" else sys.argv[4]
        worker_dot(int(sys.argv[2]), int(sys.argv[3]), prec)
    elif len(sys.argv) >= 4 and sys.argv[1] == "--worker-kmeans":
        worker_kmeans(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) >= 3 and sys.argv[1] == "--worker-aux":
        worker_aux(int(sys.argv[2]))
    elif len(sys.argv) >= 4 and sys.argv[1] == "--worker-chaos":
        worker_chaos(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) >= 4 and sys.argv[1] == "--worker-serve":
        worker_serve(int(sys.argv[2]), int(sys.argv[3]))
    else:
        main()
