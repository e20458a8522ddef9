"""Bring-up smoke run of spartan_tpu on the TPU, through the user API.

    python chip_smoke.py             # one chip: configs 1-5 + serve
    python chip_smoke.py --chips 4   # the 2x2 mesh path only

Each phase drives the normal entry points (``st.*``, the example
drivers, ``ServeEngine``) at the BASELINE.json sizes with data made
from ``--seed``, and checks its result against a NumPy reference with
a stated tolerance. Each prints one JSON line: wall and compile
seconds, the check, its tolerance and the error found. The last line
is ``{"ok": true, "device": {...}}`` only when every phase and every
post-run assertion passed. There is no CPU path: without a TPU the
script exits non-zero before the first phase. One process holds the
chip; nothing here starts another.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback

import numpy as np

# bf16 unit roundoff (8 significant bits) and f32's: TPU's default
# matmul precision rounds f32 operands to bf16 and accumulates in f32
_U_BF16 = 2.0 ** -9
_U_F32 = 2.0 ** -24

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_kw) -> None:
    # backend compile, persistent-cache retrieval included: a cache
    # hit shows up as a shorter duration here
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _check(what: str, err: float, tol: float, **extra) -> dict:
    ok = bool(np.isfinite(err) and err <= tol)
    return {"check": what, "err": float(err), "tol": tol, "ok": ok,
            **extra}


def _dot_rtol(k: int) -> float:
    """Bound on |C - C_ref| / C_ref for C = A @ B with nonnegative
    operands at the default precision: bf16 rounding of both operands
    (2u each) plus f32 accumulation over k terms."""
    return 2 * _U_BF16 + k * _U_F32


def _run_phase(st, name: str, fn, *args) -> bool:
    import jax

    c0 = _COMPILE_S[0]
    t0 = time.perf_counter()
    try:
        res = fn(st, *args)
    except Exception as e:  # noqa: BLE001 - a failed phase is reported
        traceback.print_exc()
        res = {"ok": False, "error": f"{type(e).__name__}: {str(e)[:300]}"}
    line = {"phase": name,
            "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(_COMPILE_S[0] - c0, 3), **res}
    # cached plans pin the device buffers of their leaves: drop them so
    # the next phase starts from an empty chip
    st.clear_compile_cache()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" in stats:
        line["hbm_in_use_after"] = int(stats["bytes_in_use"])
    print(json.dumps(line), flush=True)
    return bool(res.get("ok"))


# -- one-chip phases (BASELINE.json configs 1-5, plus serve) -------------


def config1(st, seed: int, n: int = 4096) -> dict:
    a = np.random.default_rng(seed).random((n, n), dtype=np.float32)
    got = float(((st.from_numpy(a) + st.from_numpy(a)) * 3.0).sum().glom())
    ref = float((a.astype(np.float64) * 6.0).sum())
    # f32 accumulation over n*n terms; far below any wrong answer
    return _check("((x + x) * 3).sum() rel err vs float64 NumPy",
                  abs(got - ref) / ref, 1e-4, shape=[n, n])


def config2(st, seed: int, n: int = 8192, rows: int = 64) -> dict:
    rng = np.random.default_rng(seed)
    a = rng.random((n, n), dtype=np.float32)
    b = rng.random((n, n), dtype=np.float32)
    c = st.dot(st.from_numpy(a), st.from_numpy(b)).glom()
    pick = np.sort(rng.choice(n, rows, replace=False))
    ref = a[pick].astype(np.float64) @ b.astype(np.float64)
    err = float(np.max(np.abs(c[pick] - ref) / ref))
    return _check(f"st.dot {rows}-row slice: max rel err vs float64 "
                  "NumPy (bound: bf16 operands + f32 accumulation)",
                  err, _dot_rtol(n), shape=list(c.shape),
                  finite=bool(np.isfinite(c).all()))


def _blobs(rng, n: int, d: int, k: int):
    """``k`` well-separated Gaussian blobs (centre spread 4, unit
    noise) and one starting centre near each: assignments have no
    near-ties, so kernel and reference agree point for point."""
    true = rng.standard_normal((k, d), dtype=np.float32) * 4.0
    lab = rng.integers(0, k, n)
    pts = true[lab] + rng.standard_normal((n, d), dtype=np.float32)
    init = true + 0.1 * rng.standard_normal((k, d), dtype=np.float32)
    return pts, init.astype(np.float32)


def _np_kmeans(pts64: np.ndarray, c: np.ndarray, iters: int) -> np.ndarray:
    k = c.shape[0]
    sq = np.einsum("ij,ij->i", pts64, pts64)
    for _ in range(iters):
        d2 = sq[:, None] - 2.0 * (pts64 @ c.T) + np.sum(c * c, axis=1)
        assign = np.argmin(d2, axis=1)
        order = np.argsort(assign, kind="stable")
        counts = np.bincount(assign, minlength=k)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        sums = np.zeros_like(c)
        nz = counts > 0
        sums[nz] = np.add.reduceat(pts64[order], starts[nz], axis=0)
        c = sums / np.maximum(counts, 1)[:, None]
    return c


def config3(st, seed: int, n: int = 1_000_000, d: int = 128, k: int = 64,
            iters: int = 5) -> dict:
    from spartan_tpu.examples.kmeans import _kernel_supports, kmeans

    pts, init = _blobs(np.random.default_rng(seed), n, d, k)
    x = st.from_numpy(pts)
    kernel = _kernel_supports(n, d, k)
    c1, _ = kmeans(x, k, num_iter=1, centers=init)
    cn, assign = kmeans(x, k, num_iter=iters, centers=init)
    pts64 = pts.astype(np.float64)
    ref1 = _np_kmeans(pts64, init.astype(np.float64), 1)
    refn = _np_kmeans(pts64, ref1, iters - 1)
    err = max(float(np.max(np.abs(c1 - ref1))),
              float(np.max(np.abs(cn - refn))))
    # f32 sums over ~n/k points per centre (HIGHEST-precision MXU)
    res = _check(f"k-means centres after 1 and {iters} iterations: "
                 "max abs err vs float64 NumPy", err, 1e-3,
                 kernel_path=kernel, assign_shape=list(np.shape(assign)))
    res["ok"] = res["ok"] and kernel
    return res


def _np_sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def config4(st, seed: int, n: int = 10_000_000, d: int = 32,
            iters: int = 5, lr: float = 0.1) -> dict:
    from spartan_tpu.examples.regression import logistic_regression

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d), dtype=np.float32)
    w_true = rng.standard_normal(d, dtype=np.float32)
    y = ((X @ w_true + rng.standard_normal(n, dtype=np.float32)) > 0
         ).astype(np.float32)
    w = logistic_regression(st.from_numpy(X), st.from_numpy(y),
                            num_iter=iters, lr=lr)
    X64 = X.astype(np.float64)
    del X
    ref = np.zeros(d)
    for _ in range(iters):
        ref = ref - lr * (X64.T @ (_np_sigmoid(X64 @ ref) - y)) / n
    # each step's gradient carries at most bf16 product rounding
    # (2u relative) of terms whose mean |x||p - y| is below 1
    return _check(f"logistic-regression weights after {iters} SGD "
                  "steps: max abs err vs float64 NumPy",
                  float(np.max(np.abs(w - ref))),
                  iters * lr * 4 * _U_BF16)


def _np_pagerank(rows, cols, n: int, damping: float, iters: int):
    deg = np.bincount(rows, minlength=n).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        y = np.bincount(cols, weights=r[rows] / deg[rows], minlength=n)
        new = damping * y + (1.0 - damping) / n
        r = new + (1.0 - new.sum()) / n
    return r


def config5(st, seed: int, n: int = 1_000_000, deg: int = 16,
            iters: int = 5, damping: float = 0.85) -> dict:
    from spartan_tpu.examples.pagerank import pagerank

    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    cols = rng.integers(0, n, n * deg)
    links = st.SparseDistArray.from_coo(
        rows, cols, np.ones(n * deg, np.float32), (n, n))
    impl = links.transition().default_impl()
    got = pagerank(links, damping=damping, num_iter=iters)
    ref = _np_pagerank(rows, cols, n, damping, iters)
    err = float(np.max(np.abs(got - ref)) / np.max(ref))
    res = _check(f"PageRank ranks after {iters} iterations: max abs "
                 "err / max rank vs float64 NumPy bincount", err, 1e-4,
                 spmv_impl=impl, edges=int(n * deg))
    res["ok"] = res["ok"] and impl == "windowed"
    return res


def serve(st, seed: int, n: int = 2048, clients: int = 8) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    rng = np.random.default_rng(seed)
    a = rng.random((n, n), dtype=np.float32)
    b = rng.random((n, n), dtype=np.float32)
    x, y = st.from_numpy(a), st.from_numpy(b)
    sum_ref = (a.astype(np.float64) + b).sum()
    dot_ref = a.astype(np.float64) @ b.astype(np.float64)
    scales = [float(i + 1) for i in range(clients)]
    with st.ServeEngine() as eng:
        def sum_client(s):
            return eng.submit((x + y).sum() * s).glom(timeout=600)

        def dot_client(s):
            return eng.submit(st.dot(x, y) * s).glom(timeout=600)

        with ThreadPoolExecutor(2 * clients) as pool:
            sums = [pool.submit(sum_client, s) for s in scales]
            dots = [pool.submit(dot_client, s) for s in scales]
            sums = [float(f.result()) for f in sums]
            dots = [f.result() for f in dots]
        stats = eng.stats()
    err_sum = max(abs(v - s * sum_ref) / (s * sum_ref)
                  for v, s in zip(sums, scales))
    err_dot = max(float(np.max(np.abs(v - s * dot_ref) / (s * dot_ref)))
                  for v, s in zip(dots, scales))
    tol = _dot_rtol(n)
    return _check(f"{2 * clients} concurrent requests ({clients} "
                  f"map+sum, {clients} dot): max rel err of any answer vs "
                  "float64 NumPy (dot bound)", max(err_sum, err_dot), tol,
                  answered=len(sums) + len(dots),
                  coalesced=stats.get("coalesced_requests"))


# -- four-chip phases: the 2x2 mesh ---------------------------------------


def _shards_placed(da, want: int) -> bool:
    """Shards of ``da`` sit on ``want`` distinct devices, with exactly
    the extents its Tiling gives."""
    arr = da.jax_array
    shards = arr.addressable_shards
    got = {tuple((sl.start or 0, arr.shape[i] if sl.stop is None else sl.stop)
                 for i, sl in enumerate(s.index)) for s in shards}
    want_ext = {tuple(zip(e.ul, e.lr)) for e in da.extents()}
    return len({s.device for s in shards}) == want and got == want_ext


def mesh_dot(st, seed: int, mesh, n: int = 8192, rows: int = 64) -> dict:
    rng = np.random.default_rng(seed)
    a = rng.random((n, n), dtype=np.float32)
    b = rng.random((n, n), dtype=np.float32)
    t = st.Tiling(("x", "y"))
    ea, eb = st.from_numpy(a, tiling=t), st.from_numpy(b, tiling=t)
    placed = all(_shards_placed(e.evaluate(), mesh.size) for e in (ea, eb))
    out = st.dot(ea, eb).evaluate()
    c = out.glom()
    pick = np.sort(rng.choice(n, rows, replace=False))
    ref = a[pick].astype(np.float64) @ b.astype(np.float64)
    res = _check(f"2-D tiled st.dot {rows}-row slice: max rel err vs "
                 "float64 NumPy", float(np.max(np.abs(c[pick] - ref) / ref)),
                 _dot_rtol(n), inputs_on_4_devices=placed,
                 out_devices=len(out.jax_array.devices()))
    res["ok"] = res["ok"] and placed
    return res


def mesh_kmeans(st, seed: int, mesh, n: int = 1_000_000, d: int = 128,
                k: int = 64, iters: int = 5) -> dict:
    from spartan_tpu.examples.kmeans import _kernel_supports, kmeans

    pts, init = _blobs(np.random.default_rng(seed), n, d, k)
    x = st.from_numpy(pts, tiling=st.Tiling(("x", None)))
    placed = _shards_placed(x.evaluate(), mesh.size)
    kernel = _kernel_supports(n, d, k)
    cn, _ = kmeans(x, k, num_iter=iters, centers=init)
    refn = _np_kmeans(pts.astype(np.float64), init.astype(np.float64),
                      iters)
    res = _check(f"k-means (shard_map + psum kernel) centres after "
                 f"{iters} iterations: max abs err vs float64 NumPy",
                 float(np.max(np.abs(cn - refn))), 1e-3,
                 kernel_path=kernel, inputs_on_4_devices=placed)
    res["ok"] = res["ok"] and placed and kernel
    return res


def mesh_sort(st, seed: int, mesh, n: int = 16 * 1024 * 1024) -> dict:
    from spartan_tpu.expr.builtins import SampleSortExpr

    v = np.random.default_rng(seed).standard_normal(n, dtype=np.float32)
    x = st.from_numpy(v, tiling=st.Tiling(("x",)))
    placed = _shards_placed(x.evaluate(), mesh.size)
    e = st.sort(x)
    got = e.glom()
    exact = bool(np.array_equal(got, np.sort(v)))
    sample = isinstance(e, SampleSortExpr)
    return {"check": "1-D st.sort (distributed sample sort) equals "
                     "np.sort exactly", "err": 0.0 if exact else 1.0,
            "tol": 0.0, "ok": exact and placed and sample,
            "sample_sort": sample, "inputs_on_4_devices": placed, "n": n}


# -- post-run assertions --------------------------------------------------


def _custom_call_in(fn, *args, **static) -> bool:
    """Does the compiled program of ``fn`` at these shapes hold a
    Mosaic kernel (``tpu_custom_call``)?"""
    return "tpu_custom_call" in fn.lower(*args, **static).compile().as_text()


def _kernel_plans_on_tpu(st) -> dict:
    """Compile the k-means and windowed-PageRank plans (at small
    shapes; only the lowering is in question) and look for Mosaic."""
    import jax
    import jax.numpy as jnp

    from spartan_tpu.examples import pagerank as pr
    from spartan_tpu.kernels import kmeans as kk

    f32 = jnp.float32
    km = _custom_call_in(
        kk.run, jax.ShapeDtypeStruct((8192, 128), f32),
        jax.ShapeDtypeStruct((64, 128), f32), k=64,
        iters=jax.ShapeDtypeStruct((), jnp.int32), valid_rows=8000)
    n = 4096
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(n), 4)
    links = st.SparseDistArray.from_coo(
        rows, rng.integers(0, n, 4 * n), np.ones(4 * n, np.float32), (n, n))
    T = links.transition()
    bufs, dims = T._windowed_plan()
    pg = _custom_call_in(
        pr._pagerank_loop, bufs, jnp.full((n,), 1.0 / n, f32), f32(0.85),
        jnp.int32(1), n=n, dims=dims)
    return {"kmeans_tpu_custom_call": km, "pagerank_tpu_custom_call": pg}


def _post_checks(st, with_kernels: bool) -> dict:
    from spartan_tpu.kernels import registry
    from spartan_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.counter_values()
    out = {
        "phase": "post",
        "metrics_on": bool(st.FLAGS.metrics),
        "resilience_retries": counters.get("resilience_retries", 0),
        "degrade_rungs": sum(v for k, v in counters.items()
                             if k.startswith("resilience_degrade")),
        "kernel_mode": registry.mode(),
        "interpret_mode": registry.interpret_mode(),
    }
    if with_kernels:
        out.update(_kernel_plans_on_tpu(st))
    out["ok"] = (out["metrics_on"] and out["resilience_retries"] == 0
                 and out["degrade_rungs"] == 0
                 and out["kernel_mode"] == "pallas"
                 and not out["interpret_mode"]
                 and all(v for k, v in out.items()
                         if k.endswith("tpu_custom_call")))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    import spartan_tpu as st

    st.initialize([])
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} "
              "device(s)", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    ok = True
    if args.chips == 1:
        for i, (name, fn) in enumerate([
                ("config1_map_sum", config1), ("config2_dot", config2),
                ("config3_kmeans", config3), ("config4_logreg", config4),
                ("config5_pagerank", config5), ("serve", serve)]):
            ok &= _run_phase(st, name, fn, args.seed + i)
        post = _post_checks(st, with_kernels=True)
    else:
        mesh = st.build_mesh(devs[:4], shape=(2, 2))
        line = st.build_mesh(devs[:4], shape=(4, 1))
        for i, (name, fn, m) in enumerate([
                ("mesh_dot", mesh_dot, mesh),
                ("mesh_kmeans", mesh_kmeans, mesh),
                ("mesh_sort", mesh_sort, mesh),
                ("mesh_sort_4x1", mesh_sort, line)]):
            with st.use_mesh(m):
                ok &= _run_phase(st, name, fn, args.seed + i, m)
        post = _post_checks(st, with_kernels=False)
    print(json.dumps(post), flush=True)
    if not (ok and post["ok"]):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
