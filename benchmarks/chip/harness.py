"""The benchmark harness: one cell, one seed, one run.

Everything is found by name from ``BENCHMARK.json``. A cell names a
configuration (``configs/<config>.json``: sizes, mesh, limits) and a
traffic mix (``traffic/<mix>.json``: the driver, the system path
``sut/<sut>.py`` with its op, the op's parameters, and ``call``, keyword
arguments handed to the program's API call as they are). Each per-layer
metric is a reader, ``metrics/<metric>.py``; a metric split by the
end-to-end metric it moves (``<base>.<part>``) may share the reader
``metrics/<base>.py``. A reader's ``read`` returns a number or ``None``
when it finds nothing to read. A later PR adds a configuration, a mix
(of an existing path, or with a ``sut`` module of its own), or a metric,
as new files and entries, and edits nothing here.

Two general drivers generate the load:

- ``jobs``: a closed loop of jobs, one at a time. Jobs start only while
  the window is open, and every job that starts is timed to its end.
  ``step_ms`` is the whole window over all the steps completed in it.
- ``clients``: ``clients`` closed-loop threads send queries through one
  ``ServeEngine``. ``query_p95_ms`` is over every query in the window,
  each timed from the start of its upload to its answer on the host;
  ``queries_per_s`` is every query answered over the whole window.

Set-up (``setup_s``) runs from the start of the process to the opening
of the window: JAX start-up, the data made on the device from the
seed, and the warm-up of exactly the shapes the window uses. After the
window the peak device memory is read, the program's cached plans are
dropped, and the kept outputs are compared with the plain reference.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
import traceback
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, ".bench_out")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def configure_env() -> None:
    """Before JAX is imported: its persistent compilation cache at a
    fixed path in the checkout, holding every program however quick to
    compile, so only a cell's first run in a checkout compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


# -- finding things by name ---------------------------------------------------


def load_bench(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(group: str, name: str):
    """``<group>/<name>.py`` under the harness, loaded by path (metric
    names hold dots, which ``import`` cannot spell)."""
    key = f"chipbench_{group}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(HERE, group, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def load_reader(metric: str):
    """The reader of ``metric``: ``metrics/<metric>.py``, or else that of
    the name with its last dotted part taken off, and so on
    (``device.idle_share.serve`` -> ``metrics/device.idle_share.py``)."""
    base = metric
    while not os.path.isfile(os.path.join(HERE, "metrics", f"{base}.py")):
        if "." not in base:
            raise FileNotFoundError(f"no reader for metric {metric!r} "
                                    "under benchmarks/chip/metrics")
        base = base.rsplit(".", 1)[0]
    return load_module("metrics", base)


def load_cell(bench: dict, workload: str) -> SimpleNamespace:
    """The cell ``workload`` with its configuration, traffic and
    metrics, all resolved by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)

    def mine(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    return SimpleNamespace(
        name=workload, chips=int(w["chips"]), config=cfg,
        traffic=_json("traffic", f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def peak(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    peaks = _json("peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/chip/peaks.json")
    return peaks[device_kind]


def jax_key(seed: int):
    """A JAX key that keeps every bit of a seed wider than 32 bits."""
    import jax

    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              seed >> 32)


# -- the drivers ----------------------------------------------------------------


def _annotate(on: bool, name: str):
    import contextlib

    import jax

    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def run_jobs(run: Callable, s, seconds: float, traffic: dict, seed: int,
             annotate: bool) -> dict:
    """Closed loop of jobs. Keeps one job drawn from the seed (a
    reservoir over every job that finished) for the comparison."""
    rng = np.random.default_rng([seed, 5])
    kept = None
    done = failed = 0
    job_s: List[float] = []
    job = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while time.perf_counter() < t_end:
        tj = time.perf_counter()
        try:
            with _annotate(annotate, "bench.job"):
                out = run(s, job)
        except Exception:  # noqa: BLE001 - a failed job is counted
            traceback.print_exc()
            failed += 1
        else:
            done += 1
            job_s.append(time.perf_counter() - tj)
            if rng.random() < 1.0 / done:
                kept = (job, out)
        job += 1
    t1 = time.perf_counter()
    steps = done * int(traffic["steps_per_job"])
    q = (np.quantile(job_s, [0.0, 0.5, 1.0]) * 1e3).tolist() if job_s \
        else []
    return {"attempted": job, "failed": failed, "steps": steps,
            "window_s": t1 - t0, "kept": [kept] if kept else [],
            "diagnostics": {"job_ms_min_median_max": q},
            "metrics": {"step_ms": (t1 - t0) * 1e3 / max(steps, 1)}}


def run_clients(run: Callable, s, seconds: float, traffic: dict, seed: int,
                annotate: bool, mesh, engine) -> dict:
    """``clients`` closed-loop threads. Each query is a pool batch with
    its rows rolled, drawn from the seed per client."""
    import spartan_tpu as st

    clients = int(traffic["clients"])
    lat: List[List[float]] = [[] for _ in range(clients)]
    kept: List[list] = [[] for _ in range(clients)]
    failed = [0] * clients
    go = threading.Barrier(clients + 1)
    t_end = [0.0]

    def client(c: int) -> None:
        rng = np.random.default_rng([seed, 4, c])
        with st.use_mesh(mesh):
            go.wait()
            while time.perf_counter() < t_end[0]:
                q = (int(rng.integers(traffic["pool_batches"])),
                     int(rng.integers(traffic["rows"])))
                t0 = time.perf_counter()
                try:
                    with _annotate(annotate, "bench.query"):
                        ids = run(s, engine, q)
                except Exception:  # noqa: BLE001 - counted as failed
                    traceback.print_exc()
                    failed[c] += 1
                    continue
                lat[c].append(time.perf_counter() - t0)
                kept[c].append((q, ids))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    before = engine.stats() if engine is not None else {}
    t_end[0] = time.perf_counter() + seconds
    go.wait()
    t0 = t_end[0] - seconds
    for t in threads:
        t.join()
    t1 = time.perf_counter()
    after = engine.stats() if engine is not None else {}
    all_lat = np.array([x for c in lat for x in c])
    answered = len(all_lat)
    return {
        "attempted": answered + sum(failed), "failed": sum(failed),
        "steps": answered, "window_s": t1 - t0,
        "kept": [x for c in kept for x in c],
        "counters": {k: after.get(k, 0) - before.get(k, 0)
                     for k in ("requests", "coalesced_requests")},
        # with no answer at all, every query waited the whole window
        "metrics": {
            "query_p95_ms": (float(np.percentile(all_lat, 95)) * 1e3
                             if answered else (t1 - t0) * 1e3),
            "queries_per_s": answered / (t1 - t0)}}


# -- one run ----------------------------------------------------------------------


_COMPILES: dict = {}


def _watch_compiles() -> None:
    """Count backend compiles per phase of the run (once a process)."""
    import jax

    if _COMPILES:
        return

    def on_compile(event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            _COMPILES[_COMPILES["phase"]] += 1

    _COMPILES.update(setup=0, window=0, after=0, phase="setup")
    jax.monitoring.register_event_duration_secs_listener(on_compile)


def _program_spans(t_lo: float, t_hi: float):
    """The program's own spans (obs/trace) inside ``[t_lo, t_hi]`` on
    the host clock: (name, start_s, end_s, thread)."""
    from spartan_tpu.obs import trace as trace_mod

    ep = trace_mod.epoch()
    out = []
    for sp in trace_mod.events():
        a = ep + sp.ts * 1e-6
        b = a + sp.dur * 1e-6
        if b >= t_lo and a <= t_hi:
            out.append((sp.name, a, b, sp.tid))
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: Optional[float] = None, control: bool = False,
             keep_trace: Optional[str] = None) -> dict:
    """Run ``cell`` once on ``devices``; return the contract's result
    line as a dict (``checks`` last). ``control`` puts the plain
    reference at the next precision down in the program's place."""
    import jax

    import spartan_tpu as st
    from spartan_tpu.utils.config import FLAGS

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, traffic = cell.config, cell.traffic
    devices = list(devices)[:cell.chips]
    st.initialize([])
    FLAGS.trace = bool(trace)
    if trace:
        FLAGS.trace_ring = 1 << 21  # a window's spans must not wrap
    _watch_compiles()
    _COMPILES.update(setup=0, window=0, after=0, phase="setup")
    mesh = st.build_mesh(devices, shape=tuple(cfg["mesh"]))
    sut = importlib.import_module(f"sut.{traffic['sut']}")
    op = traffic["op"]
    run = getattr(sut, f"{'control' if control else 'run'}_{op}")
    is_clients = traffic["driver"] == "clients"
    warm = getattr(sut, f"warm_{op}", None)
    with st.use_mesh(mesh):
        s = getattr(sut, f"setup_{op}")(cfg, traffic, seed, mesh,
                                        jax_key(seed))
        # warm-up: the shapes of the window and no others
        engine = (st.ServeEngine().start() if is_clients and not control
                  else None)
        if warm is not None and not control:
            warm(s, traffic)
        elif is_clients:
            run(s, engine, (0, 0))
        else:
            run(s, 0)
        setup_s = time.perf_counter() - t_start

        trace_dir = os.path.join(OUT, "trace", cell.name)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        _COMPILES["phase"] = "window"
        t_w0 = time.perf_counter()
        with _annotate(trace, "bench.window"):
            if is_clients:
                win = run_clients(run, s, seconds, traffic, seed, trace,
                                  mesh, engine)
            else:
                win = run_jobs(run, s, seconds, traffic, seed, trace)
        t_w1 = time.perf_counter()
        _COMPILES["phase"] = "after"
        if trace:
            jax.profiler.stop_trace()
        if engine is not None:
            engine.stop()

        stats = [d.memory_stats() or {} for d in devices]
        mem_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in stats)
        spans = _program_spans(t_w0, t_w1) if trace else []
        st.clear_compile_cache()  # cached plans pin device buffers
        gc.collect()
        checks = getattr(sut, f"check_{op}")(s, win["kept"])

    correct = (win["failed"] == 0 and win["steps"] > 0
               and all(c["value"] <= c["limit"] for c in checks))
    dev = devices[0]
    line = {"correct": bool(correct), "attempted": int(win["attempted"]),
            "failed": int(win["failed"]), "metrics": {},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": mem_peak}}
    if not trace:
        values = dict(win["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    else:
        from devtrace import breakdown, find_capture, reduce

        path = find_capture(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace,
                                           f"{cell.name}.xplane.pb"))
        red = reduce(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host clock -> capture clock, anchored on the window's start
        off = red.lo - t_w0 * 1e9
        host = [(n, a * 1e9 + off, b * 1e9 + off, tid)
                for n, a, b, tid in spans]
        ctx = SimpleNamespace(
            trace=red, steps=win["steps"], counters=win.get("counters", {}),
            spans=host, config=cfg, traffic=traffic,
            peak=peak(dev.device_kind), costs=lambda k: load_module(
                "costs", k))
        for m in cell.per_layer:
            v = load_reader(m["name"]).read(ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        line["device"]["busy_s"] = red.mean_busy_s()
        line["device"]["window_s"] = red.window_s
        line["breakdown"] = breakdown(
            red, extra_spans=[(n, a, b) for n, a, b, _ in host])
    line["diagnostics"] = {"compiles_in_setup": _COMPILES["setup"],
                           "compiles_in_window": _COMPILES["window"],
                           "steps": win["steps"],
                           "window_s": win["window_s"],
                           **win.get("diagnostics", {}),
                           **getattr(s, "diagnostics", {})}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return line


def report(line: dict) -> None:
    """Print the checks as the last lines of stderr, then the result as
    the last line of stdout."""
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(load_bench(), args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chip bench: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX has {len(devs)} {devs[0].platform} "
              "device(s)", file=sys.stderr)
        return 2
    report(run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                    t_start=t_start))
    return 0
