"""k-means through the user API: the ``fit`` and ``assign`` ops.

Each op is four functions found by name: ``setup_<op>`` makes the
cell's data on the device from the seed, ``run_<op>`` is one job or one
query through the program, ``control_<op>`` is the plain reference at
the next precision down put in the program's place, and ``check_<op>``
compares what the window kept with the float64 reference. The program
is reached through module attributes (``km.kmeans``), so a test can
break the timed path underneath.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from spartan_tpu.array import distarray as da
from spartan_tpu.array import tiling as tiling_mod
from spartan_tpu.examples import kmeans as km
from spartan_tpu.expr.base import as_expr

from reference import kmeans as ref

LOWP = jnp.bfloat16  # the control's precision: next below float32


def _check(name: str, value: float, cfg: dict) -> dict:
    return {"name": name, "value": value, "limit": cfg["limits"][name]}


# -- fit: closed-loop Lloyd jobs -------------------------------------------


def setup_fit(cfg: dict, traffic: dict, seed: int, mesh, key):
    true = ref.blob_centres(seed, cfg["k"], cfg["d"])
    pts, pool = ref.make_points(key, true, cfg["n"], traffic["init_pool"],
                                sharding=tiling_mod.row(2).sharding(mesh))
    return SimpleNamespace(
        cfg=cfg, seed=seed, pts=pts, pool=pool, iters=traffic["num_iter"],
        call=traffic.get("call", {}),
        points=as_expr(da.from_jax(pts, tiling=tiling_mod.row(2),
                                   mesh=mesh)))


def _init(s, job: int) -> np.ndarray:
    return ref.job_init(s.seed, s.pool, job, s.cfg["k"])


def run_fit(s, job: int) -> dict:
    centres, assign = km.kmeans(s.points, s.cfg["k"], num_iter=s.iters,
                                centers=_init(s, job), **s.call)
    return {"centres": np.asarray(centres), "assign": np.asarray(assign)}


_lloyd_lowp = jax.jit(ref.lloyd_lowp, static_argnames=("iters", "dtype"))


def control_fit(s, job: int) -> dict:
    c, a = _lloyd_lowp(s.pts, _init(s, job), iters=s.iters, dtype=LOWP)
    return {"centres": np.asarray(c), "assign": np.asarray(a)}


def check_fit(s, kept: list) -> list:
    """The kept job's centres against the float64 reference from the
    same start (the median centre's error), and its assignment against
    its own centres (the widest gap over the nearest)."""
    pts64 = np.asarray(jax.device_get(s.pts), np.float64)
    err, gap = np.inf, np.inf
    for job, out in kept:
        c_ref, fixed_at = ref.lloyd(pts64, _init(s, job).astype(np.float64),
                                    s.iters)
        s.diagnostics = {"reference_fixed_point_iter": fixed_at}
        err = ref.centre_err(out["centres"], c_ref)
        gap = ref.served_gap(pts64, out["centres"].astype(np.float64),
                             out["assign"])
    return [_check("centre_err_median", err, s.cfg),
            _check("assign_gap", gap, s.cfg)]


# -- assign: nearest-centre queries through the serve engine ---------------


def setup_assign(cfg: dict, traffic: dict, seed: int, mesh, key):
    true = ref.blob_centres(seed, cfg["k"], cfg["d"])
    pool = ref.query_pool(seed, true, traffic["pool_batches"],
                          traffic["rows"], traffic["boundary_share"],
                          traffic["margin_lo"], traffic["margin_hi"])
    return SimpleNamespace(cfg=cfg, seed=seed, true=true, pool=pool,
                           centres=st.from_numpy(true))


def query_rows(s, q) -> np.ndarray:
    """Query ``q = (batch, shift)``: a pool batch with its rows rolled,
    so every query in a window is a distinct array."""
    j, shift = q
    return np.roll(s.pool[j], shift, axis=0)


def run_assign(s, engine, q) -> np.ndarray:
    x = st.from_numpy(query_rows(s, q))
    return np.asarray(engine.submit(km.assign_points(x, s.centres))
                      .glom(timeout=60))


def warm_assign(s, traffic: dict) -> None:
    """Compile every batch size the window can coalesce. For each size
    in ``warm_batches``, the engine's one worker is first kept busy by
    a request of a plan it has not seen (a fresh compile), so the
    queries submitted meanwhile queue up and are taken as one batch;
    tried again until every query reports that batch size."""
    rows = query_rows(s, (0, 0))
    with st.ServeEngine(workers=1, batch_window_s=0.5) as eng:
        for size in traffic["warm_batches"]:
            for attempt in range(20):
                blocker = eng.submit(st.from_numpy(
                    np.ones((8, 8 + 64 * size + attempt), np.float32)
                ).sum())
                futs = [eng.submit(km.assign_points(st.from_numpy(rows),
                                                    s.centres))
                        for _ in range(size)]
                blocker.glom(timeout=600)
                for f in futs:
                    f.glom(timeout=600)
                if all(f.coalesced == size for f in futs):
                    break
            else:
                raise RuntimeError(f"could not warm batch size {size}")


_nearest_lowp = jax.jit(ref.nearest_lowp, static_argnames=("dtype",))


def control_assign(s, engine, q) -> np.ndarray:
    return np.asarray(_nearest_lowp(jnp.asarray(query_rows(s, q)),
                                    jnp.asarray(s.true), dtype=LOWP))


def check_assign(s, kept: list) -> list:
    c64 = s.true.astype(np.float64)
    gap = 0.0
    for q, ids in kept:
        gap = max(gap, ref.served_gap(query_rows(s, q).astype(np.float64),
                                      c64, ids))
    return [_check("served_gap", gap, s.cfg)]
