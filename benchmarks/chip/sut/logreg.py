"""Logistic regression through the user API: the ``fit`` op (see
sut/kmeans.py for the functions each op has). The program is reached
through the module attribute ``reg.logistic_regression``, so a test
can break the timed path underneath.

``COUNTS`` adds up, over the jobs the window runs, what the metrics
registry's ``loop_iterations`` and ``evaluations`` gained during each;
``metrics/loop.iters_per_eval.py`` reads it."""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from spartan_tpu.array import distarray as da
from spartan_tpu.array import tiling as tiling_mod
from spartan_tpu.examples import regression as reg
from spartan_tpu.expr.base import as_expr
from spartan_tpu.utils import profiling as prof

from reference import logreg as ref

LOWP = jnp.bfloat16  # the control's precision: next below float32
COUNTS = {"loop_iterations": 0, "evaluations": 0}


def setup_fit(cfg: dict, traffic: dict, seed: int, mesh, key):
    rows2, rows1 = tiling_mod.row(2), tiling_mod.row(1)
    x, y = ref.make_data(key, cfg["n"], cfg["d"], rows2.sharding(mesh),
                         rows1.sharding(mesh))
    COUNTS.update(loop_iterations=0, evaluations=0)
    return SimpleNamespace(
        cfg=cfg, seed=seed, x=x, y=y, iters=traffic["num_iter"],
        lr=traffic["lr"], call=traffic.get("call", {}),
        xe=as_expr(da.from_jax(x, tiling=rows2, mesh=mesh)),
        ye=as_expr(da.from_jax(y, tiling=rows1, mesh=mesh)))


def _start(s, job: int) -> np.ndarray:
    return ref.job_start(s.seed, job, s.cfg["d"])


def _fit(s, job: int) -> np.ndarray:
    return np.asarray(reg.logistic_regression(
        s.xe, s.ye, s.iters, s.lr, w0=_start(s, job), **s.call))


def warm_fit(s, traffic: dict) -> None:
    _fit(s, 0)


def run_fit(s, job: int) -> np.ndarray:
    before = prof.counters()
    w = _fit(s, job)
    after = prof.counters()
    for name in COUNTS:
        COUNTS[name] += after.get(name, 0) - before.get(name, 0)
    return w


_descend_lowp = jax.jit(ref.descend_lowp,
                        static_argnames=("iters", "lr", "dtype"))


def control_fit(s, job: int) -> np.ndarray:
    return np.asarray(_descend_lowp(s.x, s.y, _start(s, job),
                                    iters=s.iters, lr=s.lr, dtype=LOWP))


def check_fit(s, kept: list) -> list:
    """The kept job's w against the float64 reference's steps from the
    same start, over the same X and y (``w_rel_err``)."""
    t0 = time.perf_counter()
    x32 = np.asarray(jax.device_get(s.x))
    y = np.asarray(jax.device_get(s.y))
    err = np.inf
    for job, w in kept:
        err = ref.w_rel_err(w, ref.descend(x32, y, _start(s, job), s.iters,
                                           s.lr))
    s.diagnostics = {"reference_s": time.perf_counter() - t0}
    return [{"name": "w_rel_err", "value": err,
             "limit": s.cfg["limits"]["w_rel_err"]}]
