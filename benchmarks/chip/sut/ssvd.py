"""Randomized SVD through the user API: the ``svd`` op (see
sut/kmeans.py for the four functions each op has). The program is
reached through the module attribute ``sv.ssvd``, so a test can break
the timed path underneath."""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from spartan_tpu.array import distarray as da
from spartan_tpu.array import tiling as tiling_mod
from spartan_tpu.examples import ssvd as sv
from spartan_tpu.expr.base import as_expr
from spartan_tpu.utils import profiling as prof

from reference import ssvd as ref

LOWP = jnp.bfloat16  # the control's precision: next below float32
TOP = 10  # leading singular vectors whose span is compared


def setup_svd(cfg: dict, traffic: dict, seed: int, mesh, key):
    tiling = tiling_mod.row(2)
    a = ref.make_matrix(key, cfg["m"], cfg["n"], tiling.sharding(mesh))
    return SimpleNamespace(
        cfg=cfg, seed=seed, a=a, calls=0,
        plans=prof.counters().get("evaluations", 0),
        k=min(cfg["rank"] + cfg["n_oversample"], cfg["m"], cfg["n"]),
        expr=as_expr(da.from_jax(a, tiling=tiling, mesh=mesh)))


def run_svd(s, job: int) -> dict:
    s.calls += 1
    u, sv_, vt = sv.ssvd(s.expr, s.cfg["rank"], s.cfg["n_oversample"],
                         s.cfg["n_power_iter"],
                         seed=ref.omega_seed(s.seed, job))
    return {"u": u, "s": sv_, "vt": vt}


_hmt_lowp = jax.jit(ref.hmt_lowp,
                    static_argnames=("rank", "power_iter", "dtype"))


def control_svd(s, job: int) -> dict:
    om = ref.omega(ref.omega_seed(s.seed, job), s.cfg["n"], s.k)
    u, sv_, vt = _hmt_lowp(s.a, jnp.asarray(om), rank=s.cfg["rank"],
                           power_iter=s.cfg["n_power_iter"], dtype=LOWP)
    return {name: np.asarray(x, np.float32)
            for name, x in (("u", u), ("s", sv_), ("vt", vt))}


def check_svd(s, kept: list) -> list:
    """The kept job's triplets against the float64 reference on the
    same A and Omega: the singular values (``sv_rel_err``), U's
    orthonormality (``orth_err``), every triplet's agreement with A
    (``triplet_err``), and the span of the
    leading left and right singular vectors (``subspace_err``, the
    larger side)."""
    t0 = time.perf_counter()
    a32 = np.asarray(jax.device_get(s.a))
    cfg = s.cfg
    sv_err = orth = triplet = sub = np.inf
    for job, out in kept:
        u_ref, s_ref, vt_ref = ref.hmt(
            a32, ref.omega(ref.omega_seed(s.seed, job), cfg["n"], s.k),
            cfg["rank"], cfg["n_power_iter"])
        sv_err = ref.sv_rel_err(out["s"], s_ref)
        orth = ref.orth_err(out["u"])
        triplet = ref.triplet_err(a32, out["u"], out["s"], out["vt"],
                                  s_ref[0])
        sub = max(ref.subspace_err(out["u"][:, :TOP], u_ref[:, :TOP]),
                  ref.subspace_err(out["vt"][:TOP].T, vt_ref[:TOP].T))
    plans = prof.counters().get("evaluations", 0) - s.plans
    s.diagnostics = {"reference_s": time.perf_counter() - t0,
                     "evaluations_per_call": plans / max(s.calls, 1)}
    lim = cfg["limits"]
    return [{"name": name, "value": value, "limit": lim[name]}
            for name, value in (("sv_rel_err", sv_err), ("orth_err", orth),
                                ("triplet_err", triplet),
                                ("subspace_err", sub))]
