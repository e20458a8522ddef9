"""Dense product through the user API: the ``dot`` op (see
sut/kmeans.py for the four functions each op has)."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from spartan_tpu.array import distarray as da
from spartan_tpu.expr.base import as_expr

from reference import dot as ref

LOWP = jnp.bfloat16  # the control's precision: next below float32


def setup_dot(cfg: dict, traffic: dict, seed: int, mesh, key):
    tiling = st.Tiling(tuple(cfg["tiling"]))
    sharding = tiling.sharding(mesh)
    a, b = ref.operands(key, cfg["n"], sharding)
    return SimpleNamespace(
        cfg=cfg, seed=seed, a=a, b=b, sharding=sharding,
        ea=as_expr(da.from_jax(a, tiling=tiling, mesh=mesh)),
        eb=as_expr(da.from_jax(b, tiling=tiling, mesh=mesh)))


def run_dot(s, job: int):
    """One evaluated product; the result stays on the device."""
    out = st.dot(s.ea, s.eb).evaluate().jax_array
    return out.block_until_ready()


def control_dot(s, job: int):
    out = jax.jit(ref.dot_lowp, static_argnames=("dtype",),
                  out_shardings=s.sharding)(s.a, s.b, dtype=LOWP)
    return out.block_until_ready()


def check_dot(s, kept: list) -> list:
    rows = ref.sample_rows(s.seed, s.cfg["n"], s.cfg["check_rows"],
                           s.cfg["mesh"][0])
    a_rows = np.asarray(s.a[rows])
    b = np.asarray(jax.device_get(s.b))
    err = max(ref.rel_err(np.asarray(out[rows]), a_rows, b)
              for _, out in kept)
    return [{"name": "dot_rel_err", "value": err,
             "limit": s.cfg["limits"]["dot_rel_err"]}]
