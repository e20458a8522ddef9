"""PageRank through the user API: the ``rank`` op (see sut/kmeans.py
for the four functions each op has)."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import spartan_tpu as st
from spartan_tpu.examples import pagerank as pr

from reference import pagerank as ref

LOWP = jnp.bfloat16  # the control's precision: next below float32


def setup_rank(cfg: dict, traffic: dict, seed: int, mesh, key):
    n, e = ref.graph_size(cfg)
    rows, cols = ref.edges(key, scale=cfg["scale"],
                           edge_factor=cfg["edge_factor"],
                           undirected=cfg["undirected"])
    links = st.SparseDistArray.from_coo_device(
        rows, cols, jnp.ones((e,), jnp.float32), (n, n), mesh=mesh)
    links.transition()  # built once and cached on ``links``: set-up
    return SimpleNamespace(cfg=cfg, n=n, rows=rows, cols=cols, links=links,
                           iters=traffic["num_iter"],
                           call=traffic.get("call", {}),
                           damping=cfg["damping"])


def run_rank(s, job: int) -> np.ndarray:
    return np.asarray(pr.pagerank(s.links, damping=s.damping,
                                  num_iter=s.iters, **s.call))


_pagerank_lowp = jax.jit(ref.pagerank_lowp,
                         static_argnames=("n", "iters", "dtype"))


def control_rank(s, job: int) -> np.ndarray:
    return np.asarray(_pagerank_lowp(s.rows, s.cols, n=s.n,
                                     damping=s.damping, iters=s.iters,
                                     dtype=LOWP))


def check_rank(s, kept: list) -> list:
    ranks = ref.pagerank(np.asarray(jax.device_get(s.rows), np.int64),
                         np.asarray(jax.device_get(s.cols), np.int64),
                         s.n, s.damping, s.iters)
    err = max((ref.rank_err(out, ranks) for _, out in kept),
              default=np.inf)
    return [{"name": "rank_err", "value": err,
             "limit": s.cfg["limits"]["rank_err"]}]
