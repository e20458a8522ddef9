"""Randomized SVD on the normal path: one call is one plan, evaluated
once and fetched once; the plan reads A 2q + 2 times and never forms
A^T; and a row-tiled A on a 4-device mesh gives what the float64
reference gives."""

import os
import re
import sys

import jax
import numpy as np
import pytest

import spartan_tpu as st
from spartan_tpu.examples.ssvd import ssvd, ssvd_expr
from spartan_tpu.utils import profiling as prof

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))

from reference import ssvd as ref  # noqa: E402

M, N, RANK, K = 1024, 96, 6, 16


def _matrix(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((M, N))
            * np.arange(1, N + 1) ** -0.5).astype(np.float32)


def _inside(spans, outer):
    return [s for s in spans if s is not outer and s.tid == outer.tid
            and outer.ts <= s.ts and s.ts + s.dur <= outer.ts + outer.dur]


def test_one_plan_one_dispatch_one_fetch():
    a = st.from_numpy(_matrix())
    ssvd(a, rank=RANK, seed=1)  # the first call compiles
    prof.reset_counters()
    st.trace_clear()
    u, s, vt = ssvd(a, rank=RANK, seed=2)
    assert u.shape == (M, RANK) and s.shape == (RANK,)
    assert vt.shape == (RANK, N)
    c = prof.counters()
    assert c["evaluations"] == 1
    assert c.get("plan_hits") == 1 and not c.get("plan_misses")
    spans = st.trace_events()
    (call,) = [sp for sp in spans if sp.name == "ssvd"]
    assert call.args == {"m": M, "n": N, "l": K, "q": 2}
    names = [sp.name for sp in _inside(spans, call)]
    assert names.count("dispatch") == 1 and "compile" not in names
    assert names.count("fetch") == 1 and names.count("fetch_wait") == 1


@pytest.mark.parametrize("q", [0, 1, 2])
def test_plan_reads_a_2q_plus_2_times_and_never_transposes_it(q):
    """On one device, where the program holds A whole."""
    with st.use_mesh(st.build_mesh(jax.devices()[:1], shape=(1, 1))):
        a = st.from_numpy(_matrix())
        omega = st.from_numpy(ref.omega(3, N, K))
        text = prof.hlo_text(ssvd_expr(a, omega, RANK, q))
    (param,) = re.findall(rf"%(\S+) = f32\[{M},{N}\]\S* parameter\(",
                          text)
    uses = [ln for ln in text.splitlines()
            if re.search(rf"[(,] ?%{re.escape(param)}[,)]", ln)]
    assert len(uses) == 2 * q + 2
    assert all(re.search(r" dot\(", ln) for ln in uses)
    # nothing else holds an (m, n) or (n, m) array: no copy of A, no A^T
    made = re.findall(rf"= f32\[({M},{N}|{N},{M})\]\S* (?!parameter)\w+",
                      text)
    assert made == []


def test_row_tiled_on_four_devices_matches_float64_reference():
    a32 = _matrix(1)
    mesh = st.build_mesh(jax.devices()[:4], shape=(4, 1))
    with st.use_mesh(mesh):
        a = st.from_numpy(a32, tiling=st.Tiling(("x", None)))
        assert len(a.evaluate().jax_array.sharding.device_set) == 4
        u, s, vt = ssvd(a, rank=RANK, seed=7)
    u_ref, s_ref, vt_ref = ref.hmt(a32, ref.omega(7, N, K), RANK, 2)
    assert ref.sv_rel_err(s, s_ref) < 1e-5
    assert ref.orth_err(u) < 1e-5
    assert ref.triplet_err(a32, u, s, vt, s_ref[0]) < 1e-5
    assert ref.subspace_err(u, u_ref) < 1e-4
    assert ref.subspace_err(vt.T, vt_ref.T) < 1e-4

