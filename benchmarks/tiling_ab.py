"""Smart-tiling A/B: does --opt_auto_tiling change what XLA emits and
how fast the canonical chain runs? (SURVEY.md §6 ablation requirement.)

Chain: ``dot(A, B)`` with both operands row-sharded on the *col* mesh
axis (row_t). Round-5 behavior (receive-bytes + FLOP-priced model):
the pass routes this GEMM onto the psum row arm — the arm the
measured-arm sweep shows fastest (pick_vs_best 1.00,
tiling_sweep.json) — and the ON arm measures ~1.07-1.2x faster than
unplanned GSPMD at n=2048/512 on the CPU mesh even though the
collective-op CENSUS coincides (the constraints change where the
collectives sit relative to the matmul, not their count). The
--sweep mode is the primary validation surface: it forces EVERY
candidate plan (output tiling x contraction placement) of each layout
combo as a measured arm, fits the contraction weights to the arms'
times and scores the model's pick against the best arm; this A/B
remains the quick ablation smoke. Reports, per arm: wall time (result
materialized in its sharded layout, no fetch) and the census.

Run on the 8-virtual-device CPU mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/tiling_ab.py [--small|--sweep]
The sweep on a v5e 2x2 (benchmarks/tiling_sweep_tpu.json):
  python benchmarks/tiling_ab.py --sweep --mesh 2x2 --sizes 4096,8192 \
      --combos "block x block,row x col,row x row,row_t x row_t,row_t x row,einsum bmm block x block" \
      --out benchmarks/tiling_sweep_tpu.json
and ``--refit <report>`` re-scores a recorded sweep under the weights
the code commits now.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = "--small" in sys.argv
N = 512 if SMALL else 2048
ITERS = 3 if SMALL else 10

_COLLECTIVE_RE = re.compile(
    r"\b(all-to-all|collective-permute|all-gather|all-reduce)\b")


def _chain(st, a, b, tiling):
    ea = st.from_numpy(a, tiling=tiling.row_t(2))
    eb = st.from_numpy(b, tiling=tiling.row_t(2))
    return st.dot(ea, eb)


def _measure(st, tiling, profiling, a, b):
    import jax

    hlo = profiling.hlo_text(_chain(st, a, b, tiling))
    counts = {}
    for m in _COLLECTIVE_RE.finditer(hlo):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    _chain(st, a, b, tiling).evaluate()  # warm the compile cache
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = _chain(st, a, b, tiling).evaluate()
        jax.block_until_ready(out.jax_array)
    dt = (time.perf_counter() - t0) / ITERS
    return float(np.asarray(out.glom()).sum()), dt, counts


def _time_arms(arms_exprs, iters):
    """Median wall time per arm, measured ROUND-ROBIN (one timing of
    every arm per round) so slow machine-load drift biases all arms
    equally instead of whichever happened to run during a stall."""
    import jax

    for e in arms_exprs:  # compile + warm each once
        e.invalidate()
        jax.block_until_ready(e.evaluate().jax_array)
    times = [[] for _ in arms_exprs]
    for _ in range(iters):
        for i, e in enumerate(arms_exprs):
            e.invalidate()
            t0 = time.perf_counter()
            out = e.evaluate()
            jax.block_until_ready(out.jax_array)
            times[i].append(time.perf_counter() - t0)
    return [float(np.median(t)) for t in times]


# Operand layouts of the sweep, by name. The default set is the CPU
# sweep's; --combos picks a subset by name.
_COMBOS = {
    "row x col": ("row", "col", "gemm"),
    "row x row": ("row", "row", "gemm"),
    "row_t x row_t": ("row_t", "row_t", "gemm"),
    "row_t x row": ("row_t", "row", "gemm"),
    "col x row": ("col", "row", "gemm"),
    "block x block": ("block", "block", "gemm"),
    "col_t x row_t": ("col_t", "row_t", "gemm"),
    "block_t x block": ("block_t", "block", "gemm"),
    "einsum bmm row x row": ("row", "row", "einsum"),
    "einsum bmm block x block": ("block", "block", "einsum"),
}


def _arm_cost(arm, move_w, flop_w) -> float:
    """An arm's model cost from its recorded parts, in psum bytes."""
    return (arm["psum_bytes"] + move_w * arm["move_bytes"]
            + flop_w * arm["flops"])


def _pick(arms, move_w, flop_w) -> int:
    """The arm the planner picks under these weights: the cheapest,
    the first in the planner's candidate order on a tie."""
    costs = [_arm_cost(a, move_w, flop_w) for a in arms]
    return costs.index(min(costs))


def _spearman(x, y) -> float:
    if len(x) < 2:
        return 1.0
    rx = np.argsort(np.argsort(x)).astype(float)
    ry = np.argsort(np.argsort(y)).astype(float)
    return float(np.corrcoef(rx, ry)[0, 1])


def fit_weights(combos):
    """The operand-move and flop weights that measured arms imply:
    least squares of every arm's seconds on its psum bytes, operand
    bytes moved and FLOPs a chip, with an intercept per combo (its
    dispatch and fixed cost). Both weights come out in psum bytes, the
    model's unit. Returns ``(move_w, flop_w, seconds per psum byte)``."""
    rows, ys = [], []
    for ci, c in enumerate(combos):
        for a in c["arms"]:
            one_hot = [0.0] * len(combos)
            one_hot[ci] = 1.0
            rows.append([a["psum_bytes"], a["move_bytes"], a["flops"]]
                        + one_hot)
            ys.append(a["sec"])
    x = np.asarray(rows)
    scale = np.abs(x).max(axis=0)
    scale[scale == 0] = 1.0
    coef = np.linalg.lstsq(x / scale, np.asarray(ys), rcond=None)[0]
    per_psum, per_move, per_flop = coef[:3] / scale[:3]
    return (float(per_move / per_psum), float(per_flop / per_psum),
            float(per_psum))


def _score(combos, move_w, flop_w):
    """Per combo: the pick, its seconds over the best arm's, and the
    rank correlation of model cost with seconds."""
    out = []
    for c in combos:
        arms = c["arms"]
        i = _pick(arms, move_w, flop_w)
        secs = [a["sec"] for a in arms]
        out.append({"pick": i, "pick_vs_best": arms[i]["sec"] / min(secs),
                    "spearman_rho": _spearman(
                        [_arm_cost(a, move_w, flop_w) for a in arms],
                        secs)})
    return out


def sweep(sizes, names, out_path, mesh_shape=None) -> None:
    """Cost-model validation sweep: for each operand-layout combo,
    force EVERY candidate plan (output tiling x contraction placement)
    as a measured arm, record its median wall time beside the model's
    parts (psum bytes, operand bytes at the width they move, FLOPs a
    chip), fit the two contraction weights to the times
    (:func:`fit_weights`), and report for each combo the pick and its
    time over the best arm's, under the committed weights and the
    fitted ones. Also records calibrate_flop_weight on this backend."""
    import jax

    import spartan_tpu as st
    from spartan_tpu.array import distarray as da
    from spartan_tpu.array import tiling
    from spartan_tpu.expr import tiling_cost as tc
    from spartan_tpu.expr.base import as_expr
    from spartan_tpu.expr.contract import ContractExpr
    from spartan_tpu.expr.dot import DotExpr
    from spartan_tpu.expr.optimize import dag_nodes
    from spartan_tpu.utils.config import FLAGS

    iters = 3 if SMALL else 13
    mesh = st.build_mesh(shape=mesh_shape)
    platform = tc._platform(mesh)
    report = {"platform": platform,
              "device_kind": jax.devices()[0].device_kind,
              "devices": len(jax.devices()),
              "mesh": {k: int(v) for k, v in mesh.shape.items()},
              "sizes": list(sizes), "iters": iters,
              "swept_weights": {
                  "operand_move": tc._operand_move_weight(mesh),
                  "flop": tc._flop_weight(mesh)},
              "combos": []}
    with st.use_mesh(mesh):
        report["calibrated_flop_weight"] = round(
            tc.calibrate_flop_weight(mesh=mesh), 9)
        FLAGS.opt_auto_tiling = False  # arms are forced manually
        for n in sizes:
            def make(key, shape):
                ka, kb = jax.random.split(key)
                return (jax.random.uniform(ka, shape, np.float32),
                        jax.random.uniform(kb, shape, np.float32))

            key = jax.random.PRNGKey(n)
            gemm = make(key, (n, n))
            # batch, m and k all divide the mesh axes
            bmm = make(key, (8, n // 4, n // 4))
            for name in names:
                ta_name, tb_name, kind = _COMBOS[name]
                a, b = gemm if kind == "gemm" else bmm
                ta = getattr(tiling, ta_name)(a.ndim)
                tb = getattr(tiling, tb_name)(b.ndim)
                ea = as_expr(da.from_jax(
                    jax.device_put(a, ta.sharding(mesh)), ta, mesh))
                eb = as_expr(da.from_jax(
                    jax.device_put(b, tb.sharding(mesh)), tb, mesh))

                def chain():
                    if kind == "gemm":
                        return st.dot(ea, eb).optimized()
                    return st.einsum("bij,bjk->bik", ea, eb).optimized()

                def node_of(e):
                    return [x for x in dag_nodes(e)
                            if isinstance(x, (DotExpr, ContractExpr))][0]

                probe = chain()
                (_, model_arms), = tc.gemm_plan_costs(probe).items()
                width = tc._moved_width(node_of(probe), platform)[0]
                plans = [(t, s) for t in tc.candidates(node_of(probe), mesh)
                         for s in tc._dot_strategies(t, mesh)]
                arms, exprs = [], []
                for t, s in plans:
                    e = chain()
                    d = node_of(e)
                    d._dot_plan = (t, s)
                    if t != d._default_tiling():
                        d._forced_tiling = t
                    parts = _unit_components(e, FLAGS, tc)
                    arms.append({"tiling": t.axes, "strategy": s,
                                 "psum_bytes": parts.get("psum", 0.0),
                                 "move_bytes": parts.get("reshard", 0.0),
                                 "flops": parts.get("contraction", 0.0)})
                    exprs.append(e)
                secs = _time_arms(exprs, iters)
                for arm, sec in zip(arms, secs):
                    arm["sec"] = round(sec, 6)
                w = report["swept_weights"]
                mine = arms[_pick(arms, w["operand_move"], w["flop"])]
                report["combos"].append({
                    "combo": name, "n": n, "move_width": width,
                    "arms": arms,
                    "model_pick_matches_planner": (
                        (mine["tiling"], mine["strategy"])
                        == (model_arms[0][0].axes, model_arms[0][1]))})
                print(json.dumps({"combo": name, "n": n,
                                  "secs": [a["sec"] for a in arms]}),
                      flush=True)
                del exprs, probe, ea, eb
                st.clear_compile_cache()  # cached plans pin operands
    FLAGS.reset_all()
    _summarize(report)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "combos"},
                     indent=1))


def _unit_components(e, FLAGS, tc):
    """The chosen plan's cost parts at unit weights: raw FLOPs a chip,
    psum bytes and operand bytes moved."""
    prev = (FLAGS.tiling_flop_weight, FLAGS.tiling_operand_move_weight)
    FLAGS.tiling_flop_weight = 1.0
    FLAGS.tiling_operand_move_weight = 1.0
    try:
        return tc.class_components(e)
    finally:
        FLAGS.tiling_flop_weight, FLAGS.tiling_operand_move_weight = prev


def _summarize(report) -> None:
    """Fit the weights, then score four pricings: the weights at the
    sweep's run with operand moves at full width (``before``) and at
    the width they move (``moved_width``), the fitted weights, and the
    weights the code commits now."""
    from spartan_tpu.expr import tiling_cost as tc

    combos = report["combos"]
    move_w, flop_w, sec_per_byte = fit_weights(combos)
    report["fit"] = {"operand_move": round(move_w, 4),
                     "flop": float(f"{flop_w:.4g}"),
                     "sec_per_psum_byte": float(f"{sec_per_byte:.4g}")}
    swept = report["swept_weights"]
    platform = report["platform"]
    pricings = {
        "before": (swept["operand_move"], swept["flop"], True),
        "moved_width": (swept["operand_move"], swept["flop"], False),
        "fitted": (move_w, flop_w, False),
        "committed": (tc._OPERAND_MOVE_WEIGHT_DEFAULTS.get(
                          platform, tc._OPERAND_MOVE_WEIGHT_FALLBACK),
                      tc._FLOP_WEIGHT_DEFAULTS.get(
                          platform, tc._FLOP_WEIGHT_FALLBACK), False)}
    for key, (mw, fw, full_width) in pricings.items():
        priced = [{"arms": [dict(a, move_bytes=a["move_bytes"]
                                 / (c["move_width"] if full_width else 1))
                            for a in c["arms"]]} for c in combos]
        scores = _score(priced, mw, fw)
        for c, sc in zip(combos, scores):
            arm = c["arms"][sc["pick"]]
            c[key] = {"pick": [arm["tiling"], arm["strategy"]],
                      "pick_vs_best": round(sc["pick_vs_best"], 3),
                      "spearman_rho": round(sc["spearman_rho"], 3)}
        report[key] = {
            "operand_move": mw, "flop": fw,
            "move_bytes": "full width" if full_width else "moved width",
            "max_pick_vs_best": round(
                max(s["pick_vs_best"] for s in scores), 3),
            "mean_spearman_rho": round(
                float(np.mean([s["spearman_rho"] for s in scores])), 3)}
    for c in combos:
        c["best"] = min(c["arms"], key=lambda a: a["sec"])["sec"]


def _arg(flag: str, default: str) -> str:
    return (sys.argv[sys.argv.index(flag) + 1] if flag in sys.argv
            else default)


if __name__ == "__main__":
    if "--refit" in sys.argv:
        # re-score a recorded sweep under the weights committed now
        path = _arg("--refit", "")
        with open(path) as f:
            rep = json.load(f)
        _summarize(rep)
        with open(path, "w") as f:
            json.dump(rep, f, indent=1)
        print(json.dumps({k: v for k, v in rep.items() if k != "combos"},
                         indent=1))
    elif "--sweep" in sys.argv:
        here = os.path.dirname(os.path.abspath(__file__))
        sweep(sizes=[int(v) for v in _arg(
                  "--sizes", "512" if SMALL else "1024").split(",")],
              names=_arg("--combos", ",".join(_COMBOS)).split(","),
              out_path=_arg("--out", os.path.join(here,
                                                  "tiling_sweep.json")),
              mesh_shape=(tuple(int(v) for v in _arg("--mesh", "").split(
                  "x")) if "--mesh" in sys.argv else None))
    else:
        main()
