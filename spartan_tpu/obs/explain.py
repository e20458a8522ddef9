"""Plan introspection: make every ``evaluate()`` explainable after the
fact.

``st.explain(expr)`` answers "what will (or did) this evaluate do":
which optimizer passes ran and how they changed the DAG, which tiling
the cost model chose per node (with its cost estimate), where reshard
collectives were planned, the leaf -> executable argument order, the
donation slots of the last dispatch, and the compiled program's
``cost_analysis()`` FLOPs/bytes.

The structured report is built ONCE, on the plan-cache miss path
(``expr/base._build_plan`` calls :func:`build_plan_report` and stores
the dict on the ``_Plan``), so explaining a cached plan is a signature
traversal + dict copy — no optimizer re-run. Explaining a never-
evaluated expr builds (and caches) its plan without dispatching, so
the following ``evaluate()`` hits. The ``cost_analysis`` field is the
one lazy part: the first request AOT-lowers and XLA-compiles the
plan's traced function (memoized on the plan; pass ``cost=False`` to
skip).

Top-level imports stay off the expr layer (cycle: expr/base imports
this module); expr/tiling helpers load lazily inside the builders.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple


def key_hash(key: Any) -> Optional[str]:
    """Short printable digest of a plan/compile cache key (process-
    stable, matching what evaluate spans carry)."""
    if key is None:
        return None
    return format(hash(key) & 0xFFFFFFFFFFFF, "012x")


def _label(node: Any) -> str:
    return f"{type(node).__name__}#{node._id}"


def _fmt_bytes(n: Any) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024.0
    return f"{n:.1f}GiB"


def _site_str(site: Optional[Tuple[str, int, str]]) -> Optional[str]:
    return f"{site[0]}:{site[1]} (in {site[2]})" if site else None


def _leaf_entries(leaves: Sequence[Any]) -> List[Dict[str, Any]]:
    from ..expr.base import ScalarExpr, ValExpr

    out = []
    for pos, leaf in enumerate(leaves):
        if isinstance(leaf, ScalarExpr):
            out.append({"pos": pos, "kind": "scalar",
                        "weak_kind": leaf.weak_kind})
        else:
            kind = "val" if isinstance(leaf, ValExpr) else "cached"
            out.append({"pos": pos, "kind": kind, "shape": leaf.shape,
                        "dtype": str(leaf.dtype),
                        "tiling": leaf.out_tiling().axes})
    return out


def _arg_specs(leaves: Sequence[Any]) -> List[Any]:
    """Abstract argument specs matching the plan's traced function —
    enough to AOT-lower for cost_analysis without real buffers."""
    import jax

    from ..expr.base import ScalarExpr

    specs: List[Any] = []
    for leaf in leaves:
        if isinstance(leaf, ScalarExpr):
            specs.append(leaf.pyvalue)
        else:
            specs.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
    return specs


def _tiling_entries(dag: Any) -> List[Dict[str, Any]]:
    from ..expr.base import ScalarExpr, ValExpr
    from ..expr.optimize import dag_nodes

    out = []
    for n in dag_nodes(dag):
        if isinstance(n, (ValExpr, ScalarExpr)):
            continue
        try:
            tiling = n.out_tiling().axes
        except Exception:
            tiling = None
        entry: Dict[str, Any] = {
            "node": _label(n), "shape": n.shape, "dtype": str(n.dtype),
            "tiling": tiling, "forced": n._forced_tiling is not None,
        }
        cost = getattr(n, "_plan_cost", None)
        if cost is not None:
            entry["cost_estimate"] = round(float(cost), 3)
        plan = getattr(n, "_dot_plan", None)
        if plan is not None:
            entry["contraction"] = {"grid": plan[0].axes,
                                    "strategy": plan[1]}
        site = _site_str(n._site)
        if site is not None:
            entry["site"] = site
        out.append(entry)
    return out


def _reshard_edges(dag: Any) -> List[Dict[str, Any]]:
    """Edges where the plan demands an operand layout different from
    the child's own output layout — the points a resharding collective
    (all-gather / all-to-all) must materialize. With
    ``FLAGS.redistribution_planner`` on, each edge also names its
    CHOSEN collective schedule, the modeled cost, and whether the
    explicit lowering or the GSPMD fallback was taken — the A/B is
    readable from one ``st.explain`` call."""
    from ..expr import tiling_cost
    from ..expr.optimize import dag_nodes
    from ..parallel import mesh as mesh_mod
    from ..parallel import redistribute as redist_mod
    from . import ledger as ledger_mod

    mesh = mesh_mod.get_mesh()
    planner = redist_mod.planner_on()
    factors = ledger_mod.factors() if planner else None
    edges = []
    for n in dag_nodes(dag):
        kids = n.children()
        if not kids:
            continue
        try:
            t = n.out_tiling()
        except Exception:
            continue
        cview = tiling_cost._contraction_view(n)
        reqs: List[Optional[Any]] = [None] * len(kids)
        if cview is not None and getattr(n, "_dot_plan", None) is not None:
            grid, strategy = n._dot_plan
            try:
                reqs = list(cview[1](grid, strategy))
            except Exception:
                reqs = [None] * len(kids)
        else:
            for i, c in enumerate(kids):
                try:
                    reqs[i] = tiling_cost._operand_requirement(n, t, c, i)
                except Exception:
                    reqs[i] = None
        for i, (c, req) in enumerate(zip(kids, reqs)):
            if req is None:
                continue
            try:
                src = c.out_tiling().axes
            except Exception:
                continue
            if src == req.axes:
                continue
            nbytes = float(c.size) * c.dtype.itemsize
            try:
                moved = tiling_cost.reshard_cost(
                    c.out_tiling(), req, nbytes, mesh)
            except Exception:
                moved = None
            if moved == 0.0:
                continue  # e.g. replicated source: no wire traffic
            entry = {
                "edge": f"{_label(c)} -> {_label(n)}", "operand": i,
                "src": src, "dst": req.axes,
                "bytes_per_chip": (round(moved, 1)
                                   if moved is not None else None),
            }
            if planner:
                # the SAME decision the lowering seam makes for this
                # edge (redistribute.constrain) — schedule, modeled
                # cost and explicit-vs-GSPMD path
                try:
                    d = redist_mod.decide(c.out_tiling(), req,
                                          c.shape, c.dtype, mesh,
                                          factors)
                except Exception:
                    d = None
                if d is not None:
                    entry["schedule"] = d.schedule.describe()
                    entry["modeled_cost"] = round(d.cost, 1)
                    entry["path"] = ("explicit" if d.explicit
                                     else "gspmd")
                    entry["reason"] = d.reason
            edges.append(entry)
    return edges


def build_plan_report(expr: Any, dag: Any, leaves: Sequence[Any],
                      plan_key: Any, passes: List[Dict[str, Any]],
                      out_tilings: Sequence[Any],
                      arg_order: Optional[Tuple[int, ...]]
                      ) -> Dict[str, Any]:
    """The structured per-plan report, built on the miss path and
    stored on the ``_Plan`` (shared by the cached and the identity
    variant, so a cache-hit ``st.explain`` is instant)."""
    from ..parallel import mesh as mesh_mod

    # the tiling DP's prediction for this plan: the roots' cumulative
    # chosen-tiling cost (bytes-equivalent) and its per-op-class
    # decomposition — what the cost ledger compares against measured
    # dispatch time and what fit_profile calibrates from
    dp_cost: Optional[float] = None
    components: Optional[Dict[str, float]] = None
    try:
        from ..expr import tiling_cost
        from ..expr.base import TupleExpr

        roots = dag.elements if isinstance(dag, TupleExpr) else (dag,)
        vals = [getattr(r, "_plan_cost", None) for r in roots]
        vals = [float(v) for v in vals if v is not None]
        dp_cost = sum(vals) if vals else None
        components = tiling_cost.class_components(dag) or None
    except Exception:  # noqa: BLE001 - the prediction is advisory
        pass

    # planned cross-mesh migrations (elastic re-tiling): leaves that
    # were rehomed or restored through the redistribution planner
    # carry a _migration record — schedule, route, modeled wire
    # bytes, reason (docs/RESILIENCE.md "cross-mesh migration")
    migrations = None
    try:
        migs = []
        for leaf in leaves:
            arr = getattr(leaf, "value", None)
            if arr is None:
                arr = getattr(leaf, "_result", None)
            m = getattr(arr, "_migration", None)
            if m:
                migs.append(dict(m))
        migrations = migs or None
    except Exception:  # noqa: BLE001 - the report is advisory
        pass

    report: Dict[str, Any] = {
        "root": _label(expr),
        "site": _site_str(expr._site),
        "plan_key": key_hash(plan_key),
        "dp_cost": dp_cost,
        "cost_components": components,
        # the mesh generation this plan was built for: after an
        # elastic rebuild (device loss), post-recovery explains show
        # which epoch — and therefore which device set — a plan binds
        "mesh_epoch": mesh_mod.mesh_epoch(),
        "passes": passes,
        "optimized_nodes": (passes[-1]["nodes_after"] if passes
                            else None),
        "leaves": _leaf_entries(leaves),
        "arg_order": (list(arg_order) if arg_order is not None
                      else None),
        "out_tilings": [t.axes for t in out_tilings],
        "tilings": _tiling_entries(dag),
        "reshard_edges": _reshard_edges(dag),
        "migrations": migrations,
        "donation": {"last_donated_args": None, "donated_dispatches": 0},
        "arg_specs": _arg_specs(leaves),
        "cost_analysis": None,
    }
    # filled in by _build_plan's epilogue (scope_digest_table): the
    # digest must be computed from FINAL node state, after every
    # build-time walk that can stamp tiling decisions onto nodes
    report["scope_digests"] = {}
    return report


def scope_digest_table(dag: Any) -> Dict[str, Dict[str, Any]]:
    """digest -> node table for the plan auditor: the SAME ``__sg_``
    scope digests a naming session (obs/profile.py) stamps into this
    plan's lowered HLO, mapped back to node label + user build site.
    Called at the very END of ``_build_plan`` (miss path, one extra
    signing traversal) because (a) the optimized DAG is unreachable
    once the plan is cached and (b) the build's later walks mutate
    node tiling state, which is part of the signature the trace-time
    naming session will hash."""
    try:
        from ..expr.optimize import dag_nodes
        from .profile import _NamingCtx

        nctx = _NamingCtx()
        # memoize ROOT-FIRST, exactly like the trace-time session: a
        # signing context writes ("ref", i) placeholders for already-
        # visited subtrees, so leaf-first memoization would hash
        # DIFFERENT parent signatures than the scopes in the HLO carry
        nctx.digest(dag)
        digests: Dict[str, Dict[str, Any]] = {}
        for n in dag_nodes(dag):
            dg = nctx.digest(n)
            if dg:
                digests[dg] = {"node": _label(n),
                               "site": _site_str(n._site)}
        return digests
    except Exception:  # noqa: BLE001 - attribution is advisory
        return {}


def compiled_cost_analysis(compiled: Any) -> Dict[str, float]:
    """Normalize a jax ``Compiled.cost_analysis()`` read-out — the ONE
    sanctioned call site (lint rule 9): every FLOPs/bytes estimate in
    the package flows through here so it can land in the cost ledger
    next to the model's prediction."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0] if analysis else {}
    return dict(analysis or {})


def _compute_cost_analysis(plan: Any) -> Dict[str, float]:
    """AOT-lower + compile the plan's traced function over abstract
    arg specs and read XLA's FLOPs/bytes estimate. Memoized on the
    plan report by :func:`explain`."""
    import jax

    specs = plan.report.get("arg_specs") or []
    compiled = jax.jit(plan.traced).lower(*specs).compile()
    return compiled_cost_analysis(compiled)


class ExplainReport:
    """Structured plan report with a pretty ``str()`` rendering.

    ``.data`` is the raw dict; the common fields are attributes:
    ``cache`` ('hit' / 'miss' / 'evaluated'), ``plan_key``,
    ``passes``, ``tilings``, ``reshard_edges``, ``leaves``,
    ``arg_order``, ``donation``, ``cost_analysis``, ``flops``, and —
    once ``st.profile`` or the ``FLAGS.profile_sample_every`` sampler
    has measured this plan — ``device_profile`` (per-node measured
    device seconds next to the modeled costs, hottest first).
    """

    def __init__(self, data: Dict[str, Any]):
        self.data = data

    def __getattr__(self, name: str) -> Any:
        try:
            return self.__dict__["data"][name]
        except KeyError:
            raise AttributeError(name)

    def to_dict(self) -> Dict[str, Any]:
        out = dict(self.data)
        out.pop("arg_specs", None)  # not JSON-serializable, internal
        return out

    @property
    def flops(self) -> Optional[float]:
        ca = self.data.get("cost_analysis")
        return ca.get("flops") if ca else None

    def __str__(self) -> str:
        d = self.data
        lines = [f"plan for {d.get('root')} "
                 f"[cache {d.get('cache', '?')}, "
                 f"key {d.get('plan_key')}]"]
        if d.get("site"):
            lines.append(f"  built at {d['site']}")
        if d.get("mesh_epoch"):  # epoch 0 (no rebuild yet) is implied
            lines.append(f"  mesh epoch {d['mesh_epoch']} "
                         "(rebuilt after device loss)")
        if d.get("passes"):
            lines.append("  passes:")
            for p in d["passes"]:
                delta = p["nodes_after"] - p["nodes_before"]
                lines.append(
                    f"    {p['name']:<18} {p['nodes_before']:>4} -> "
                    f"{p['nodes_after']:<4} nodes ({delta:+d}) "
                    f"{p.get('seconds', 0.0) * 1e3:8.2f} ms")
        if d.get("tilings"):
            lines.append("  tilings:")
            for t in d["tilings"]:
                extra = ""
                if t.get("forced"):
                    extra += " FORCED"
                if t.get("cost_estimate") is not None:
                    extra += f" cost~{t['cost_estimate']}"
                if t.get("contraction"):
                    cstrat = t["contraction"]
                    extra += (f" contraction(grid={cstrat['grid']}, "
                              f"axis={cstrat['strategy']})")
                lines.append(f"    {t['node']:<22} {str(t['shape']):<16} "
                             f"{str(t['tiling']):<14}{extra}")
        pz = d.get("persist")
        if pz:
            # warm-start provenance (spartan_tpu/persist): whether the
            # executable was restored from the on-disk store or
            # compiled here — and, for a compile, why a store entry
            # was not usable (corrupt / stale / version skew / io)
            if pz.get("source") == "disk":
                line = "  persist: disk hit"
            else:
                line = "  persist: compiled"
                if pz.get("stored"):
                    line += ", stored to cache dir"
            if pz.get("digest"):
                line += f" (entry {str(pz['digest'])[:12]})"
            if pz.get("reason"):
                line += f" [fallback: {pz['reason']}]"
            lines.append(line)
        if d.get("reshard_edges"):
            lines.append("  reshard edges:")
            for e in d["reshard_edges"]:
                line = (f"    {e['edge']}: {e['src']} -> {e['dst']} "
                        f"(~{e['bytes_per_chip']} B/chip)")
                if e.get("schedule"):
                    # planned edge: chosen schedule, modeled cost, and
                    # which path the lowering took (the one-call A/B)
                    line += (f" via {e['schedule']} [{e['path']}, "
                             f"cost~{e['modeled_cost']}]")
                lines.append(line)
        aud = d.get("audit")
        if aud:
            # static communication audit (analysis/plan_audit.py):
            # the per-node collective table with modeled wire bytes,
            # plus any findings (full_gather / replicated_intermediate
            # / missed_donation) — docs/ANALYSIS.md explains how to
            # read it
            from ..analysis.plan_audit import PlanAudit

            for ln in str(PlanAudit.from_dict(aud)).splitlines():
                lines.append("  " + ln)
        if d.get("migrations"):
            # leaves that crossed a mesh-shape transition (elastic
            # rehome / checkpoint restore) through the migration
            # planner: per-array schedule + bytes + route + reason
            lines.append("  migrations (cross-mesh re-tiling):")
            for m in d["migrations"]:
                line = (f"    {str(m.get('shape', '?')):<14} "
                        f"{str(m.get('src_tiling', '?'))} -> "
                        f"{str(m.get('dst_tiling', '?'))} "
                        f"[{m.get('route')}, "
                        f"~{m.get('bytes', 0)} B]")
                if m.get("schedule"):
                    line += f" via {m['schedule']}"
                if m.get("reason"):
                    line += f" ({m['reason']})"
                lines.append(line)
        dp = d.get("device_profile")
        if dp:
            # measured device time (obs/profile.py: st.profile or the
            # FLAGS.profile_sample_every sampler) next to the modeled
            # cost, hottest nodes first — the measured counterpart of
            # the tilings section's cost estimates
            lines.append(
                f"  device profile [{dp.get('tier')}]: wall "
                f"{dp.get('wall_s', 0.0) * 1e3:.3f}ms, attributed "
                f"{dp.get('attributed_fraction', 0.0) * 100:.1f}% "
                f"(unattributed "
                f"{dp.get('unattributed_s', 0.0) * 1e3:.3f}ms)")
            nodes = dp.get("nodes") or []
            shown = nodes if len(nodes) <= 8 else nodes[:5]
            for n in shown:
                modeled = (f" modeled~{n['modeled_cost']}"
                           if n.get("modeled_cost") is not None else "")
                lines.append(
                    f"    {n['node']:<24} "
                    f"{n['seconds'] * 1e3:9.3f}ms "
                    f"{n.get('share', 0.0) * 100:5.1f}%"
                    f"{modeled}")
            if len(nodes) > len(shown):
                lines.append(f"    ... ({len(nodes) - len(shown)} "
                             "more attributed node(s))")
        sk = d.get("skew")
        if sk:
            # shard-level skew (obs/skew.py: st.skew or the sampler):
            # the per-DEVICE view under the per-node seconds above —
            # hottest shard, per-node imbalance ratios, and the
            # barrier wait attributed to the plan's collective edges
            line = (f"  shard skew [{sk.get('tier')}]: imbalance "
                    f"max/mean {sk.get('imbalance_ratio') or 'n/a'}")
            hs = sk.get("hottest_shard")
            if hs:
                line += (f", hottest shard {hs['device']} "
                         f"({hs['seconds'] * 1e3:.3f}ms)")
            lines.append(line)
            for r in (sk.get("nodes") or [])[:3]:
                lines.append(
                    f"    {r['node']:<24} ratio {r['ratio']:<7} wait "
                    f"{r['wait_s'] * 1e3:8.3f}ms  straggler "
                    f"{r['straggler']}")
            for e in (sk.get("straggler_edges") or [])[:3]:
                kinds = ", ".join(f"{k}x{n}" if n > 1 else k
                                  for k, n in sorted(e["kinds"].items()))
                lines.append(
                    f"    edge {e['node']:<19} {kinds:<18} wait "
                    f"{e['wait_s'] * 1e3:8.3f}ms")
            adv = sk.get("advisory")
            if adv:
                lines.append(
                    f"    ADVISORY: re-tile {adv['src']} -> "
                    f"{adv['dst']} ~cost {adv['modeled_cost']} "
                    f"via {adv['schedule']} (report-only)")
        integ = d.get("integrity")
        if integ:
            # SDC sentinel verdict (resilience/integrity.py): the last
            # sampled checksum cross-check of this plan
            line = (f"  integrity [{integ.get('verdict')}]: check "
                    f"#{integ.get('check')}, rotation "
                    f"+{integ.get('rotation')}")
            if integ.get("verdict") != "ok":
                line += (f", {integ.get('shards')} shard(s) disagree, "
                         f"suspects {integ.get('suspects')}")
                if integ.get("quarantined") is not None:
                    line += (f" — device {integ['quarantined']} "
                             "QUARANTINED")
            lines.append(line)
        if d.get("leaves") is not None:
            lines.append(f"  leaves: {len(d['leaves'])} "
                         f"(arg order {d.get('arg_order')})")
        don = d.get("donation") or {}
        if don.get("last_donated_args"):
            lines.append(
                f"  donation: args {don['last_donated_args']} donated "
                f"({don['donated_dispatches']} donated dispatch(es))")
        mem = d.get("memory")
        if mem:
            line = (f"  memory: predicted peak "
                    f"{_fmt_bytes(mem.get('peak_bytes_per_chip'))}/chip")
            if mem.get("budget_bytes"):
                line += f" (budget {_fmt_bytes(mem['budget_bytes'])})"
            if mem.get("governed_rung"):
                line += (f", GOVERNED -> rung {mem['governed_rung']}")
                if mem.get("governed_peak_bytes"):
                    line += (f" predicted "
                             f"{_fmt_bytes(mem['governed_peak_bytes'])}")
            lines.append(line)
            for top in (mem.get("top") or [])[:5]:
                lines.append(f"    {top['node']:<28} "
                             f"{_fmt_bytes(top['bytes'])}")
            val = mem.get("validation")
            if val:
                lines.append(
                    f"    validated: xla peak "
                    f"{_fmt_bytes(val.get('xla_peak_bytes'))}, "
                    f"predicted/actual {val.get('error_ratio')}")
        res = d.get("resilience")
        if res:
            line = f"  resilience: retries={res.get('retries', 0)}"
            if res.get("rung"):
                line += f", degraded rung={res['rung']}"
                # a PREDICTIVE pick (memory governor, before any
                # dispatch) must be distinguishable from a REACTIVE
                # one (after a real OOM) in bug reports
                line += f" ({res.get('origin', 'reactive')}"
                if res.get("rung_predicted_bytes") is not None:
                    line += (", predicted "
                             f"{_fmt_bytes(res['rung_predicted_bytes'])}")
                line += ")"
            if res.get("restores"):
                line += f", loop restores={res['restores']}"
            if res.get("resumed_from") is not None:
                line += f", resumed from iteration {res['resumed_from']}"
            lines.append(line)
            for fault in (res.get("faults") or [])[:3]:
                lines.append(f"    fault [{fault['class']}]: "
                             f"{fault['error']}")
        sv = d.get("serve")
        if sv:
            lines.append(
                f"  serve: coalesced {sv.get('batches', 0)} batch(es), "
                f"last batch={sv.get('last_batch')} client(s) "
                f"[{sv.get('mode')}], {sv.get('requests', 0)} "
                f"request(s) total")
        ca = d.get("cost_analysis")
        if ca:
            lines.append(
                f"  cost_analysis: flops={ca.get('flops')} "
                f"bytes={ca.get('bytes accessed')}")
        elif ca is None and "cost_analysis" in d:
            lines.append("  cost_analysis: (skipped; "
                         "st.explain(expr, cost=True) to compile)")
        inc = d.get("incremental")
        if inc:
            # delta-aware evaluation (expr/incremental.py): what the
            # last warm dispatch of this plan did — served whole from
            # the result cache, recomputed a dirty sub-region, or fell
            # back to full with the reason (the honest-fallback trail)
            line = f"  incremental: {inc.get('mode')}"
            if inc.get("dirty_frac") is not None:
                line += f", dirty_frac={inc['dirty_frac']}"
            if inc.get("dirty_box"):
                ul, lr = inc["dirty_box"]
                line += f", box {tuple(ul)}..{tuple(lr)}"
            if inc.get("fallback"):
                line += f" [fallback: {inc['fallback']}]"
            line += (f" (cache {_fmt_bytes(inc.get('cache_bytes', 0))}"
                     f" in {inc.get('entries', 0)} entr(ies))")
            lines.append(line)
            for nd in (inc.get("nodes") or [])[:8]:
                lines.append(
                    f"    {nd['node']:<24} dirty "
                    f"{nd['dirty_tiles']}/{nd['tiles']} tile(s)")
        return "\n".join(lines)

    __repr__ = __str__


def explain(expr: Any, cost: bool = True) -> ExplainReport:
    """Explain the evaluation plan for ``expr`` (see module docstring).

    ``cost=True`` (default) also fills ``cost_analysis`` — the first
    call per plan pays an AOT XLA compile; later calls reuse it.
    Never dispatches: explaining an unevaluated expr pre-plans it (the
    next ``evaluate()`` is a plan-cache hit)."""
    from ..expr import base
    from ..parallel import mesh as mesh_mod

    root = expr if isinstance(expr, base.Expr) else base.as_expr(expr)
    if root._result is not None:
        return ExplainReport({
            "root": _label(root), "site": _site_str(root._site),
            "cache": "evaluated", "plan_key": None, "passes": [],
            "tilings": [], "reshard_edges": [], "leaves": None,
            "arg_order": None, "donation": {}, "cost_analysis": None,
            # the resilience record (retries taken, OOM rung reached,
            # loop restores/resume) survives on the expr even after
            # its plan report is unreachable through the cache
            "resilience": getattr(root, "_resilience", None),
            "note": "expr already carries a result; nothing to plan",
        })

    mesh = mesh_mod.get_mesh()
    plan_key, rctx = base.plan_signature(root, mesh)
    plan = base.lookup_plan(plan_key)
    status = "hit" if plan is not None else "miss"
    if plan is None:
        plan, dag, _ = base._build_plan(root, mesh, rctx, plan_key)
        if plan is None:  # optimizer collapsed to an already-held result
            return ExplainReport({
                "root": _label(root), "site": _site_str(root._site),
                "cache": "evaluated", "plan_key": key_hash(plan_key),
                "passes": [], "tilings": [], "reshard_edges": [],
                "leaves": None, "arg_order": None, "donation": {},
                "cost_analysis": None,
                "note": "optimized DAG already carries a result",
            })
    if cost and plan.report.get("cost_analysis") is None:
        plan.report["cost_analysis"] = _compute_cost_analysis(plan)
        # the measured FLOPs land in the cost ledger next to the
        # tiling DP's prediction for the same plan digest
        from . import ledger as ledger_mod

        ledger_mod.note_cost_analysis(plan.report.get("plan_key"),
                                      plan.report["cost_analysis"])
    data = dict(plan.report)
    data["cache"] = status
    return ExplainReport(data)
