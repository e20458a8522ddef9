"""tools/lint_repo.py in the tier-1 flow: the codebase must stay clean
under its own AST lint, and the lint itself must catch the bug classes
it exists for (the pre-0.9 experimental shard_map; Expr subclasses missing the
structural hooks; raw wall-clock timing that escapes the trace)."""

import ast
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import lint_repo  # noqa: E402


def test_repo_is_clean():
    findings = lint_repo.run_lint()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_catches_experimental_shard_map(tmp_path):
    bad = tmp_path / "bad_mod.py"
    bad.write_text(
        "from jax.experimental.shard_map import shard_map\n"
        "import jax\n"
        "f = jax.experimental.shard_map\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_shard_map_imports(str(bad), tree)
    assert any(f.rule == "experimental-shard-map" for f in findings)


def test_allows_jax_shard_map(tmp_path):
    ok = tmp_path / "ok_mod.py"
    ok.write_text("from jax import shard_map\nimport jax\n"
                  "f = jax.shard_map\n")
    tree = ast.parse(ok.read_text(), filename=str(ok))
    assert lint_repo.lint_shard_map_imports(str(ok), tree) == []


def test_catches_raw_timing(tmp_path):
    bad = tmp_path / "timed_mod.py"
    bad.write_text(
        "import time\n"
        "import time as _time\n"
        "from time import perf_counter\n"
        "t0 = time.perf_counter()\n"
        "t1 = _time.monotonic()\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_raw_timing(str(bad), tree)
    assert sum(f.rule == "raw-timing" for f in findings) == 3
    # ... and the span/phase API is named in the remedy
    assert all("span/phase" in f.message for f in findings)


def test_raw_timing_allowed_in_obs_and_profiling():
    obs_path = os.path.join(lint_repo.REPO, "spartan_tpu", "obs",
                            "trace.py")
    prof_path = os.path.join(lint_repo.REPO, "spartan_tpu", "utils",
                             "profiling.py")
    tree = ast.parse("import time\nt = time.perf_counter()\n")
    assert lint_repo.lint_raw_timing(obs_path, tree) == []
    assert lint_repo.lint_raw_timing(prof_path, tree) == []
    # time.time()/sleep etc. are NOT flagged anywhere (not timing)
    other = ast.parse("import time\ntime.sleep(0.1)\nt = time.time()\n")
    assert lint_repo.lint_raw_timing("/x/y.py", other) == []


def test_catches_expr_subclass_missing_hooks(tmp_path):
    mod = tmp_path / "exprs.py"
    mod.write_text(
        "class Expr:\n"
        "    def _sig(self, ctx): raise NotImplementedError\n"
        "    def replace_children(self, k): raise NotImplementedError\n"
        "class GoodExpr(Expr):\n"
        "    def _sig(self, ctx): return ('good',)\n"
        "    def replace_children(self, k): return self\n"
        "class InheritsGood(GoodExpr):\n"
        "    pass\n"
        "class BadExpr(Expr):\n"
        "    def _sig(self, ctx): return ('bad',)\n")
    findings = lint_repo.lint_expr_subclasses([str(mod)])
    names = {(f.rule, "BadExpr" in f.message) for f in findings}
    assert ("expr-subclass-hooks", True) in names
    # the hook-complete classes (direct or inherited) are NOT flagged
    assert not any("GoodExpr" in f.message or "InheritsGood" in f.message
                   for f in findings)


def test_catches_raw_debug_callbacks(tmp_path):
    bad = tmp_path / "telemetry_mod.py"
    bad.write_text(
        "import jax\n"
        "import jax.debug\n"
        "from jax import debug\n"
        "from jax.debug import callback\n"
        "jax.debug.callback(lambda x: x, 1)\n"
        "jax.debug.print('{}', 1)\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_debug_callbacks(str(bad), tree)
    assert sum(f.rule == "raw-debug-callback" for f in findings) == 5
    # ... and the sentinel API is named in the remedy
    assert all("numerics" in f.message for f in findings)


def test_debug_callbacks_allowed_in_obs_and_loop():
    numerics_path = os.path.join(lint_repo.REPO, "spartan_tpu", "obs",
                                 "numerics.py")
    loop_path = os.path.join(lint_repo.REPO, "spartan_tpu", "expr",
                             "loop.py")
    tree = ast.parse("import jax\njax.debug.callback(lambda: None)\n")
    assert lint_repo.lint_debug_callbacks(numerics_path, tree) == []
    assert lint_repo.lint_debug_callbacks(loop_path, tree) == []
    # unrelated .print attributes (not jax.debug) are NOT flagged
    other = ast.parse("console.print('x')\nobj.debug.callback()\n")
    assert lint_repo.lint_debug_callbacks("/x/y.py", other) == []


def test_catches_bare_recovery(tmp_path):
    bad = tmp_path / "retry_mod.py"
    bad.write_text(
        "def f(expr):\n"
        "    try:\n"
        "        return expr.evaluate()\n"
        "    except RuntimeError:\n"
        "        return expr.evaluate()\n"
        "def g(expr):\n"
        "    try:\n"
        "        out = expr.force()\n"
        "    except Exception as e:\n"
        "        out = None\n"
        "    return out\n"
        "def h(fn):\n"
        "    try:\n"
        "        return jax.jit(fn)()\n"
        "    except:\n"
        "        return None\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_bare_recovery(str(bad), tree)
    assert sum(f.rule == "bare-recovery" for f in findings) == 3
    # ... and the policy engine is named in the remedy
    assert all("resilience" in f.message for f in findings)


def test_bare_recovery_allows_engine_route_and_resilience_dir():
    # the sanctioned boundary: a handler routing into the engine
    routed = ast.parse(
        "def ev(expr):\n"
        "    try:\n"
        "        return _dispatch(expr)\n"
        "    except Exception as e:\n"
        "        return _handle_failure(e, expr)\n")
    assert lint_repo.lint_bare_recovery("/x/y.py", routed) == []
    # the resilience subsystem itself may catch broadly
    eng = os.path.join(lint_repo.REPO, "spartan_tpu", "resilience",
                       "engine.py")
    broad = ast.parse(
        "try:\n"
        "    expr.evaluate()\n"
        "except Exception:\n"
        "    pass\n")
    assert lint_repo.lint_bare_recovery(eng, broad) == []
    # specific exceptions around dispatch are fine anywhere
    specific = ast.parse(
        "try:\n"
        "    expr.evaluate()\n"
        "except ValueError:\n"
        "    pass\n")
    assert lint_repo.lint_bare_recovery("/x/y.py", specific) == []
    # broad except NOT around dispatch calls is rule-5-clean too
    unrelated = ast.parse(
        "try:\n"
        "    x = parse(text)\n"
        "except Exception:\n"
        "    x = None\n")
    assert lint_repo.lint_bare_recovery("/x/y.py", unrelated) == []


def test_catches_shared_state_access(tmp_path):
    bad = tmp_path / "rogue.py"
    bad.write_text(
        "from spartan_tpu.expr import base\n"
        "base._plan_cache.clear()\n"
        "x = base._compile_cache\n"
        "with base._cache_lock:\n"
        "    pass\n"
        "from spartan_tpu.obs.metrics import REGISTRY\n"
        "REGISTRY._counters['hacked'] = 1\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_shared_state(str(bad), tree)
    assert sum(f.rule == "shared-state" for f in findings) == 4
    # ... and the remedy names the sanctioned accessors
    assert any("lookup_plan" in f.message for f in findings)
    assert any("REGISTRY.counter()" in f.message for f in findings)


def test_shared_state_allowed_in_owners():
    # the owning modules ARE the locking discipline; each may touch
    # its own tables (and only its own — expr/base must still go
    # through the registry API and vice versa)
    for rel in (os.path.join("spartan_tpu", "expr", "base.py"),
                os.path.join("spartan_tpu", "obs", "metrics.py")):
        path = os.path.join(lint_repo.REPO, rel)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        assert lint_repo.lint_shared_state(path, tree) == []


def test_shared_state_accessor_use_is_clean(tmp_path):
    ok = tmp_path / "client.py"
    ok.write_text(
        "from spartan_tpu.expr.base import lookup_plan, store_plan\n"
        "from spartan_tpu.obs.metrics import REGISTRY\n"
        "plan = lookup_plan(('key',))\n"
        "REGISTRY.counter('serve_requests').inc()\n"
        "REGISTRY.gauge('serve_queue_depth').set(3)\n")
    tree = ast.parse(ok.read_text(), filename=str(ok))
    assert lint_repo.lint_shared_state(str(ok), tree) == []


def test_catches_mesh_capture(tmp_path):
    bad = tmp_path / "cachey.py"
    bad.write_text(
        "from spartan_tpu.parallel.mesh import get_mesh, build_mesh\n"
        "from jax.sharding import Mesh\n"
        "_MESH = get_mesh()\n"                       # module global
        "GRID = build_mesh(None, shape=(4, 2))\n"    # module global
        "class Planner:\n"
        "    mesh = Mesh(None, ('x', 'y'))\n"        # class attribute
        "def refresh():\n"
        "    global _MESH\n"
        "    _MESH = get_mesh()\n")                  # global via decl
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_mesh_capture(str(bad), tree)
    assert sum(f.rule == "mesh-capture" for f in findings) == 4
    assert all("rebuild_mesh" in f.message for f in findings)


def test_mesh_capture_allows_use_time_and_instances(tmp_path):
    ok = tmp_path / "clean.py"
    ok.write_text(
        "from spartan_tpu.parallel.mesh import get_mesh\n"
        "def run():\n"
        "    mesh = get_mesh()\n"                   # use-time local
        "    return mesh\n"
        "class Arr:\n"
        "    def __init__(self):\n"
        "        self.mesh = get_mesh()\n")         # instance attr
    tree = ast.parse(ok.read_text(), filename=str(ok))
    assert lint_repo.lint_mesh_capture(str(ok), tree) == []


def test_mesh_capture_allowed_in_parallel():
    # the owning package holds the one sanctioned global (the
    # epoch-fenced _global_mesh rebuild_mesh maintains)
    path = os.path.join(lint_repo.REPO, "spartan_tpu", "parallel",
                        "mesh.py")
    tree = ast.parse("from x import get_mesh\n_M = get_mesh()\n")
    assert lint_repo.lint_mesh_capture(path, tree) == []


def test_catches_raw_memory_stats(tmp_path):
    bad = tmp_path / "probe.py"
    bad.write_text(
        "import jax\n"
        "s = jax.local_devices()[0].memory_stats()\n"
        "def probe(dev):\n"
        "    return dev.memory_stats() or {}\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_raw_memory_stats(str(bad), tree)
    assert sum(f.rule == "raw-memory-stats" for f in findings) == 2
    # ... and the sanctioned aggregate is named in the remedy
    assert all("device_memory_aggregate" in f.message for f in findings)


def test_catches_raw_profiling(tmp_path):
    bad = tmp_path / "measurer.py"
    bad.write_text(
        "import jax\n"
        "import jax.profiler\n"
        "from jax.profiler import start_trace\n"
        "with jax.profiler.trace('/tmp/t'):\n"
        "    pass\n"
        "flops = compiled.cost_analysis()\n"
        "mem = compiled.memory_analysis()\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_raw_profiling(str(bad), tree)
    # import jax.profiler + from jax.profiler import + the attribute
    # use inside the with + the two introspection calls
    assert sum(f.rule == "raw-profiling" for f in findings) == 5
    # ... and the sanctioned entry points are named in the remedy
    assert all("ledger" in f.message for f in findings)


def test_raw_profiling_allowed_in_owners():
    # per-entry-point allowlists (device-time attribution PR): the
    # capture seam lives in obs/trace.py + obs/profile.py, the
    # compiled-program introspection in obs/explain.py +
    # resilience/memory.py — neither owner inherits the other's right
    profiler_tree = ast.parse(
        "import jax\n"
        "with jax.profiler.trace('/tmp/t'):\n"
        "    pass\n")
    analysis_tree = ast.parse(
        "a = compiled.cost_analysis()\n"
        "m = compiled.memory_analysis()\n")
    for rel in (os.path.join("spartan_tpu", "obs", "trace.py"),
                os.path.join("spartan_tpu", "obs", "profile.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_raw_profiling(path, profiler_tree) == []
    for rel in (os.path.join("spartan_tpu", "obs", "explain.py"),
                os.path.join("spartan_tpu", "resilience", "memory.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_raw_profiling(path, analysis_tree) == []
    # non-call attribute reads (docs, function defs) are NOT flagged,
    # and unrelated .profiler attributes (not jax's) pass
    other = ast.parse("fn = obj.cost_analysis\n"
                      "p = torch.profiler\n"
                      "def cost_analysis(expr):\n"
                      "    return None\n")
    assert lint_repo.lint_raw_profiling("/x/y.py", other) == []


def test_rule9_tightened_within_obs():
    # obs/ membership alone no longer grants either right: a capture
    # in obs/explain.py and an analysis call in obs/trace.py are both
    # findings — obs/profile.py is the ONE new sanctioned jax.profiler
    # consumer, not the whole package
    profiler_tree = ast.parse(
        "import jax\n"
        "with jax.profiler.trace('/tmp/t'):\n"
        "    pass\n")
    analysis_tree = ast.parse("a = compiled.cost_analysis()\n")
    explain = os.path.join(lint_repo.REPO, "spartan_tpu", "obs",
                           "explain.py")
    trace = os.path.join(lint_repo.REPO, "spartan_tpu", "obs",
                         "trace.py")
    assert any(f.rule == "raw-profiling" for f in
               lint_repo.lint_raw_profiling(explain, profiler_tree))
    assert any(f.rule == "raw-profiling" for f in
               lint_repo.lint_raw_profiling(trace, analysis_tree))


def test_catches_raw_named_scope(tmp_path):
    bad = tmp_path / "scoped.py"
    bad.write_text(
        "import jax\n"
        "from jax import named_scope\n"
        "with jax.named_scope('my_kernel'):\n"
        "    pass\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_named_scopes(str(bad), tree)
    assert sum(f.rule == "raw-named-scope" for f in findings) == 2
    # ... and the sanctioned wrapper is named in the remedy
    assert all("obs.trace.named_scope" in f.message for f in findings)


def test_named_scope_allowed_in_owners():
    tree = ast.parse(
        "import jax\n"
        "with jax.named_scope('MapExpr_3__sg_ab12'):\n"
        "    pass\n")
    for rel in (os.path.join("spartan_tpu", "expr", "base.py"),
                os.path.join("spartan_tpu", "obs", "trace.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_named_scopes(path, tree) == []
    # expr/loop.py is NOT allowed raw scopes any more (it routes
    # through obs.trace.named_scope), and non-jax scopes pass
    loop = os.path.join(lint_repo.REPO, "spartan_tpu", "expr",
                        "loop.py")
    assert any(f.rule == "raw-named-scope"
               for f in lint_repo.lint_named_scopes(loop, tree))
    other = ast.parse("with torch.named_scope('x'):\n    pass\n")
    assert lint_repo.lint_named_scopes("/x/y.py", other) == []


def test_raw_memory_stats_allowed_in_owners(tmp_path):
    tree = ast.parse("import jax\n"
                     "s = jax.local_devices()[0].memory_stats()\n")
    for rel in (os.path.join("spartan_tpu", "obs", "metrics.py"),
                os.path.join("spartan_tpu", "parallel", "mesh.py"),
                os.path.join("spartan_tpu", "resilience", "memory.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_raw_memory_stats(path, tree) == []
    # attribute reads that are not calls (docs, strings) are NOT flagged
    other = ast.parse("name = 'memory_stats'\nx = obj.memory_stats\n")
    assert lint_repo.lint_raw_memory_stats("/x/y.py", other) == []


def test_catches_raw_sharding_constraint(tmp_path):
    bad = tmp_path / "bad_wsc.py"
    bad.write_text(
        "import jax\n"
        "from jax.lax import with_sharding_constraint\n"
        "x = jax.lax.with_sharding_constraint(x, s)\n"
        "y = with_sharding_constraint(y, s)\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_sharding_constraints(str(bad), tree)
    # the import binding + the attribute call (the bare Name call is
    # covered by the import-binding finding at its source)
    assert sum(f.rule == "raw-sharding-constraint"
               for f in findings) == 2
    assert all("redistribute.constrain" in f.message for f in findings)


def test_raw_sharding_constraint_allowed_in_owners():
    tree = ast.parse(
        "import jax\n"
        "v = jax.lax.with_sharding_constraint(v, t.sharding(mesh))\n")
    for rel in (os.path.join("spartan_tpu", "parallel",
                             "redistribute.py"),
                os.path.join("spartan_tpu", "expr", "base.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_sharding_constraints(path, tree) == []
    # unrelated attributes and plain name mentions are NOT flagged
    other = ast.parse("name = 'with_sharding_constraint'\n"
                      "fn = redistribute.constrain\n")
    assert lint_repo.lint_sharding_constraints("/x/y.py", other) == []


def test_catches_pallas_outside_kernels(tmp_path):
    bad = tmp_path / "bad_pallas.py"
    bad.write_text(
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "import jax.experimental.pallas as p2\n"
        "out = pl.pallas_call(kern, out_shape=shape)(x)\n"
        "mod = jax.experimental.pallas\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_pallas_imports(str(bad), tree)
    assert sum(f.rule == "pallas-outside-kernels"
               for f in findings) == 5
    assert all("spartan_tpu/kernels/" in f.message for f in findings)


def test_pallas_allowed_in_kernel_layer():
    tree = ast.parse(
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "out = pl.pallas_call(kern, out_shape=shape)(x)\n")
    for rel in (os.path.join("spartan_tpu", "kernels", "segment.py"),
                os.path.join("spartan_tpu", "kernels", "kmeans.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_pallas_imports(path, tree) == []
    # a Selection.pallas property read is NOT the pallas module
    other = ast.parse("if sel.pallas:\n    pass\n"
                      "name = 'pallas_call'\n")
    assert lint_repo.lint_pallas_imports("/x/y.py", other) == []


def test_catches_persist_seam_violations(tmp_path):
    bad = tmp_path / "bad_persist.py"
    bad.write_text(
        "from jax.experimental import serialize_executable as se\n"
        "from jax.experimental.serialize_executable import "
        "deserialize_and_load\n"
        "import jax.experimental.serialize_executable as se2\n"
        "payload = se.serialize(compiled)\n"
        "d = FLAGS.persist_cache_dir\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_persist_seam(str(bad), tree)
    assert sum(f.rule == "persist-seam" for f in findings) >= 4
    assert all("spartan_tpu/persist" in f.message for f in findings)


def test_persist_seam_allowed_in_persist_layer():
    tree = ast.parse(
        "from jax.experimental import serialize_executable as se\n"
        "payload, it, ot = se.serialize(compiled)\n"
        "c = se.deserialize_and_load(payload, it, ot)\n"
        "d = FLAGS.persist_cache_dir\n")
    for rel in (os.path.join("spartan_tpu", "persist", "store.py"),
                os.path.join("spartan_tpu", "persist", "__init__.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_persist_seam(path, tree) == []
    # ordinary attributes named like the API elsewhere are fine
    other = ast.parse("x = obj.serialize\nname = 'persist_cache_dir'\n")
    assert lint_repo.lint_persist_seam("/x/y.py", other) == []


def test_catches_buffer_mutation_outside_seam(tmp_path):
    bad = tmp_path / "bad_mutation.py"
    bad.write_text(
        "arr._jax = new_buf\n"
        "arr._lineage = None\n"
        "arr._version += 1\n"
        "a._version, b._version = 1, 2\n"
        "del arr._lineage\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_buffer_mutation(str(bad), tree)
    assert sum(f.rule == "buffer-mutation" for f in findings) >= 5
    assert all("DistArray.update()" in f.message for f in findings)
    # reads are fine — only stores detach the lineage log
    ok = ast.parse("v = arr._version\nif arr._lineage is None:\n"
                   "    pass\n")
    assert lint_repo.lint_buffer_mutation("/x/y.py", ok) == []


def test_buffer_mutation_allowed_in_array_and_seam():
    tree = ast.parse("self._jax = out\nself._lineage = lin\n"
                     "child._version = lin.note(region)\n")
    for rel in (os.path.join("spartan_tpu", "array", "distarray.py"),
                os.path.join("spartan_tpu", "expr", "incremental.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_buffer_mutation(path, tree) == []
    # same stores anywhere else are findings
    other = os.path.join(lint_repo.REPO, "spartan_tpu", "serve",
                         "engine.py")
    assert lint_repo.lint_buffer_mutation(other, tree) != []


def test_catches_dynamic_slice_outside_seam(tmp_path):
    bad = tmp_path / "bad_slice.py"
    bad.write_text(
        "import jax.lax as lax\n"
        "from jax.lax import dynamic_slice\n"
        "def f(x, i):\n"
        "    y = lax.dynamic_slice(x, (i, 0), (4, 4))\n"
        "    return lax.dynamic_update_slice(x, y, (i, 0))\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_dynamic_slices(str(bad), tree)
    assert sum(f.rule == "traced-start-slice" for f in findings) == 3
    assert all("full_gather" in f.message for f in findings)
    assert all("docs/INCREMENTAL.md" in f.message for f in findings)
    # the static-bound forms are NOT the gather class and pass
    ok = ast.parse("import jax.lax as lax\n"
                   "a = lax.dynamic_slice_in_dim(x, 0, 4)\n"
                   "b = lax.slice(x, (0,), (4,))\n")
    assert lint_repo.lint_dynamic_slices("/x/y.py", ok) == []


def test_dynamic_slice_allowed_in_incremental_seam():
    tree = ast.parse("import jax.lax as lax\n"
                     "y = lax.dynamic_slice(x, starts, sizes)\n"
                     "z = lax.dynamic_update_slice(d, s, starts)\n")
    seam = os.path.join(lint_repo.REPO, "spartan_tpu", "expr",
                        "incremental.py")
    assert lint_repo.lint_dynamic_slices(seam, tree) == []
    other = os.path.join(lint_repo.REPO, "spartan_tpu", "ops",
                         "stencil.py")
    assert lint_repo.lint_dynamic_slices(other, tree) != []


def test_json_output_schema(capsys):
    import json

    # clean repo: --json prints an empty array, exit code 0
    assert lint_repo.main(["--json"]) == 0
    assert json.loads(capsys.readouterr().out) == []

    # the serialization itself: every finding becomes a flat object
    # with exactly the four keys CI tooling keys on
    f = lint_repo.Finding(
        os.path.join(lint_repo.REPO, "spartan_tpu", "x.py"),
        7, "traced-start-slice", "msg")
    row = {"path": f.path, "line": f.line, "rule": f.rule,
           "message": f.message}
    assert row == {"path": os.path.join("spartan_tpu", "x.py"),
                   "line": 7, "rule": "traced-start-slice",
                   "message": "msg"}


def test_module_entry_point():
    import subprocess
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint_repo", "--json"],
        cwd=lint_repo.REPO, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    assert json.loads(proc.stdout) == []


def test_catches_background_threads_outside_seams(tmp_path):
    bad = tmp_path / "bad_thread.py"
    bad.write_text(
        "import threading\n"
        "from threading import Thread\n"
        "from threading import Timer\n"
        "t = threading.Thread(target=work, daemon=True)\n"
        "w = threading.Timer(5.0, fire)\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_background_threads(str(bad), tree)
    assert sum(f.rule == "background-thread" for f in findings) == 4
    assert all("epoch fence" in f.message for f in findings)
    # synchronization primitives are NOT threads of execution
    ok = ast.parse("import threading\n"
                   "lock = threading.Lock()\n"
                   "ev = threading.Event()\n"
                   "cv = threading.Condition(lock)\n"
                   "tl = threading.local()\n")
    assert lint_repo.lint_background_threads("/x/y.py", ok) == []


def test_background_threads_allowed_in_seams():
    tree = ast.parse("import threading\n"
                     "t = threading.Thread(target=run, daemon=True)\n"
                     "w = threading.Timer(1.0, fire)\n")
    for rel in (os.path.join("spartan_tpu", "serve", "engine.py"),
                os.path.join("spartan_tpu", "resilience", "drill.py"),
                os.path.join("spartan_tpu", "obs", "monitor.py"),
                os.path.join("spartan_tpu", "obs", "numerics.py"),
                os.path.join("spartan_tpu", "persist", "__init__.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_background_threads(path, tree) == []
    # the same construction in any other obs module is a finding
    other = os.path.join(lint_repo.REPO, "spartan_tpu", "obs",
                         "trace.py")
    assert lint_repo.lint_background_threads(other, tree) != []


def test_catches_raw_shard_walks(tmp_path):
    bad = tmp_path / "walk_mod.py"
    bad.write_text(
        "def tile_bytes(jarr):\n"
        "    return [s.data.nbytes for s in jarr.addressable_shards]\n"
        "n = len(x.jax_array.addressable_shards)\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_shard_walks(str(bad), tree)
    assert sum(f.rule == "shard-walk" for f in findings) == 2
    # ... and the sanctioned seam is named in the remedy
    assert all("per_shard_stats" in f.message for f in findings)


def test_shard_walks_allowed_in_owners():
    tree = ast.parse("def f(jarr):\n"
                     "    return list(jarr.addressable_shards)\n")
    for rel in (os.path.join("spartan_tpu", "obs", "skew.py"),
                os.path.join("spartan_tpu", "utils", "checkpoint.py"),
                os.path.join("spartan_tpu", "array", "distarray.py"),
                os.path.join("spartan_tpu", "array", "sparse.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_shard_walks(path, tree) == []
    # the same walk anywhere else in obs (or the expr layer) is a
    # finding: per-tile reads single-source through obs/skew.py
    for rel in (os.path.join("spartan_tpu", "obs", "numerics.py"),
                os.path.join("spartan_tpu", "expr", "base.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_shard_walks(path, tree) != []


def test_catches_checksum_walks(tmp_path):
    bad = tmp_path / "sum_mod.py"
    bad.write_text(
        "from spartan_tpu.resilience import integrity\n"
        "def verify(jarr):\n"
        "    return integrity.shard_checksums(jarr)\n"
        "def chaos(out):\n"
        "    return flip_bit(out, 0, 0, 0)\n")
    tree = ast.parse(bad.read_text(), filename=str(bad))
    findings = lint_repo.lint_checksum_walks(str(bad), tree)
    assert sum(f.rule == "checksum-walk" for f in findings) == 2
    # ... and the sanctioned seam is named in the remedy
    assert all("integrity" in f.message for f in findings)


def test_checksum_walks_allowed_in_integrity_seam():
    tree = ast.parse("def f(jarr):\n"
                     "    return shard_checksums(jarr)\n")
    for rel in (os.path.join("spartan_tpu", "resilience", "integrity.py"),
                os.path.join("spartan_tpu", "resilience", "faults.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_checksum_walks(path, tree) == []
    # checksum comparison anywhere else — even elsewhere in the
    # resilience layer — single-sources through integrity.py
    for rel in (os.path.join("spartan_tpu", "resilience", "engine.py"),
                os.path.join("spartan_tpu", "serve", "engine.py")):
        path = os.path.join(lint_repo.REPO, rel)
        assert lint_repo.lint_checksum_walks(path, tree) != []
