"""spartan_tpu: a TPU-native distributed N-d array framework.

A brand-new JAX/XLA implementation of the capability surface of
``sdutheone/spartan`` (see SURVEY.md): a lazy NumPy-like expression DAG
(map / map2 / reduce / shuffle / outer / scan) over tile-partitioned
distributed arrays — where a DistArray is a GSPMD-sharded ``jax.Array``,
each tile is a device shard, expression forcing compiles the whole DAG
into one XLA program, and shuffle/reduce lower to all-to-all/all-reduce
collectives over ICI (BASELINE.json:5).

Typical use::

    import spartan_tpu as st
    x = st.rand(4096, 4096)
    y = ((x + x) * 3.0).sum()
    print(y.glom())
"""

from .array import distarray as _da
from .array.distarray import DistArray
from .array.extent import TileExtent
from .array.tiling import Tiling
from .expr import *  # noqa: F401,F403
from .expr import __all__ as _expr_all
from .array.sparse import SparseDistArray
from .array.masked import MaskedDistArray
from .parallel import collectives
from .parallel import mesh as _mesh
from .parallel.mesh import (StaleMeshError, build_mesh, get_mesh,
                            initialize_distributed, mesh_epoch,
                            rebuild_mesh, set_mesh, use_mesh)
from .ops.stencil import avgpool, maxpool, stencil
from .analysis import PlanAudit, audit_plan, check, lint
from . import obs
from .obs import (AuditReport, CalibrationProfile, DeviceProfile,
                  ExplainReport, SkewReport, Watchpoint, audit, explain,
                  fit_profile, fleet_status, load_profile, loop_health,
                  metrics, save_profile, status, trace_clear,
                  trace_events, trace_export, unwatch, watch)
from . import resilience
from .resilience import (ChaosPlan, FatalMeshError, IntegrityError,
                         chaos, chaos_clear)
from . import serve
from .serve import (Backpressure, DeadlineExceeded, EvalFuture,
                    MeshReconfiguring, ServeEngine, evaluate_async)
from . import persist
from .utils import checkpoint, profiling
from .utils.config import FLAGS

__version__ = "0.1.0"

__all__ = (["DistArray", "SparseDistArray", "MaskedDistArray", "TileExtent",
            "Tiling", "FLAGS",
            "build_mesh", "get_mesh", "set_mesh", "use_mesh", "initialize",
            "initialize_distributed", "shutdown", "status",
            "fleet_status", "collectives",
            "rebuild_mesh", "mesh_epoch", "StaleMeshError",
            "checkpoint", "profiling", "stencil", "maxpool", "avgpool",
            "check", "lint", "audit_plan", "PlanAudit",
            "obs", "persist", "explain", "ExplainReport", "metrics", "trace_export",
            "trace_events", "trace_clear",
            "ledger", "flightrec", "CalibrationProfile", "fit_profile",
            "save_profile", "load_profile",
            "profile", "profile_export", "DeviceProfile",
            "skew", "SkewReport",
            "audit", "AuditReport", "watch", "unwatch", "Watchpoint",
            "loop_health",
            "resilience", "chaos", "chaos_clear", "ChaosPlan",
            "FatalMeshError", "IntegrityError",
            "serve", "ServeEngine", "EvalFuture", "evaluate_async",
            "Backpressure", "DeadlineExceeded", "MeshReconfiguring"]
           + list(_expr_all))


def initialize(argv=None):
    """Parity with the reference's ``spartan.initialize()`` (SURVEY.md
    §3.1): parse flags, place JAX's persistent compilation cache
    (:func:`_compile_cache_dir`), bring up the multi-host control plane
    when a cluster environment is present (``jax.distributed`` plays
    the reference master's registration/barrier role — SURVEY.md §2.7;
    no-op standalone), and install the ambient mesh. The whole
    master/worker bring-up otherwise collapses to mesh construction."""
    rest = FLAGS.parse_args(argv)
    import jax

    cache_dir = _compile_cache_dir()
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    resilience.faults.install_from_flags()  # FLAGS.fault_inject chaos
    _mesh.initialize_distributed()  # no-op unless COORDINATOR/SLURM env
    _mesh.get_mesh()
    return rest


def _compile_cache_dir():
    """Where compiled XLA programs persist across processes: None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX read it already — the
    caller placed the cache), else ``<checkout>/.jax_cache``. A fixed
    path: the cache key includes it, so a moving directory never hits."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(checkout, ".jax_cache")


def ledger(validate=False):
    """The device-time cost ledger (docs/OBSERVABILITY.md): per-plan
    predicted-vs-measured ratios for the tiling-DP cost, peak-HBM and
    service-time models, per-model aggregates + drift counts, and the
    active calibration state. ``validate=True`` first runs the XLA
    memory validation for live plans missing actuals (one AOT compile
    each)."""
    return obs.ledger_snapshot(validate=validate)


def flightrec(limit=None):
    """The per-request flight recorder (docs/OBSERVABILITY.md): recent
    lifecycle events (newest ``limit`` when given), reconstructed
    per-request timelines, and per-tenant latency-decomposition
    histograms for the serve path."""
    return obs.flightrec(limit=limit)


def profile(expr, tier=None, reps=None):
    """Device-time attribution (docs/OBSERVABILITY.md): run one
    profiled evaluation of ``expr`` and return per-expr-node device
    seconds keyed by each node's structural-signature digest, with
    measured time next to the tiling DP's modeled cost. ``tier``:
    'auto' (default) tries the XPlane/trace-parse capture and falls
    back to the portable segmented replay; 'xplane' / 'replay' force
    one. Continuous sampling in production:
    ``FLAGS.profile_sample_every = N``."""
    return obs.profile.profile(expr, tier=tier, reps=reps)


def skew(expr, tier=None, reps=None):
    """Shard-level skew report (docs/OBSERVABILITY.md): per-device
    time skew with a collective wait decomposition (time-at-barrier
    attributed to the plan's psum/all_gather edges via the plan
    auditor), per-tile data skew over the expression's leaves, and an
    advisory redistribution-priced re-tiling suggestion when the
    imbalance ratio exceeds ``FLAGS.skew_warn_ratio`` (report-only).
    ``tier``/``reps`` forward to the underlying profiler run.
    Continuous sampling rides ``FLAGS.profile_sample_every``."""
    return obs.skew.skew(expr, tier=tier, reps=reps)


def profile_export(path=None, profile=None):
    """One Perfetto-loadable Chrome trace merging the host span ring
    (``st.trace_export``'s content) with a device timeline — the given
    :class:`DeviceProfile`, else the most recent one (st.profile or a
    sampled dispatch). See docs/OBSERVABILITY.md."""
    return obs.profile.export_merged(path, profile=profile)


def shutdown():
    _mesh.set_mesh(None)
