"""Device mesh management.

Replaces the reference's cluster bring-up entirely (SURVEY.md §3.1: master
spawn + worker registration + BlobCtx install collapses to mesh
construction). A single ambient mesh plays the role the ambient ``BlobCtx``
played: every DistArray is sharded over it.

Mesh axes:
  * ``"x"`` — the primary tiling axis (rows / batch). Data-parallel axis.
  * ``"y"`` — the secondary tiling axis (cols / model). Tensor-parallel axis.

A 2-D mesh is built by default whenever the device count is composite, so
row (``P('x', None)``), col (``P(None, 'y')``) and block (``P('x', 'y')``)
tilings are all expressible — the reference's tiling vocabulary
(SURVEY.md §2.6). On one device the mesh is 1×1 and every spec degrades to
replicated, so code is mesh-size agnostic (SURVEY.md §7 hard part 6).
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.config import FLAGS

AXIS_ROW = "x"
AXIS_COL = "y"

_state = threading.local()

# -- mesh epoch (elastic recovery) ---------------------------------------
#
# A monotonic process-wide generation counter, bumped by every
# ``rebuild_mesh`` (device/host loss shrinks the mesh). Everything that
# binds to a mesh — DistArrays at construction, plan/compile-cache keys
# at signing time (expr/base._mesh_key) — records the epoch it was born
# under, so an artifact from a dead mesh can never dispatch: stale
# plans simply miss the cache, and stale DistArrays raise
# :class:`StaleMeshError` at arg-gather time instead of handing XLA a
# buffer on a device that no longer exists. Reads are unlocked (one
# module-attribute load on the hot path); writes hold ``_epoch_lock``.

_EPOCH = 0
_epoch_lock = threading.Lock()
_global_mesh: Optional[Mesh] = None
_excluded_ids: Tuple[int, ...] = ()

# epoch -> {axis: size} of the mesh that generation ran on. Recorded
# by rebuild_mesh (both the dying and the rebuilt shape), so the
# cross-mesh migration planner (parallel/redistribute.plan_transition)
# and the recovery spans can name the source grid of an artifact whose
# mesh object is gone — e.g. a loop carry restored from a snapshot
# written two epochs ago.
_shape_history: dict = {}


def mesh_epoch() -> int:
    """The current mesh generation (bumped by ``rebuild_mesh``)."""
    return _EPOCH


def mesh_shape_at(epoch: int) -> Optional[dict]:
    """The {axis: size} grid of mesh generation ``epoch``, when known
    (rebuild_mesh records both sides of every transition)."""
    return _shape_history.get(int(epoch))


class StaleMeshError(RuntimeError):
    """A mesh-bound artifact (DistArray, plan) from a previous mesh
    epoch was used after ``rebuild_mesh``: its device buffers live on
    a mesh that no longer exists. Carries the offending arrays on
    ``.arrays`` so elastic recovery (``resilience/elastic.rehome``)
    can migrate the ones that are still fetchable."""

    def __init__(self, msg: str, arrays: Sequence = ()):
        super().__init__(msg)
        self.arrays = list(arrays)


def _factor_2d(n: int) -> Tuple[int, int]:
    """Split n devices into the most-square (rows, cols) grid, favoring
    more rows (the batch axis carries most parallelism in the workloads)."""
    best = (n, 1)
    for c in range(1, int(math.isqrt(n)) + 1):
        if n % c == 0:
            best = (n // c, c)
    return best


def build_mesh(devices: Optional[Sequence[jax.Device]] = None,
               shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """Build an (x, y) mesh over ``devices`` (default: all)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if FLAGS.default_mesh_1d and FLAGS.default_mesh_1d > 0:
        n = min(n, FLAGS.default_mesh_1d)
        devices = devices[:n]
    if shape is None:
        shape = _factor_2d(n)
    if shape[0] * shape[1] != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, (AXIS_ROW, AXIS_COL))


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh
    _state.epoch = _EPOCH


def get_mesh() -> Mesh:
    """The ambient mesh, epoch-fenced: a thread-local pin (``set_mesh``
    / ``use_mesh``) from a previous epoch is discarded — after a
    ``rebuild_mesh`` every thread sees the rebuilt mesh, including
    threads parked inside a ``use_mesh`` of the dead one."""
    mesh = getattr(_state, "mesh", None)
    if mesh is not None and getattr(_state, "epoch", 0) == _EPOCH:
        return mesh
    global _global_mesh
    mesh = _global_mesh
    if mesh is None:
        with _epoch_lock:
            if _global_mesh is None:
                _global_mesh = _build_surviving()
            mesh = _global_mesh
    _state.mesh = mesh
    _state.epoch = _EPOCH
    return mesh


class use_mesh:
    """Context manager pinning the ambient mesh (tests use a CPU mesh).

    The pin is epoch-scoped: if ``rebuild_mesh`` runs inside the
    context, ``get_mesh`` stops honoring the (now-dead) pinned mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._prev: Optional[Mesh] = None
        self._prev_epoch: int = 0

    def __enter__(self) -> Mesh:
        self._prev = getattr(_state, "mesh", None)
        self._prev_epoch = getattr(_state, "epoch", _EPOCH)
        _state.mesh = self.mesh
        _state.epoch = _EPOCH
        return self.mesh

    def __exit__(self, *exc) -> None:
        _state.mesh = self._prev
        _state.epoch = self._prev_epoch


def _build_surviving(shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """Build a mesh over every device NOT excluded by a prior
    ``rebuild_mesh`` (the current survivor set)."""
    devices = [d for d in jax.devices() if d.id not in _excluded_ids]
    if not devices:
        raise RuntimeError("rebuild_mesh excluded every device")
    return build_mesh(devices, shape=shape)


def rebuild_mesh(exclude_devices: Sequence = (),
                 shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """Shrink (or reshape) the mesh after persistent device/host loss
    and bump the mesh epoch — the terminal rung of the resilience
    ladder (docs/RESILIENCE.md, elastic recovery).

    ``exclude_devices`` are devices (or device ids) to REMOVE from the
    survivor set, cumulative with previous rebuilds. The epoch bump
    invalidates every mesh-bound artifact: plan/compile-cache keys
    carry the epoch (stale plans miss), DistArrays record their birth
    epoch (cross-epoch use raises :class:`StaleMeshError`), and
    ``get_mesh``'s thread-local pins are fenced. The caller
    (``resilience/elastic``) is responsible for draining dispatches
    first and evicting the dead epoch's cache entries after."""
    global _EPOCH, _global_mesh, _excluded_ids
    with _epoch_lock:
        if _global_mesh is not None:
            _shape_history.setdefault(
                _EPOCH, {k: int(v) for k, v in _global_mesh.shape.items()})
        excluded = set(_excluded_ids)
        for d in exclude_devices:
            excluded.add(d if isinstance(d, int) else d.id)
        _excluded_ids = tuple(sorted(excluded))
        _EPOCH += 1
        _global_mesh = _build_surviving(shape)
        _shape_history[_EPOCH] = {k: int(v)
                                  for k, v in _global_mesh.shape.items()}
        _state.mesh = _global_mesh
        _state.epoch = _EPOCH
        from ..utils.log import log_warn

        log_warn("mesh epoch %d: rebuilt over %d surviving device(s)"
                 "%s", _EPOCH, _global_mesh.devices.size,
                 f" (excluded ids {_excluded_ids})" if _excluded_ids
                 else "")
        return _global_mesh


def reset_epoch_for_tests() -> None:
    """Restore the full-device, epoch-0 world (test isolation only:
    production epochs are monotonic by design). Plans are keyed by
    epoch, so the cached ones go too: a later test's epoch 1 may hold
    other survivors than this one's."""
    global _EPOCH, _global_mesh, _excluded_ids
    from ..expr import base as expr_base

    with _epoch_lock:
        _EPOCH = 0
        _global_mesh = None
        _excluded_ids = ()
        _shape_history.clear()
        _state.mesh = None
        _state.epoch = 0
    expr_base.clear_compile_cache()


def mesh_axis_sizes(mesh: Optional[Mesh] = None) -> Tuple[int, int]:
    mesh = mesh or get_mesh()
    return (mesh.shape[AXIS_ROW], mesh.shape[AXIS_COL])


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), P())


def named_sharding(spec: P, mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), spec)


def device_count(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return int(np.prod(list(mesh.shape.values())))


def rotated_mesh(mesh: Optional[Mesh] = None, k: int = 1
                 ) -> Optional[Mesh]:
    """A mesh with the SAME shape and axis names but the device
    assignment rotated by ``k`` positions — every logical coordinate
    maps to a different physical chip. The integrity sentinel
    (resilience/integrity.py) re-executes sampled plans on a rotated
    assignment so a per-shard checksum disagreement separates "this
    chip computes wrong bits" from "this value is wrong wherever it is
    computed". Returns None for a single-device mesh (no rotation
    exists). Never installed or cached: callers build one per check
    and drop it (the epoch/staleness machinery only governs the one
    global mesh)."""
    mesh = mesh or get_mesh()
    devs = list(mesh.devices.flat)
    n = len(devs)
    if n < 2:
        return None
    k = k % n
    if k == 0:
        k = 1
    rot = devs[k:] + devs[:k]
    return Mesh(np.array(rot).reshape(mesh.devices.shape),
                mesh.axis_names)


_dist_initialized = False
_dist_lock = threading.Lock()

# "already initialized" phrasings across jax versions: the re-entrant
# fast path treats them as success, not failure
_ALREADY_INIT = ("already initialized", "already been initialized",
                 "initialize should be called once")


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           max_attempts: int = 3,
                           backoff_s: float = 0.5) -> bool:
    """Multi-host bring-up: ``jax.distributed`` plays the role the
    reference's master played (registration/barrier over DCN —
    SURVEY.md §2.7). No-op (returns False) when single-host: args absent
    and no cluster environment detected.

    Re-entrant: a second call (e.g. from elastic recovery after a host
    loss, or ``st.initialize`` called twice) returns True without
    re-dialing the coordinator. Transient connect failures
    (UNAVAILABLE / DEADLINE_EXCEEDED / refused connections — a
    coordinator restarting after the same host loss that triggered the
    reconnect) retry up to ``max_attempts`` times with doubling
    ``backoff_s``; anything else fails once, loudly."""
    import jax

    from ..utils.log import log_warn

    global _dist_initialized
    want = (coordinator_address is not None
            or bool(os.environ.get("COORDINATOR_ADDRESS")))
    if not want:
        # Auto-detection ONLY on an explicit coordinator address: a
        # bare SLURM_JOB_ID must not trigger it — a single-process run
        # inside a multi-task allocation would start the coordinator
        # and BLOCK waiting for peers that never register. SLURM/pod
        # users launched on every task call this with explicit args or
        # set COORDINATOR_ADDRESS.
        return False
    with _dist_lock:
        if _dist_initialized:
            return True
        delay = backoff_s
        for attempt in range(max(1, max_attempts)):
            try:
                if coordinator_address is not None:
                    jax.distributed.initialize(coordinator_address,
                                               num_processes, process_id)
                else:
                    jax.distributed.initialize()
                _dist_initialized = True
                return True
            except Exception as e:  # pragma: no cover - env-dependent
                text = str(e).lower()
                if any(m in text for m in _ALREADY_INIT):
                    _dist_initialized = True
                    return True
                transient = any(m in text for m in (
                    "unavailable", "deadline", "connection refused",
                    "connection reset", "failed to connect", "timed out"))
                if transient and attempt + 1 < max(1, max_attempts):
                    log_warn("jax.distributed connect attempt %d/%d "
                             "failed (%s); retrying in %.2fs",
                             attempt + 1, max_attempts, str(e)[:120],
                             delay)
                    time.sleep(delay)
                    delay *= 2
                    continue
                log_warn("jax.distributed initialization failed: %s", e)
                return False
    return False


def status() -> dict:
    """Cluster status snapshot (the observability analogue of the
    reference's worker-status heartbeats — SURVEY.md §5)."""
    import jax

    mesh = get_mesh()
    devs = jax.devices()
    # memory_stats aggregated across ALL local devices — the reading
    # from device 0 alone hid the hottest chip's high-water on
    # multi-chip hosts. Per key: max (the chip that OOMs first) + sum.
    mem: dict = {}
    try:
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            for key, v in stats.items():
                try:
                    v = float(v)
                except (TypeError, ValueError):
                    continue
                cur = mem.get(key)
                if cur is None:
                    mem[key] = {"max": v, "sum": v}
                else:
                    cur["max"] = max(cur["max"], v)
                    cur["sum"] += v
    except Exception:
        pass
    return {
        "platform": devs[0].platform if devs else "none",
        "num_devices": len(devs),
        "num_local_devices": len(jax.local_devices()),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "memory_stats": mem,
    }
