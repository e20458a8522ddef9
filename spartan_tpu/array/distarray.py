"""DistArray: a tile-partitioned distributed N-d array as a sharded jax.Array.

Capability parity with the reference's distributed array layer (SURVEY.md
§2.2: ``[U] spartan/array/distarray.py`` — tile map, ``create``, ``fetch``,
``update``, ``foreach_tile``, ``glom``, broadcast wrapper). Re-designed
TPU-first per BASELINE.json:5: *"DistArray tiling becomes a GSPMD
NamedSharding over a TPU mesh, with each Tile a device shard"*. There is no
tile store, no placement RPC and no per-tile locking: the array IS a
``jax.Array`` whose sharding is described by a :class:`Tiling`; the tile map
of the reference is recoverable as ``self.extents()``. All mutation-flavored
APIs (``update``) are functional — they return a new DistArray (SURVEY.md §7
hard part 5).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from ..obs import trace as trace_mod
from ..parallel import mesh as mesh_mod
from ..utils import profiling as prof
from . import extent as extent_mod
from . import tiling as tiling_mod
from .extent import TileExtent
from .tiling import Tiling

# Reducers for update(): name -> (jnp combine, at[].op name)
REDUCERS = {
    None: "set",
    "set": "set",
    "add": "add",
    "mul": "multiply",
    "max": "max",
    "min": "min",
}

_COMBINE = {
    "add": jnp.add,
    "multiply": jnp.multiply,
    "max": jnp.maximum,
    "min": jnp.minimum,
}

# update() dispatches through ONE jitted program per (op, sharding,
# rank): region starts are traced scalars, so a stream of region
# writes (the incremental engine's mutation seam — sliding windows,
# rotating edge batches) compiles once per data shape instead of once
# per call site/region. The write itself is a mask + clipped-gather
# select rather than a dynamic_update_slice: GSPMD can only lower a
# traced-start DUS on a sharded dim by gathering the whole operand
# (~20x the cost of the write), while iota-mask/where/gather-of-the-
# small-delta all partition cleanly.
_UPDATE_JIT: dict = {}
_UPDATE_JIT_MAX = 512


def _update_callable(op: str, sharding: NamedSharding,
                     delta_sharding: NamedSharding, ndim: int):
    key = (op, sharding, delta_sharding, ndim)
    fn = _UPDATE_JIT.get(key)
    if fn is None:
        from jax import lax

        def _apply(x, d, *starts):
            ixs = [lax.broadcasted_iota(jnp.int32, x.shape, ax)
                   - starts[ax] for ax in range(x.ndim)]
            inb = None
            for ax, ix in enumerate(ixs):
                m = (ix >= 0) & (ix < d.shape[ax])
                inb = m if inb is None else (inb & m)
            dfull = d[tuple(jnp.clip(ix, 0, d.shape[ax] - 1)
                            for ax, ix in enumerate(ixs))]
            val = dfull if op == "set" else _COMBINE[op](x, dfull)
            # second output: the post-write region values for op "set"
            # — the incremental engine's stash (byte-identical to the
            # committed region; combine reducers don't stash, their
            # post-write values only exist inside the full array)
            return jnp.where(inb, val, x), d

        fn = jax.jit(_apply, out_shardings=(sharding, delta_sharding))
        if len(_UPDATE_JIT) >= _UPDATE_JIT_MAX:
            _UPDATE_JIT.clear()
        _UPDATE_JIT[key] = fn
    return fn


def _stash_enabled() -> bool:
    from ..utils.config import FLAGS

    return bool(getattr(FLAGS, "incremental", False))


def _canonical_reducer(reducer: Any) -> str:
    """Accept the reference's np-function reducers as well as names."""
    if reducer is None:
        return "set"
    if isinstance(reducer, str):
        if reducer not in REDUCERS:
            raise ValueError(f"unknown reducer {reducer!r}")
        return reducer
    for name, fn in (("add", np.add), ("mul", np.multiply),
                     ("max", np.maximum), ("min", np.minimum)):
        if reducer is fn:
            return name
    raise ValueError(f"unsupported reducer {reducer!r}; use one of "
                     f"{sorted(k for k in REDUCERS if k)}")


def _caller_site():
    """First stack frame outside spartan_tpu — records WHERE a
    donation was requested, so use-after-donate errors (and the
    plan-time lint, analysis/lints.py) name the donating call."""
    import sys

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.startswith(pkg):
            return (fn, f.f_lineno, f.f_code.co_name)
        f = f.f_back
    return None


_MUTLOG_MAX = 256  # mutation-log cap: overflow collapses to whole-array


class Lineage:
    """Shared mutation history of a family of :class:`DistArray` handles.

    ``update()`` is functional — it returns a NEW DistArray — but the
    returned array shares its parent's ``Lineage`` so the incremental
    engine (expr/incremental.py) can tell *what moved* between the leaf
    a result cache entry recorded and the leaf a later evaluate sees:
    same lineage + a higher version means "this array, with exactly the
    extents logged in between dirty"; anything else is a new identity
    and the engine falls back to a full recompute. The log is bounded:
    past ``_MUTLOG_MAX`` entries it collapses to one whole-array marker
    (``None`` extent), which is the conservative (always-correct)
    over-approximation."""

    __slots__ = ("log", "latest", "stash", "stash_bytes")

    # post-write region values kept per logged entry (the incremental
    # engine serves restricted leaves from these instead of dynamic-
    # slicing the sharded parent, which GSPMD lowers to a gather)
    _STASH_MAX_BYTES = 64 << 20

    def __init__(self) -> None:
        # log: [(version, TileExtent | None)] — None means whole array
        self.log: List[Tuple[int, Optional[TileExtent]]] = []
        self.latest = 0
        self.stash: dict = {}  # version -> jax.Array (region values)
        self.stash_bytes = 0

    def note(self, ext: Optional[TileExtent],
             value: Optional[jax.Array] = None) -> int:
        self.latest += 1
        if len(self.log) >= _MUTLOG_MAX:
            self.log = [(self.latest, None)]
            self.stash.clear()
            self.stash_bytes = 0
        else:
            self.log.append((self.latest, ext))
            if value is not None and ext is not None:
                nb = int(value.size) * value.dtype.itemsize
                if nb <= self._STASH_MAX_BYTES:
                    self.stash[self.latest] = value
                    self.stash_bytes += nb
                    while self.stash_bytes > self._STASH_MAX_BYTES:
                        v = next(iter(self.stash))
                        old = self.stash.pop(v)
                        self.stash_bytes -= (int(old.size)
                                             * old.dtype.itemsize)
        return self.latest

    def stashed_between(self, v0: int, v1: int
                        ) -> Optional[Tuple[TileExtent, jax.Array]]:
        """The post-write values of the delta — available iff EXACTLY
        one write landed in ``v0 < version <= v1`` and its values were
        stashed (stashes of sequential writes don't compose: the later
        region's values may overlap the earlier)."""
        found = None
        for v, ext in self.log:
            if v0 < v <= v1:
                if found is not None:
                    return None
                found = (v, ext)
        if found is None:
            return None
        v, ext = found
        val = self.stash.get(v)
        if ext is None or val is None:
            return None
        return ext, val

    def dirty_between(self, v0: int, v1: int,
                      shape: tuple) -> Optional[TileExtent]:
        """Bounding box of extents logged with ``v0 < version <= v1``;
        ``None`` means the whole array (a full marker, a dropped entry,
        or no box algebra possible)."""
        box: Optional[TileExtent] = None
        seen = 0
        for v, ext in self.log:
            if v0 < v <= v1:
                seen += 1
                if ext is None:
                    return None
                if box is None:
                    box = ext
                else:
                    box = TileExtent(
                        tuple(min(a, b) for a, b in zip(box.ul, ext.ul)),
                        tuple(max(a, b) for a, b in zip(box.lr, ext.lr)),
                        shape)
        if seen == 0 and v1 > v0:
            return None  # versions fell off the bounded log
        return box


class DistArray:
    """A distributed N-d array: ``jax.Array`` + :class:`Tiling` over the
    ambient mesh."""

    __slots__ = ("_jax", "tiling", "mesh", "_donate_next", "_donate_site",
                 "_epoch", "_migration", "_lineage", "_version")

    def __init__(self, jax_array: jax.Array, tiling: Tiling,
                 mesh: Optional[Mesh] = None):
        if tiling.ndim != jax_array.ndim:
            raise ValueError(
                f"tiling rank {tiling.ndim} != array rank {jax_array.ndim}")
        self._jax = jax_array
        self._donate_next = False
        self._donate_site = None
        self._migration = None  # planned cross-mesh migration record
        self._lineage = None  # mutation history (update/assign seam)
        self._version = 0
        self.tiling = tiling
        self.mesh = mesh or mesh_mod.get_mesh()
        # birth epoch: using this array after a rebuild_mesh (its
        # buffers live on the dead mesh) raises StaleMeshError at
        # dispatch instead of handing XLA a dead-device buffer
        self._epoch = mesh_mod._EPOCH

    # -- buffer donation (expr/base.py evaluate(donate=...)) ------------

    @property
    def jax_array(self) -> jax.Array:
        arr = self._jax
        if arr is None:
            site = (f" (donated at {self._donate_site[0]}:"
                    f"{self._donate_site[1]}, in {self._donate_site[2]})"
                    if self._donate_site else "")
            raise RuntimeError(
                "DistArray used after donation: its device buffer was "
                "released to an evaluate(donate=...) / .donate() "
                f"dispatch{site}; rebuild the array (or keep a copy) "
                "instead of reusing the donated handle")
        return arr

    @jax_array.setter
    def jax_array(self, value: jax.Array) -> None:
        self._jax = value

    def donate(self) -> "DistArray":
        """Release this array's buffer to the NEXT ``evaluate()`` that
        consumes it as a leaf: the executable is compiled as a
        ``donate_argnums`` variant so XLA may alias the buffer into the
        outputs (the loop-carry re-feed pattern — old centers/weights
        feed the step that produces their replacement), and this
        DistArray is invalidated after the dispatch so use-after-donate
        raises cleanly instead of reading freed HBM. Returns ``self``
        for call-site chaining: ``evaluate(step(c.donate()))``."""
        self._donate_next = True
        if self._donate_site is None:
            self._donate_site = _caller_site()
        return self

    @property
    def is_donated(self) -> bool:
        return self._jax is None

    def _release_donated(self) -> None:
        """Called by the evaluate() dispatch after a donating run."""
        self._jax = None
        self._donate_next = False

    # -- basic properties ----------------------------------------------

    @property
    def shape(self) -> tuple:
        return tuple(self.jax_array.shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self.jax_array.dtype)

    @property
    def ndim(self) -> int:
        return self.jax_array.ndim

    @property
    def size(self) -> int:
        return int(self.jax_array.size)

    def __repr__(self) -> str:
        if self._jax is None:  # donated handle: no metadata left to read
            return f"DistArray(<donated>, tiling={self.tiling})"
        return (f"DistArray(shape={self.shape}, dtype={self.dtype}, "
                f"tiling={self.tiling})")

    def sharding(self) -> NamedSharding:
        return self.tiling.sharding(self.mesh)

    # -- tile map view (the reference's {TileExtent -> TileId}) ---------

    def extents(self) -> List[TileExtent]:
        return self.tiling.extents(self.shape, self.mesh)

    def tile_shape(self) -> tuple:
        """Shape of the largest shard."""
        exts = self.extents()
        return max((e.shape for e in exts), key=lambda s: np.prod(s or (1,)))

    # -- data access ----------------------------------------------------

    def glom(self) -> np.ndarray:
        """Fetch the whole array to the host (the reference's ``glom``)."""
        return fetch_to_host(self.jax_array)[0]

    def fetch(self, region: Union[TileExtent, tuple, slice, int]
              ) -> np.ndarray:
        """Fetch an arbitrary rectangular region to the host.

        The reference assembled this from per-tile RPCs (SURVEY.md §3.5);
        here XLA slices the sharded array and gathers the result.
        """
        if not isinstance(region, TileExtent):
            region = extent_mod.from_slice(region, self.shape)
        sl = region.to_slice()
        return np.asarray(jax.device_get(self.jax_array[sl]))

    def update(self, region: Union[TileExtent, tuple, slice],
               data: Any, reducer: Any = None) -> "DistArray":
        """Functional region write: a new DistArray whose ``region`` holds
        ``reducer(existing, data)`` (default: overwrite).

        The reference's ``update(extent, data, reducer)`` mutated tiles
        through worker RPCs with reducer-merge (SURVEY.md §2.2); here it is
        a functional scatter-combine, deterministic by construction
        (SURVEY.md §7 hard part 3).

        This is also the mutation seam of the incremental engine
        (docs/INCREMENTAL.md): the returned array shares this array's
        :class:`Lineage` with ``region`` logged as its dirty extent, so
        a warm ``evaluate()`` whose plan-cache key still hits (leaf
        signatures are positional, not identity-based) can recompute
        only what the update touched.
        """
        if not isinstance(region, TileExtent):
            region = extent_mod.from_slice(region, self.shape)
        op = REDUCERS[_canonical_reducer(reducer)]
        data = jnp.asarray(data, dtype=self.dtype)
        if data.shape != region.shape:
            data = jnp.broadcast_to(data, region.shape)
        # the delta output keeps the parent's sharding on axes the
        # region takes whole and replicates cut axes — the same rule as
        # the engine's DynSliceExpr, so a stash-served restricted
        # program has the identical partial-sum structure (bit-equality
        # with the full recompute)
        dt = self.tiling
        for ax, (u, l, s) in enumerate(zip(region.ul, region.lr,
                                           self.shape)):
            if not (u == 0 and l == s):
                dt = dt.with_axis(ax, None)
        fn = _update_callable(op, self.sharding(), dt.sharding(self.mesh),
                              self.ndim)
        starts = [jnp.asarray(u, jnp.int32) for u in region.ul]
        out, delta = fn(self.jax_array, data, *starts)
        res = DistArray(out, self.tiling, self.mesh)
        stash = delta if (op == "set" and _stash_enabled()) else None
        self._record_mutation(res, region, stash)
        return res

    def _record_mutation(self, child: "DistArray",
                         region: Optional[TileExtent],
                         value: Optional[jax.Array] = None) -> None:
        """Thread this array's lineage through a functionally-updated
        child: ``region`` (or whole-array when ``None``) becomes the
        delta between ``self``'s version and ``child``'s, with the
        post-write region ``value`` stashed when available.

        A Lineage log is LINEAR, but ``update()`` is functional and may
        branch: two children minted from the same parent diverge, and
        if both shared one log the incremental engine would read a
        sibling's writes as part of the other child's delta — and miss
        that the child LACKS them — splicing a stale result. So a child
        cut from a handle that is not the lineage tip gets a FRESH
        Lineage (new identity): the engine's same-lineage check fails,
        it performs one honest full recompute, and the new lineage
        serves the branch's own deltas from then on."""
        lin = self._lineage
        if lin is None:
            lin = Lineage()
            lin.latest = self._version
            self._lineage = lin
        elif self._version != lin.latest:
            # branch point: ``self`` is an interior handle
            lin = Lineage()
            lin.latest = self._version
        child._lineage = lin
        child._version = lin.note(region, value)

    # -- resharding -----------------------------------------------------

    def retile(self, new_tiling: Tiling) -> "DistArray":
        """Redistribute to a new tiling. XLA emits the minimal collective
        (all-to-all / all-gather over ICI) — the lowering of the
        reference's shuffle-based redistribution (SURVEY.md §2.6)."""
        if new_tiling == self.tiling:
            return self
        arr = jax.device_put(self.jax_array, new_tiling.sharding(self.mesh))
        return DistArray(arr, new_tiling, self.mesh)

    def replicate(self) -> "DistArray":
        return self.retile(tiling_mod.replicated(self.ndim))

    def rehome(self) -> "DistArray":
        """Migrate this array (IN PLACE) onto the current mesh epoch
        after a ``rebuild_mesh`` — the one sanctioned mutation outside
        donation, because healing must reach every holder of the
        handle (loop closures, caches). Valid only while the buffers
        are still fetchable (replicated arrays, or simulated loss);
        an array whose shards died with the device must be re-created
        from source — elastic recovery says so in its error.

        The migration is PLANNED (``parallel/redistribute.plan_rehome``,
        docs/REDISTRIBUTION.md "cross-mesh-shape transitions"): the
        chosen schedule, modeled wire bytes, route and reason land on
        ``self._migration`` — ``resilience/elastic.rehome`` folds them
        into the ``elastic_*`` metrics and the recovery span, and
        ``st.explain`` names them per migrated leaf. The ``direct``
        route repartitions sharding-to-sharding (``jax.device_put``,
        ICI where the runtime can); anything else — indivisible on the
        survivor grid, tuple-sharded flat_row axes, a failed direct
        transfer — takes the gather (host round-trip) route.

        A donated/invalidated handle is SKIPPED with a labeled reason,
        never crashed on: its buffer is gone by contract, and recovery
        must keep healing the arrays that still have one."""
        if self._jax is None:
            # invalidated by donation: nothing to migrate; record the
            # reason so the recovery span can label the skip
            self._migration = {
                "route": "skipped", "bytes": 0,
                "reason": "buffer invalidated by donation"}
            return self
        if self._epoch == mesh_mod._EPOCH:
            return self
        from ..parallel import redistribute as redist_mod

        mesh = mesh_mod.get_mesh()
        t, dec = redist_mod.plan_rehome(self, mesh)
        mig = {
            "route": dec.route, "bytes": int(dec.bytes),
            "schedule": (dec.schedule.describe()
                         if dec.schedule is not None else None),
            "reason": dec.reason, "shape": self.shape,
            "src_tiling": self.tiling.axes, "dst_tiling": t.axes,
            "from_epoch": self._epoch, "to_epoch": mesh_mod._EPOCH,
        }
        arr = None
        if dec.route == "direct":
            try:
                arr = jax.device_put(self._jax, t.sharding(mesh))
            except Exception as e:  # noqa: BLE001 - a real device loss
                # can fail the direct repartition mid-transfer; the
                # gather route below reads whatever is still fetchable
                mig["route"] = "gather"
                mig["reason"] = (f"{dec.reason}; direct transfer "
                                 f"failed ({type(e).__name__}), host "
                                 "gather fallback")
        if arr is None:
            host = np.asarray(jax.device_get(self._jax))
            arr = jax.device_put(host, t.sharding(mesh))
        self._jax = arr
        self.tiling = t
        self.mesh = mesh
        self._epoch = mesh_mod._EPOCH
        self._migration = mig
        return self

    # -- data health (obs/numerics.py, the numerics sentinel) -----------

    def health(self) -> dict:
        """One-shot device-side health word: NaN/Inf counts, absmax,
        zero fraction (a tiny jitted reduction + scalar fetch)."""
        from ..obs import numerics

        return numerics.array_health(self)

    def tile_health(self) -> list:
        """Per-tile (per device shard) health stats — names the
        poisoned tile, not just the array."""
        from ..obs import numerics

        return numerics.tile_stats(self)

    def watch(self, label: Optional[str] = None):
        """Install a persistent numerics watchpoint on this array
        (``st.watch(arr)``): checked now, after every ``evaluate()``
        dispatch, and via ``.check()`` / ``.update(new_arr)``; its
        health series feeds the metrics registry and the tracer."""
        from ..obs import numerics

        return numerics.watch(self, label)

    # -- per-shard execution (the foreach_tile analogue) ----------------

    def map_shards(self, fn: Callable[[jax.Array], jax.Array]
                   ) -> "DistArray":
        """Apply a shape-preserving jax-traceable fn to every shard
        independently (owner-computes, no communication) — the analogue of
        ``foreach_tile`` (SURVEY.md §2.2) for traceable kernels."""
        from jax import shard_map

        spec = self.tiling.spec()
        mapped = shard_map(fn, mesh=self.mesh, in_specs=(spec,),
                           out_specs=spec)
        out = jax.jit(mapped)(self.jax_array)
        return DistArray(out, self.tiling, self.mesh)


# -- host transfers -----------------------------------------------------


def fetch_to_host(x: Any) -> Tuple[Any, float]:
    """``x`` (an array, or a tuple of arrays) on the host, and the wall
    seconds of its one ``fetch`` phase. Every device->host copy is
    enqueued first, right behind the computation; the wait for the
    computation is the ``fetch_wait`` span inside ``fetch``, and what
    follows it is the rest of the transfers and the conversions."""
    leaves, tree = jax.tree.flatten(x)
    ctx = prof.phase("fetch")
    with ctx as sp:
        sp.set(shape=tree.unflatten([tuple(e.shape) for e in leaves]),
               dtype=tree.unflatten([str(e.dtype) for e in leaves]))
        for e in leaves:
            e.copy_to_host_async()
        with prof.span("fetch_wait"):
            for e in leaves:
                e.block_until_ready()
        out = tree.unflatten([np.asarray(e) for e in leaves])
    return out, ctx.seconds


# -- creation -----------------------------------------------------------


def _resolve_tiling(shape: Sequence[int], tiling: Optional[Tiling],
                    tile_hint: Optional[Sequence[int]],
                    mesh: Optional[Mesh]) -> Tiling:
    if tiling is not None:
        return tiling
    if tile_hint is not None:
        return tiling_mod.from_tile_hint(shape, tile_hint, mesh)
    return tiling_mod.default_tiling(shape, mesh)


def from_numpy(arr: Any, tiling: Optional[Tiling] = None,
               tile_hint: Optional[Sequence[int]] = None,
               mesh: Optional[Mesh] = None) -> DistArray:
    arr = np.asarray(arr)
    mesh = mesh or mesh_mod.get_mesh()
    t = _resolve_tiling(arr.shape, tiling, tile_hint, mesh)
    with upload_span(arr):
        jarr = jax.device_put(arr, t.sharding(mesh))
    return DistArray(jarr, t, mesh)


def upload_span(arr: np.ndarray):
    """The ``upload`` span around the host->device copy of ``arr``
    (nothing at all when tracing is off)."""
    if not trace_mod._TRACE_FLAG._value:
        return contextlib.nullcontext()
    return trace_mod.span("upload", bytes=int(arr.nbytes))


def from_jax(arr: jax.Array, tiling: Optional[Tiling] = None,
             mesh: Optional[Mesh] = None) -> DistArray:
    mesh = mesh or mesh_mod.get_mesh()
    if tiling is None:
        spec = (arr.sharding.spec if isinstance(arr.sharding, NamedSharding)
                else None)
        tiling = (tiling_mod.spec_to_tiling(spec, arr.ndim) if spec is not None
                  else tiling_mod.replicated(arr.ndim))
    return DistArray(arr, tiling, mesh)


def _filled(shape: Sequence[int], dtype: Any, fill: Callable[..., jax.Array],
            tiling: Optional[Tiling], tile_hint: Optional[Sequence[int]],
            mesh: Optional[Mesh]) -> DistArray:
    shape = tuple(int(s) for s in shape)
    mesh = mesh or mesh_mod.get_mesh()
    t = _resolve_tiling(shape, tiling, tile_hint, mesh)
    make = jax.jit(fill, static_argnums=(), out_shardings=t.sharding(mesh))
    return DistArray(make(), t, mesh)


def zeros(shape: Sequence[int], dtype: Any = np.float32,
          tiling: Optional[Tiling] = None,
          tile_hint: Optional[Sequence[int]] = None,
          mesh: Optional[Mesh] = None) -> DistArray:
    return _filled(shape, dtype, lambda: jnp.zeros(shape, dtype),
                   tiling, tile_hint, mesh)


def ones(shape: Sequence[int], dtype: Any = np.float32,
         tiling: Optional[Tiling] = None,
         tile_hint: Optional[Sequence[int]] = None,
         mesh: Optional[Mesh] = None) -> DistArray:
    return _filled(shape, dtype, lambda: jnp.ones(shape, dtype),
                   tiling, tile_hint, mesh)


def full(shape: Sequence[int], fill_value: Any, dtype: Any = None,
         tiling: Optional[Tiling] = None,
         tile_hint: Optional[Sequence[int]] = None,
         mesh: Optional[Mesh] = None) -> DistArray:
    return _filled(shape, dtype, lambda: jnp.full(shape, fill_value, dtype),
                   tiling, tile_hint, mesh)


def arange(*args, dtype: Any = None, tiling: Optional[Tiling] = None,
           tile_hint: Optional[Sequence[int]] = None,
           mesh: Optional[Mesh] = None) -> DistArray:
    probe = np.arange(*args, dtype=dtype)
    return _filled(probe.shape, probe.dtype,
                   lambda: jnp.arange(*args, dtype=dtype),
                   tiling, tile_hint, mesh)


def rand(*shape: int, seed: int = 0, tiling: Optional[Tiling] = None,
         tile_hint: Optional[Sequence[int]] = None,
         mesh: Optional[Mesh] = None) -> DistArray:
    key = jax.random.key(seed)
    return _filled(shape, np.float32,
                   lambda: jax.random.uniform(key, shape, jnp.float32),
                   tiling, tile_hint, mesh)


def randn(*shape: int, seed: int = 0, tiling: Optional[Tiling] = None,
          tile_hint: Optional[Sequence[int]] = None,
          mesh: Optional[Mesh] = None) -> DistArray:
    key = jax.random.key(seed)
    return _filled(shape, np.float32,
                   lambda: jax.random.normal(key, shape, jnp.float32),
                   tiling, tile_hint, mesh)
