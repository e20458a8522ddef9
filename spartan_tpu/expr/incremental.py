"""Delta-aware incremental evaluation (ROADMAP open item 2).

The plan cache (expr/base.py) made "same DAG" skip planning; this layer
makes "same DAG + mostly-same data" skip most of the *compute*. The
mutation seam is ``DistArray.update()`` / the ``assign`` expr route
(array/distarray.py): a functional update returns a new handle that
SHARES its parent's :class:`~..array.distarray.Lineage` with the
written extent logged, so the raw-DAG plan key — leaf signatures are
positional shape/dtype/tiling, not data identity — still hits, and
this module can tell exactly which tiles moved since the result it
cached.

On a warm ``evaluate()`` whose plan is cached (and only there —
``intercept`` is called from the plan-cache hit path, behind one flag
read when ``FLAGS.incremental`` is off):

1. Per-leaf dirty extents come from comparing each leaf against the
   snapshot the result-cache entry recorded (same handle = clean; same
   lineage at a later version = the logged extents; anything else =
   whole-leaf dirty).
2. Dirty boxes propagate bottom-up through the RAW DAG with per-node
   access-pattern rules: map = identity under broadcast, axis-reduce =
   the box with reduced axes collapsed, dot = dirty rows/cols of the
   non-contracted dims (dirt along the contracted dim feeds every
   output it touches), reduce_all / loop / shuffle / anything unknown
   = whole-node (conservative is always correct — over-recompute of a
   deterministic program is bit-equal).
3. If the root's dirty box is a small-enough sub-region
   (``FLAGS.incremental_max_dirty_frac``), the engine rebuilds a
   RESTRICTED sub-DAG computing just that region. Preferred leaf
   form: when every dirty leaf's delta is a single write whose
   post-write values the mutation seam stashed
   (``Lineage.stashed_between``), the restriction uses the EXACT root
   box and the stash becomes a materialized ValExpr leaf — no slicing
   of sharded parents at all (GSPMD can only lower a traced-start
   dynamic-slice on a sharded dim by gathering the sliced operand,
   ~30x the restricted compute), and streaming deltas that repeat
   their batch shape share one plan (leaf sigs are positional).
   Otherwise leaves become dynamic slices with traced (ScalarExpr)
   starts and power-of-two-quantized static sizes, so consecutive
   deltas of similar size still share one plan and one executable.
   Either way the sub-DAG dispatches through the ordinary
   ``evaluate()`` and splices into the cached previous result with
   a dynamic-update-slice under the committed output sharding.
   Bit-equality with a full recompute holds because the restricted
   program runs the same per-element contractions (contracted dims
   are never cut; the stash keeps the parent's sharding on un-cut
   axes, so even the partial-sum structure of sharded contractions
   matches) and the clean region is byte-identical by induction.
4. Anything the rules can't prove clean falls back to the ordinary
   full dispatch with the reason recorded in metrics and
   ``st.explain`` — the honest-fallback contract every prior layer
   uses.

Cached results live in a bounded LRU under ``FLAGS.result_cache_bytes``
(reported to the memory governor's ledger via :func:`cache_bytes` and
the ``incremental_cache_bytes`` gauge). Entries are mesh-epoch fenced:
``evict_stale()`` (called from ``evict_stale_plans()`` after elastic
recovery) reaps entries born under a dead mesh, and an entry whose
result or leaves were donated is dropped on first touch.

Expr-layer imports happen lazily inside functions: expr/base.py binds
this module at import time (``incremental_mod``, swappable by the
null-shim arm of benchmarks/incremental.py) and map/reduce/dot import
base themselves.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from ..array.extent import TileExtent
from ..obs.metrics import REGISTRY
from ..parallel import mesh as mesh_mod
from ..utils import profiling as prof
from ..utils.config import FLAGS

_INC_FLAG = FLAGS.define_bool(
    "incremental", False,
    "Delta-aware evaluation: on a plan-cache hit, recompute only the "
    "tiles dirtied by DistArray.update()/assign since the cached "
    "result, splicing them into the cached output (bit-equal to a "
    "full recompute; falls back to one whenever cleanliness can't be "
    "proven, with the reason in incremental_* metrics + st.explain). "
    "Off by default: the hit path then pays exactly one flag read.")
_CACHE_FLAG = FLAGS.define_int(
    "result_cache_bytes", 256 << 20,
    "Budget for the incremental engine's per-plan result cache "
    "(bounded LRU, host-held references to device buffers). Each "
    "entry is charged for its cached result PLUS the leaf snapshots "
    "it pins; residency (incl. the mutation-seam stash kept alive "
    "by cached snapshots) is visible to the memory governor's ledger "
    "via the incremental_cache_bytes gauge / "
    "expr.incremental.cache_bytes(). A single entry larger than the "
    "budget is never cached.")
_FRAC_FLAG = FLAGS.define_float(
    "incremental_max_dirty_frac", 0.25,
    "Dirty-fraction ceiling for the incremental path: when the root's "
    "propagated dirty box exceeds this fraction of the output, a full "
    "recompute is cheaper than restrict+splice and the engine falls "
    "back (reason 'dirty-frac').")

NOT_HANDLED = object()  # sentinel: caller proceeds with the full path

_MISS = object()


class _Full:
    """Whole-node dirty (the conservative propagation sentinel)."""

    __repr__ = __str__ = lambda self: "FULL"


FULL = _Full()


class Unsupported(Exception):
    """A DAG construct the restriction builder has no rule for — the
    caller degrades to a full recompute with this as the reason."""


# -- the bounded result cache -------------------------------------------


class _Entry:
    """One cached result. IMMUTABLE after construction: a warm splice
    publishes a fresh entry with a compare-and-swap on the entry object
    (see ``intercept``), so concurrent intercepts for the same plan key
    can never observe — or double-account — a half-updated entry.

    ``nbytes`` is the device residency attributable to the entry: the
    cached result PLUS the leaf snapshots ``slots`` keeps alive (each
    is a strong reference pinning that leaf's buffers), so the LRU
    budget and the memory governor see what the entry actually pins.
    Lineage stash bytes are shared across entries and accounted
    separately in :func:`cache_bytes`."""

    __slots__ = ("result", "slots", "epoch", "nbytes")

    def __init__(self, result: Any, slots: Tuple, epoch: int,
                 nbytes: int):
        self.result = result
        self.slots = slots
        self.epoch = epoch
        self.nbytes = nbytes


_lock = threading.RLock()
_cache: "OrderedDict[Tuple, _Entry]" = OrderedDict()
_total_bytes = 0
_tls = threading.local()  # re-entry guard for the inner evaluates


def cache_bytes() -> int:
    """Current result-cache residency — the number the memory
    governor's ledger sees: cached results, the leaf snapshots the
    entries pin, and the mutation-seam stash of every Lineage a cached
    snapshot keeps alive (deduplicated — lineages are shared across
    handles and entries)."""
    with _lock:
        total = _total_bytes
        seen: set = set()
        for e in _cache.values():
            for s in e.slots:
                if s[0] != "a":
                    continue
                lin = s[1]._lineage
                if lin is not None and id(lin) not in seen:
                    seen.add(id(lin))
                    total += lin.stash_bytes
    return total


def cache_entries() -> int:
    return len(_cache)


def clear() -> int:
    """Drop every cached result (tests/benchmarks). Returns entries
    dropped."""
    global _total_bytes
    with _lock:
        n = len(_cache)
        _cache.clear()
        _total_bytes = 0
    _gauge()
    return n


def evict_stale() -> int:
    """Reap entries born under a dead mesh epoch — called from
    ``evict_stale_plans()`` (elastic recovery) next to the plan/compile
    cache purge, so a rebuilt mesh can never be served buffers that
    lived on its predecessor's devices."""
    global _total_bytes
    epoch = mesh_mod._EPOCH
    with _lock:
        dead = [k for k, e in _cache.items() if e.epoch != epoch]
        for k in dead:
            _total_bytes -= _cache.pop(k).nbytes
    if dead:
        prof.count("incremental_evictions", len(dead))
        _gauge()
    return len(dead)


def _drop(key: Tuple) -> None:
    global _total_bytes
    with _lock:
        e = _cache.pop(key, None)
        if e is not None:
            _total_bytes -= e.nbytes
    _gauge()


def _gauge() -> None:
    REGISTRY.gauge(
        "incremental_cache_bytes",
        "incremental result-cache residency, bytes").set(cache_bytes())


def _slots_nbytes(slots: Tuple) -> int:
    """Device bytes pinned by an entry's leaf snapshots (deduplicated:
    the same DistArray may fill several arg slots)."""
    seen: set = set()
    total = 0
    for s in slots:
        if s[0] != "a" or id(s[1]) in seen:
            continue
        seen.add(id(s[1]))
        arr = s[1]
        total += int(arr.size) * arr.dtype.itemsize
    return total


def _snapshot_slots(ordered: List[Any]) -> Optional[Tuple]:
    """Per-arg-slot leaf snapshot: ('s', value) for scalars, ('a',
    array, version) for DistArray-backed leaves; None when a leaf is
    outside the model (nothing to compare against next time)."""
    from .base import ScalarExpr, _leaf_array

    slots = []
    for leaf in ordered:
        if isinstance(leaf, ScalarExpr):
            slots.append(("s", leaf.pyvalue))
            continue
        arr = _leaf_array(leaf)
        if arr is None or arr.is_donated:
            return None
        slots.append(("a", arr, arr._version))
    return tuple(slots)


def note_result(plan: Any, leaves: List[Any], order: Tuple[int, ...],
                result: Any, donated: List[Any], mesh: Any) -> None:
    """Seed/refresh the result cache after an ordinary (full) dispatch.
    Called from both evaluate paths behind the FLAGS.incremental read;
    skips anything outside the model (tuple roots, donated buffers,
    oversized results) — those evaluations simply stay full."""
    global _total_bytes
    if getattr(_tls, "active", False) or donated:
        return
    from ..array.distarray import DistArray

    if not isinstance(result, DistArray):
        return  # tuple roots (multi-output plans) are not modeled
    try:
        ordered = [leaves[i] for i in order]
    except (IndexError, TypeError):
        return
    slots = _snapshot_slots(ordered)
    if slots is None:
        return
    # charge the whole entry: result + the leaf snapshots it pins
    nbytes = (int(result.size) * result.dtype.itemsize
              + _slots_nbytes(slots))
    budget = _CACHE_FLAG._value
    if nbytes > budget:
        return
    with _lock:
        old = _cache.pop(plan.key, None)
        if old is not None:
            _total_bytes -= old.nbytes
        _cache[plan.key] = _Entry(result, slots, mesh_mod._EPOCH, nbytes)
        _total_bytes += nbytes
        evicted = 0
        while _total_bytes > budget and len(_cache) > 1:
            _, e = _cache.popitem(last=False)
            _total_bytes -= e.nbytes
            evicted += 1
    if evicted:
        prof.count("incremental_evictions", evicted)
    _gauge()


# -- per-leaf dirt -------------------------------------------------------


def _leaf_dirt(leaf: Any, slot: Tuple) -> Tuple[Any, Any]:
    """(dirt, stash) for one arg slot: dirt is None (clean) |
    TileExtent | FULL; stash is the lineage's (extent, post-write
    values) pair when the whole delta is a single stashed write."""
    from .base import ScalarExpr, _leaf_array

    if isinstance(leaf, ScalarExpr):
        if slot[0] == "s" and slot[1] == leaf.pyvalue:
            return None, None
        return FULL, None  # a changed scalar feeds everything downstream
    arr = _leaf_array(leaf)
    if arr is None or slot[0] != "a":
        return FULL, None
    rec_arr, rec_ver = slot[1], slot[2]
    if arr is rec_arr and arr._version == rec_ver:
        return None, None
    lin = arr._lineage
    if (lin is None or rec_arr._lineage is not lin
            or arr._version <= rec_ver):
        return FULL, None  # new identity / rewound handle: no delta
    # same lineage at a higher version IS the ancestor chain:
    # _record_mutation gives a branching update (child cut from a
    # non-tip handle) a fresh Lineage, so each log stays linear and
    # dirty_between() is exactly the delta between the two handles
    box = lin.dirty_between(rec_ver, arr._version, arr.shape)
    if box is None:
        return FULL, None
    return (TileExtent(box.ul, box.lr, arr.shape),
            lin.stashed_between(rec_ver, arr._version))


# -- dirty propagation ---------------------------------------------------


def _bbox(a: TileExtent, b: TileExtent, shape: Tuple[int, ...]
          ) -> TileExtent:
    return TileExtent(tuple(min(x, y) for x, y in zip(a.ul, b.ul)),
                      tuple(max(x, y) for x, y in zip(a.lr, b.lr)),
                      shape)


def _covers(box: TileExtent, shape: Tuple[int, ...]) -> bool:
    return (all(u == 0 for u in box.ul)
            and tuple(box.lr) == tuple(shape))


def _union_children(node: Any, children: Tuple, shape: Tuple[int, ...],
                    dirt: Dict, memo: Dict, details: List) -> Any:
    """The broadcast-map rule: a same-shaped dirty child passes its box
    through; a dirty broadcast child (shape differs) dirties the whole
    node."""
    out: Any = None
    for c in children:
        d = _propagate(c, dirt, memo, details)
        if d is None:
            continue
        if d is FULL or tuple(c.shape) != tuple(shape):
            return FULL
        box = TileExtent(d.ul, d.lr, shape)
        out = box if out is None else _bbox(out, box, shape)
    return out


def _propagate(n: Any, dirt: Dict[int, Any], memo: Dict[int, Any],
               details: List[Tuple[Any, Any]]) -> Any:
    """Dirty region of ``n`` in its own coordinates: None | box | FULL."""
    hit = memo.get(n._id, _MISS)
    if hit is not _MISS:
        return hit
    from .base import ScalarExpr, ValExpr
    from .dot import DotExpr
    from .map import MapExpr
    from .reduce import ReduceExpr, _NO_KEEPDIMS

    r: Any
    if n._id in dirt:
        r = dirt[n._id]
    elif (isinstance(n, (ValExpr, ScalarExpr))
          or n._result is not None):
        r = None  # an un-arged leaf / cached sub-DAG: data unchanged
    elif isinstance(n, MapExpr):
        r = _union_children(n, n.inputs, n.shape, dirt, memo, details)
    elif isinstance(n, ReduceExpr):
        pre = _union_children(n, n.inputs, n._pre_shape, dirt, memo,
                              details)
        if pre is None:
            r = None
        elif pre is FULL or n.axis is None:
            r = FULL  # reduce_all: every output element sees the dirt
        elif n.keepdims and n.op not in _NO_KEEPDIMS:
            ul = list(pre.ul)
            lr = list(pre.lr)
            for a in n.axis:
                ul[a], lr[a] = 0, 1
            r = TileExtent(ul, lr, n.shape)
        else:
            box = pre
            for a in sorted(n.axis, reverse=True):
                box = box.drop_axis(a)
            r = TileExtent(box.ul, box.lr, n.shape)
    elif isinstance(n, DotExpr):
        r = _dot_dirt(n, dirt, memo, details)
    else:
        # unknown access pattern (slice, shuffle, loop, transpose,
        # general reduce, shard_map nodes, ...): whole-node dirty —
        # always correct, and the root-level fallback keeps it honest
        r = None
        for c in n.children():
            if _propagate(c, dirt, memo, details) is not None:
                r = FULL
                break
    memo[n._id] = r
    if r is not None:
        details.append((n, r))
    return r


def _dot_dirt(n: Any, dirt: Dict, memo: Dict, details: List) -> Any:
    a, b = n.children()
    da = _propagate(a, dirt, memo, details)
    db = _propagate(b, dirt, memo, details)
    if da is None and db is None:
        return None
    if da is not None and db is not None:
        return FULL
    an, bn = a.ndim, b.ndim
    if da is not None:
        if da is FULL or an != 2:
            return FULL  # dirt on the contracted dim feeds every output
        if bn == 2:  # (n,k)@(k,m): dirty rows -> those output rows
            return TileExtent((da.ul[0], 0), (da.lr[0], n.shape[1]),
                              n.shape)
        return TileExtent((da.ul[0],), (da.lr[0],), n.shape)  # (n,k)@(k,)
    if db is FULL or bn != 2:
        return FULL
    if an == 2:  # (n,k)@(k,m): dirty cols -> those output cols
        return TileExtent((0, db.ul[1]), (n.shape[0], db.lr[1]), n.shape)
    return TileExtent((db.ul[1],), (db.lr[1],), n.shape)  # (k,)@(k,m)


# -- restriction (the dirty sub-plan) ------------------------------------


class DynSliceExpr:
    """``lax.dynamic_slice`` with traced starts and static sizes — the
    restriction leaf. Starts are ScalarExprs (value-free signatures),
    sizes are quantized to powers of two at the root, so successive
    deltas of similar size share one plan and one executable."""


class DynUpdateExpr:
    """``lax.dynamic_update_slice`` splicing the recomputed dirty
    region into the cached previous result, under the destination's
    committed tiling."""


def _build_expr_types():
    """Define the real expr subclasses lazily (base import cycle)."""
    global DynSliceExpr, DynUpdateExpr
    from ..array import tiling as tiling_mod
    from ..array.tiling import Tiling
    from .base import Expr

    class _DynSliceExpr(Expr):
        __doc__ = DynSliceExpr.__doc__

        def __init__(self, input: Expr, starts: Tuple[Expr, ...],
                     sizes: Tuple[int, ...]):
            self.input = input
            self.starts = tuple(starts)
            self.sizes = tuple(int(s) for s in sizes)
            super().__init__(self.sizes, input.dtype)

        def children(self) -> Tuple[Expr, ...]:
            return (self.input,) + self.starts

        def replace_children(self, new_children: Tuple[Expr, ...]):
            return _DynSliceExpr(new_children[0],
                                 tuple(new_children[1:]), self.sizes)

        def _lower(self, env: Dict[int, Any]) -> Any:
            import jax.numpy as jnp
            from jax import lax

            x = self.input.lower(env)
            starts = [jnp.asarray(s.lower(env), jnp.int32)
                      for s in self.starts]
            return lax.dynamic_slice(x, starts, self.sizes)

        def _sig(self, ctx) -> Tuple:
            return (("dynslice", self.sizes)
                    + tuple(ctx.of(c) for c in self.children()))

        def _default_tiling(self) -> Tiling:
            # keep the input's sharding on axes taken whole; cut axes
            # lose alignment with the shard grid (SliceExpr's rule)
            t = self.input.out_tiling()
            for d, sz in enumerate(self.sizes):
                if sz != self.input.shape[d]:
                    t = t.with_axis(d, None)
            return t

    class _DynUpdateExpr(Expr):
        __doc__ = DynUpdateExpr.__doc__

        def __init__(self, dst: Expr, src: Expr,
                     starts: Tuple[Expr, ...]):
            self.dst = dst
            self.src = src
            self.starts = tuple(starts)
            super().__init__(dst.shape, dst.dtype)

        def children(self) -> Tuple[Expr, ...]:
            return (self.dst, self.src) + self.starts

        def replace_children(self, new_children: Tuple[Expr, ...]):
            return _DynUpdateExpr(new_children[0], new_children[1],
                                  tuple(new_children[2:]))

        def _lower(self, env: Dict[int, Any]) -> Any:
            import jax.numpy as jnp
            from jax import lax

            dst = self.dst.lower(env)
            src = jnp.asarray(self.src.lower(env), dst.dtype)
            starts = [jnp.asarray(s.lower(env), jnp.int32)
                      for s in self.starts]
            return lax.dynamic_update_slice(dst, src, starts)

        def _sig(self, ctx) -> Tuple:
            return ("dynupdate",) + tuple(
                ctx.of(c) for c in self.children())

        def _default_tiling(self) -> Tiling:
            return self.dst.out_tiling()  # the committed sharding

    DynSliceExpr = _DynSliceExpr
    DynUpdateExpr = _DynUpdateExpr


_types_built = False


def _types() -> None:
    global _types_built
    if not _types_built:
        _build_expr_types()
        _types_built = True


def _quantize(box: TileExtent, shape: Tuple[int, ...]) -> TileExtent:
    """Round the root's dirty box up to power-of-two sizes (clamped to
    the dim), sliding the start so the box stays covered and in
    bounds: distinct deltas collapse onto ~log2(dim) compiled shapes
    per axis instead of one per delta."""
    ul, lr = [], []
    for u, l, d in zip(box.ul, box.lr, shape):
        size = max(1, l - u)
        q = 1
        while q < size:
            q <<= 1
        q = min(q, d)
        start = min(u, d - q)
        ul.append(start)
        lr.append(start + q)
    return TileExtent(ul, lr, shape)


def _restrict(n: Any, box: TileExtent, memo: Dict,
              stashes: Optional[Dict[int, Tuple]] = None) -> Any:
    """An expr computing ``n[box]`` — same contractions, restricted
    output region. Raises :class:`Unsupported` for nodes without a
    restriction rule. ``stashes`` maps leaf ids to (extent, values)
    pairs from the mutation seam: a leaf whose needed box equals its
    stashed extent is served as a materialized value instead of a
    traced-start dynamic slice of the sharded parent (which GSPMD can
    only lower to a gather of the sliced dim)."""
    key = (n._id, box.ul, box.lr)
    hit = memo.get(key)
    if hit is not None:
        return hit
    from .base import ScalarExpr, ValExpr
    from .dot import DotExpr
    from .map import MapExpr
    from .reduce import ReduceExpr

    if _covers(box, n.shape):
        out = n
    elif (isinstance(n, (ValExpr, ScalarExpr))
          or n._result is not None):
        sv = stashes.get(n._id) if stashes else None
        if (sv is not None and tuple(sv[0].ul) == tuple(box.ul)
                and tuple(sv[0].lr) == tuple(box.lr)):
            from ..array import distarray as da_mod

            out = ValExpr(da_mod.from_jax(sv[1]))
        else:
            out = _dyn_slice(n, box)
    elif isinstance(n, MapExpr):
        out = MapExpr(
            tuple(_restrict_bcast(c, box, n.shape, memo, stashes)
                  for c in n.inputs), n.op)
    elif isinstance(n, ReduceExpr):
        if n.axis is None:
            raise Unsupported("restrict:reduce_all")
        ps = n._pre_shape
        if n.keepdims and n.op not in ("argmax", "argmin"):
            ul = list(box.ul)
            lr = list(box.lr)
            for a in n.axis:
                ul[a], lr[a] = 0, ps[a]
        else:
            ul, lr = [], []
            kept = [d for d in range(len(ps)) if d not in n.axis]
            pos = {d: i for i, d in enumerate(kept)}
            for d in range(len(ps)):
                if d in pos:
                    ul.append(box.ul[pos[d]])
                    lr.append(box.lr[pos[d]])
                else:
                    ul.append(0)
                    lr.append(ps[d])
        pre_box = TileExtent(ul, lr, ps)
        out = ReduceExpr(
            None, n.op, n.axis, n.keepdims, n.req_dtype,
            _inputs=tuple(_restrict_bcast(c, pre_box, ps, memo, stashes)
                          for c in n.inputs),
            _pre=n.pre)
    elif isinstance(n, DotExpr):
        a, b = n.children()
        if _split_mismatch(a, b):
            raise Unsupported("restrict:dot-contraction-split")
        if a.ndim == 2 and b.ndim == 2:
            abox = TileExtent((box.ul[0], 0), (box.lr[0], a.shape[1]),
                              a.shape)
            bbox = TileExtent((0, box.ul[1]), (b.shape[0], box.lr[1]),
                              b.shape)
        elif a.ndim == 2 and b.ndim == 1:
            abox = TileExtent((box.ul[0], 0), (box.lr[0], a.shape[1]),
                              a.shape)
            bbox = TileExtent((0,), (b.shape[0],), b.shape)
        elif a.ndim == 1 and b.ndim == 2:
            abox = TileExtent((0,), (a.shape[0],), a.shape)
            bbox = TileExtent((0, box.ul[0]), (b.shape[0], box.lr[0]),
                              b.shape)
        else:
            raise Unsupported("restrict:dot-rank")
        out = DotExpr(_restrict(a, abox, memo, stashes),
                      _restrict(b, bbox, memo, stashes), n.precision)
    else:
        raise Unsupported(f"restrict:{type(n).__name__}")
    memo[key] = out
    return out


def _split_mismatch(a: Any, b: Any) -> bool:
    """Do the operands of ``a @ b`` split the contracted dim over
    different mesh axes? The partitioner then picks the full plan's
    per-shard partial sums from the operand shapes, and a restricted
    dot may pick another split, which rounds differently."""
    from ..parallel import mesh as mesh_mod

    mesh = mesh_mod.get_mesh()

    def split(axis):
        names = axis if isinstance(axis, tuple) else (axis,)
        return tuple(nm for nm in names
                     if nm is not None and int(mesh.shape.get(nm, 1)) > 1)

    return (split(a.out_tiling().axes[-1])
            != split(b.out_tiling().axes[0]))


def _restrict_bcast(c: Any, box: TileExtent,
                    target_shape: Tuple[int, ...], memo: Dict,
                    stashes: Optional[Dict[int, Tuple]] = None) -> Any:
    """Restrict a broadcast-aligned child: slice axes that match the
    target, keep broadcast (size-1 / missing) axes whole."""
    cs = tuple(c.shape)
    off = len(target_shape) - len(cs)
    if off < 0:
        raise Unsupported("restrict:broadcast-rank")
    ul, lr = [], []
    for i, d in enumerate(cs):
        td = i + off
        if d == target_shape[td]:
            ul.append(box.ul[td])
            lr.append(box.lr[td])
        elif d == 1:
            ul.append(0)
            lr.append(1)
        else:
            raise Unsupported("restrict:broadcast-shape")
    return _restrict(c, TileExtent(ul, lr, cs), memo, stashes)


def _dyn_slice(n: Any, box: TileExtent) -> Any:
    from .base import ScalarExpr

    _types()
    starts = tuple(ScalarExpr(int(u)) for u in box.ul)
    return DynSliceExpr(n, starts, box.shape)


# -- tile accounting / reporting ----------------------------------------


def _tile_counts(n: Any, r: Any, mesh: Any) -> Tuple[int, int]:
    """(total tiles, dirty tiles) of node ``n`` under its committed
    tiling — the per-node dirty/clean view st.explain shows."""
    try:
        tiles = n.out_tiling().tiles_per_dim(mesh)
    except Exception:  # noqa: BLE001 - accounting is advisory
        tiles = tuple(1 for _ in n.shape)
    total = 1
    for t in tiles:
        total *= max(1, t)
    if r is FULL:
        return total, total
    dirty = 1
    for u, l, d, t in zip(r.ul, r.lr, n.shape, tiles):
        t = max(1, t)
        ts = -(-d // t)  # ceil tile size
        lo = u // ts
        hi = -(-l // ts)
        dirty *= max(1, hi - lo)
    return total, min(total, dirty)


def _report(plan: Any, **fields: Any) -> None:
    if plan is not None and plan.report is not None:
        inc = {"cache_bytes": cache_bytes(), "entries": len(_cache)}
        inc.update(fields)
        plan.report["incremental"] = inc


def degrade_to_full(plan: Any, reason: str) -> Any:
    prof.count("incremental_fallbacks")
    _report(plan, mode="full", fallback=reason)
    from ..obs import flight as flight_mod

    flight_mod.note(0, "incremental", mode="full", reason=reason)
    return NOT_HANDLED


# -- the intercept (plan-cache hit path) ---------------------------------


def intercept(expr: Any, plan: Any, leaves: List[Any],
              order: Tuple[int, ...], donated: List[Any],
              mesh: Any) -> Any:
    """Try to serve a warm evaluate from the result cache + a dirty
    sub-plan. Returns the result, or NOT_HANDLED to let the ordinary
    full dispatch run (which then refreshes the cache via
    ``note_result``)."""
    global _total_bytes
    if getattr(_tls, "active", False):
        return NOT_HANDLED  # inner restricted/splice evaluate
    if donated:
        return degrade_to_full(plan, "donation")
    with _lock:
        entry = _cache.get(plan.key)
        if entry is not None:
            _cache.move_to_end(plan.key)
    if entry is None:
        return NOT_HANDLED  # cold: seeded by note_result after dispatch
    if entry.epoch != mesh_mod._EPOCH:
        _drop(plan.key)
        return NOT_HANDLED
    if entry.result.is_donated:
        _drop(plan.key)
        return degrade_to_full(plan, "result-donated")
    try:
        ordered = [leaves[i] for i in order]
    except (IndexError, TypeError):
        return degrade_to_full(plan, "leaf-mismatch")
    if len(ordered) != len(entry.slots):
        _drop(plan.key)
        return degrade_to_full(plan, "leaf-mismatch")
    from .base import _leaf_array

    for leaf in ordered:
        arr = _leaf_array(leaf)
        if arr is not None and arr._donate_next:
            # a .donate()-marked leaf: the caller is owed a buffer
            # release that only the real dispatch performs — serving
            # from the cache would silently skip the donation
            return degrade_to_full(plan, "donation")

    with prof.phase("incremental"):
        try:
            dirt: Dict[int, Any] = {}
            stashes: Dict[int, Tuple] = {}
            for leaf, slot in zip(ordered, entry.slots):
                d, sv = _leaf_dirt(leaf, slot)
                if d is not None:
                    dirt[leaf._id] = d
                    if sv is not None:
                        stashes[leaf._id] = sv
            if not dirt:
                # every leaf byte-identical to the cached evaluation:
                # the cached result IS the answer — zero dispatches
                prof.count("incremental_hits")
                _report(plan, mode="cache-hit", fallback=None)
                return entry.result

            details: List[Tuple[Any, Any]] = []
            root_dirt = _propagate(expr, dirt, {}, details)
            if root_dirt is None:
                prof.count("incremental_hits")
                _report(plan, mode="cache-hit", fallback=None)
                return entry.result
            if root_dirt is FULL:
                return degrade_to_full(plan, "dirty-full")
            frac = root_dirt.size / max(1, expr.size)
            if frac > _FRAC_FLAG._value:
                return degrade_to_full(plan, f"dirty-frac:{frac:.3f}")
            use_box = _quantize(root_dirt, expr.shape)
        except Exception as e:  # noqa: BLE001 - honest-fallback: dirt
            # computation/propagation errors degrade exactly like
            # dispatch errors instead of failing the whole evaluate()
            return degrade_to_full(plan, f"error:{type(e).__name__}")
        try:
            _tls.active = True
            sub_expr = None
            # exact-box pass: when every dirty leaf's delta is a single
            # stashed write, restrict to the UN-quantized root box so
            # each leaf's needed box lines up with its stashed extent
            # and the sub-plan takes the materialized delta as a leaf —
            # no traced-start slice of a sharded parent, no gather.
            # Plan sharing survives because streaming deltas repeat
            # their batch shape (positional leaf sigs).
            if stashes and all(
                    d is not FULL and lid in stashes
                    and tuple(stashes[lid][0].ul) == tuple(d.ul)
                    and tuple(stashes[lid][0].lr) == tuple(d.lr)
                    for lid, d in dirt.items()):
                try:
                    sub_expr = _restrict(expr, root_dirt, {}, stashes)
                    use_box = root_dirt
                except Unsupported:
                    sub_expr = None
            if sub_expr is None:
                use_box = _quantize(root_dirt, expr.shape)
                sub_expr = _restrict(expr, use_box, {})
            from .base import ScalarExpr, ValExpr, evaluate

            sub = evaluate(sub_expr)
            _types()
            starts = tuple(ScalarExpr(int(u)) for u in use_box.ul)
            combined = evaluate(
                DynUpdateExpr(ValExpr(entry.result), ValExpr(sub),
                              starts))
        except Unsupported as e:
            return degrade_to_full(plan, str(e))
        except Exception as e:  # noqa: BLE001 - the honest-fallback
            # contract: ANY failure mid-incremental-dispatch (chaos
            # faults included) degrades to the ordinary full path
            return degrade_to_full(plan, f"error:{type(e).__name__}")
        finally:
            _tls.active = False

        slots = _snapshot_slots(ordered)
        if slots is not None:
            nbytes = (int(combined.size) * combined.dtype.itemsize
                      + _slots_nbytes(slots))
            budget = _CACHE_FLAG._value
            fresh = _Entry(combined, slots, entry.epoch, nbytes)
            evicted = 0
            with _lock:
                # CAS on the entry object: publish only if the slot
                # still holds the entry this splice was derived from. A
                # racing intercept that loses the race keeps (and
                # returns) its own correct result but doesn't publish,
                # so the cache never mixes two splices' deltas and
                # _total_bytes swaps exactly one entry's accounting.
                if nbytes <= budget and _cache.get(plan.key) is entry:
                    _cache[plan.key] = fresh
                    _cache.move_to_end(plan.key)
                    _total_bytes += fresh.nbytes - entry.nbytes
                    while _total_bytes > budget and len(_cache) > 1:
                        _, e = _cache.popitem(last=False)
                        _total_bytes -= e.nbytes
                        evicted += 1
            if evicted:
                prof.count("incremental_evictions", evicted)
        root_total, root_dirty = _tile_counts(expr, use_box, mesh)
        prof.count("incremental_hits")
        prof.count("incremental_recomputed_tiles", root_dirty)
        _report(plan, mode="incremental", fallback=None,
                dirty_frac=round(frac, 6),
                dirty_box=[list(use_box.ul), list(use_box.lr)],
                nodes=[{"node": f"{type(n).__name__}#{n._id}",
                        "tiles": _tile_counts(n, r, mesh)[0],
                        "dirty_tiles": _tile_counts(n, r, mesh)[1]}
                       for n, r in details[-8:]])
        from ..obs import flight as flight_mod

        flight_mod.note(0, "incremental", mode="incremental",
                        dirty_frac=round(frac, 6),
                        recomputed_tiles=root_dirty)
        _gauge()
        return combined
