"""NumPy-surface builtins over the expr DAG.

Parity with ``[U] spartan/expr/builtins.py`` (SURVEY.md §2.3: ``zeros ones
rand randn arange astype ravel sum mean max min argmin argmax diag diagonal
norm concatenate bincount tril triu scan``) — mostly thin wrappers over
map/reduce/creation exprs, exactly as in the reference.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..array import distarray as da
from .base import Expr, ScalarExpr, ValExpr, as_expr
from .map import MapExpr, build_unop, map as map_expr
from .ndarray import CreateExpr, RandomExpr, ndarray
from .reduce import (all, any, argmax, argmin, max, mean, min, prod,
                     reduce, sum)

__all__ = [
    "zeros", "ones", "full", "arange", "eye", "identity", "rand", "randn",
    "randint", "astype", "absolute", "exp", "log", "sqrt", "square", "abs",
    "sign", "sin", "cos", "tan", "tanh", "maximum", "minimum", "where",
    "clip", "sum", "mean", "max", "min", "prod", "all", "any", "argmax",
    "argmin", "reduce", "ndarray", "norm", "diag", "diagonal", "tril",
    "triu", "bincount", "concatenate", "ravel", "sqrt", "dot", "power",
    "equal", "from_numpy", "count_nonzero", "count_zero", "size", "scan",
    "sort", "argsort", "median", "percentile", "quantile", "histogram",
    "unique_counts", "unique", "topk",
    "isnan", "isinf",
    "isfinite", "logical_not", "var", "std", "ptp", "cumsum", "cumprod",
    "take", "linspace", "log1p", "expm1", "log2", "log10", "floor", "ceil",
    "rint", "negative", "reciprocal", "add", "subtract", "multiply",
    "divide", "true_divide", "mod", "not_equal", "greater", "greater_equal",
    "less", "less_equal", "logical_and", "logical_or", "logical_xor",
    "outer_product", "einsum", "tensordot", "matmul", "trace", "inner",
]


# -- creation -----------------------------------------------------------


def zeros(shape, dtype=np.float32, tile_hint=None, tiling=None) -> Expr:
    return CreateExpr(shape, dtype, "zeros", (), tiling, tile_hint)


def ones(shape, dtype=np.float32, tile_hint=None, tiling=None) -> Expr:
    return CreateExpr(shape, dtype, "ones", (), tiling, tile_hint)


def full(shape, fill_value, dtype=np.float32, tile_hint=None,
         tiling=None) -> Expr:
    return CreateExpr(shape, dtype, "full", (fill_value,), tiling, tile_hint)


def arange(*args, dtype=None, tile_hint=None, tiling=None) -> Expr:
    probe = np.arange(*args, dtype=dtype)
    if probe.dtype == np.float64:
        probe = probe.astype(np.float32)
    if probe.dtype == np.int64:
        probe = probe.astype(np.int32)
    return CreateExpr(probe.shape, probe.dtype, "arange", tuple(args),
                      tiling, tile_hint)


def eye(n, m=None, k=0, dtype=np.float32, tile_hint=None) -> Expr:
    m = n if m is None else m
    return CreateExpr((n, m), dtype, "eye", (n, m, k), None, tile_hint)


def identity(n, dtype=np.float32) -> Expr:
    return eye(n, dtype=dtype)


def rand(*shape, seed=None, tile_hint=None, tiling=None) -> Expr:
    return RandomExpr(shape, "uniform", seed, np.float32, tiling, tile_hint)


def randn(*shape, seed=None, tile_hint=None, tiling=None) -> Expr:
    return RandomExpr(shape, "normal", seed, np.float32, tiling, tile_hint)


def randint(*shape, low=0, high=10, seed=None, tile_hint=None) -> Expr:
    e = RandomExpr(shape, "randint", seed, np.int32, None, tile_hint)
    e.params_range = (low, high)
    return e


def from_numpy(arr, tiling=None, tile_hint=None) -> Expr:
    return ValExpr(da.from_numpy(arr, tiling=tiling, tile_hint=tile_hint))


# -- elementwise wrappers ----------------------------------------------


def _unary(name):
    def fn(x) -> Expr:
        return build_unop(name, x)

    fn.__name__ = name
    return fn


absolute = _unary("absolute")
abs = absolute
exp = _unary("exp")
log = _unary("log")
sqrt = _unary("sqrt")
square = _unary("square")
sign = _unary("sign")
sin = _unary("sin")
cos = _unary("cos")
tan = _unary("tan")
tanh = _unary("tanh")
isnan = _unary("isnan")
isinf = _unary("isinf")
isfinite = _unary("isfinite")
logical_not = _unary("logical_not")
log1p = _unary("log1p")
expm1 = _unary("expm1")
log2 = _unary("log2")
log10 = _unary("log10")
floor = _unary("floor")
ceil = _unary("ceil")
rint = _unary("rint")
negative = _unary("negative")
reciprocal = _unary("reciprocal")


def _binary(name):
    def fn(a, b) -> Expr:
        from .map import build_binop

        return build_binop(name, a, b)

    fn.__name__ = name
    return fn


add = _binary("add")
subtract = _binary("subtract")
multiply = _binary("multiply")
true_divide = _binary("true_divide")
divide = true_divide
mod = _binary("mod")
not_equal = _binary("not_equal")
greater = _binary("greater")
greater_equal = _binary("greater_equal")
less = _binary("less")
less_equal = _binary("less_equal")
logical_and = _binary("logical_and")
logical_or = _binary("logical_or")
logical_xor = _binary("logical_xor")


def maximum(a, b) -> Expr:
    from .map import build_binop

    return build_binop("maximum", a, b)


def minimum(a, b) -> Expr:
    from .map import build_binop

    return build_binop("minimum", a, b)


def power(a, b) -> Expr:
    from .map import build_binop

    return build_binop("power", a, b)


def equal(a, b) -> Expr:
    from .map import build_binop

    return build_binop("equal", a, b)


def where(cond, a, b) -> Expr:
    from .local import LocalInput, LocalUfunc

    inputs = (as_expr(cond), as_expr(a), as_expr(b))
    return MapExpr(inputs, LocalUfunc(
        "where", (LocalInput(0), LocalInput(1), LocalInput(2))))


def clip(x, lo, hi) -> Expr:
    from .local import LocalInput, LocalUfunc

    inputs = (as_expr(x), as_expr(lo), as_expr(hi))
    return MapExpr(inputs, LocalUfunc(
        "clip", (LocalInput(0), LocalInput(1), LocalInput(2))))


def astype(x, dtype) -> Expr:
    dtype = np.dtype(dtype)
    return map_expr(lambda v: v.astype(dtype), as_expr(x))


# -- shape-flavoured / misc builtins -----------------------------------


def ravel(x) -> Expr:
    from .reshape import ravel as _ravel

    return _ravel(x)


def concatenate(arrays, axis=0) -> Expr:
    from .reshape import concatenate as _concat

    return _concat(arrays, axis)


def dot(a, b, precision=None) -> Expr:
    from .dot import dot as _dot

    return _dot(a, b, precision=precision)


def norm(x, ord=2) -> Expr:
    x = as_expr(x)
    if ord == 2:
        return sqrt(sum(x * x))
    if ord == 1:
        return sum(absolute(x))
    raise ValueError(f"unsupported norm order {ord}")


def diag(x) -> Expr:
    """1-D -> diagonal matrix; 2-D -> its diagonal (NumPy semantics)."""
    x = as_expr(x)
    if x.ndim == 1:
        return map_expr(lambda v: jnp.diag(v), x)
    if x.ndim == 2:
        return diagonal(x)
    raise ValueError("diag requires 1-D or 2-D input")


def diagonal(x) -> Expr:
    x = as_expr(x)
    if x.ndim != 2:
        raise ValueError("diagonal requires a 2-D input")
    from .map import MapExpr
    from .local import LocalCall, LocalInput

    return MapExpr((x,), LocalCall(jnp.diagonal, (LocalInput(0),)))


def tril(x, k=0) -> Expr:
    return map_expr(lambda v: jnp.tril(v, k), as_expr(x))


def triu(x, k=0) -> Expr:
    return map_expr(lambda v: jnp.triu(v, k), as_expr(x))


class BincountExpr(Expr):
    """Counts of ints in ``[0, length)`` — the histogram family's
    reduction, lowered as the traced ``jnp.bincount`` (XLA scatter-add,
    GSPMD-partitioned). Negative ids clip to bucket 0 and ids >= length
    are dropped (jnp.bincount semantics)."""

    def __init__(self, x: Expr, length: int):
        self.x = x
        self.length = int(length)
        super().__init__((self.length,), np.int32)

    def children(self):
        return (self.x,)

    def replace_children(self, new_children) -> "BincountExpr":
        return BincountExpr(new_children[0], self.length)

    def _lower(self, env) -> Any:
        v = self.x.lower(env)
        return jnp.bincount(v.ravel(), length=self.length)

    def _sig(self, ctx):
        return ("bincount", self.length, ctx.of(self.x))

    def _default_tiling(self):
        from ..array import tiling as tiling_mod

        return tiling_mod.replicated(1)


def bincount(x, minlength: Optional[int] = None,
             length: Optional[int] = None) -> Expr:
    """Counts of nonnegative ints. A static ``length``/``minlength`` keeps
    the output shape static for XLA (dynamic shapes are TPU-hostile); it
    defaults to ``x.max()+1`` computed eagerly (one small collective)."""
    x = as_expr(x)
    n = length or minlength
    if n is None:
        n = int(max(x).glom()) + 1
    return BincountExpr(x, n)


def count_nonzero(x) -> Expr:
    x = as_expr(x)
    return sum(astype(x != 0, np.int32))


def count_zero(x) -> Expr:
    x = as_expr(x)
    return sum(astype(x == 0, np.int32))


def size(x) -> int:
    return as_expr(x).size


class SampleSortExpr(Expr):
    """Distributed sample sort (SURVEY.md §2.3 misc ops: the
    reference's sampling-based distributed sort). Lowers to the
    static-shape shard_map program in ``ops/sort.py``: local sort,
    gathered splitter samples, all_to_all bucket exchange, local
    merge, all_to_all rebalance to even row shards. Any length (a
    validity channel carries ragged tails); N-d arrays sort along
    ``axis`` with the 1-D kernel vmapped over the other axes — the
    sharded sort axis is never gathered. With ``indices=True`` it is
    the distributed argsort (source indices ride the pipeline as a
    sort payload)."""

    def __init__(self, x: Expr, indices: bool = False, axis: int = -1):
        self.x = x
        self.indices = indices
        self.axis = _checked_axis(axis, x.ndim)
        super().__init__(x.shape, np.int32 if indices else x.dtype)

    def children(self):
        return (self.x,)

    def replace_children(self, new_children) -> "SampleSortExpr":
        return SampleSortExpr(new_children[0], self.indices, self.axis)

    def _moved_in_tiling(self):
        """The operand's tiling with the sort axis moved last — what
        the lowering's moveaxis produces; lets the kernel keep batch
        shardings and follow the sort axis's existing placement."""
        t = self.x.out_tiling()
        axes = list(t.axes)
        axes.append(axes.pop(self.axis))
        from ..array.tiling import Tiling

        return Tiling(axes)

    def _lower(self, env) -> Any:
        from ..ops import sort as sort_ops

        v = self.x.lower(env)
        if self.x.ndim <= 1:
            fn = (sort_ops.sample_argsort if self.indices
                  else sort_ops.sample_sort)
            return fn(v)
        last = self.x.ndim - 1
        if self.axis != last:
            v = jnp.moveaxis(v, self.axis, last)
        out = sort_ops.sample_sort_axis(
            v, with_indices=self.indices,
            in_tiling=self._moved_in_tiling())
        if self.axis != last:
            out = jnp.moveaxis(out, last, self.axis)
        return out

    def _sig(self, ctx):
        return ("sample_sort", self.indices, self.axis, ctx.of(self.x))

    def _default_tiling(self):
        from ..array import tiling as tiling_mod
        from ..ops import sort as sort_ops

        if self.ndim <= 1:
            return tiling_mod.row(1)
        # batch axes keep the operand's shardings; the sort axis comes
        # back sharded where the kernel ran it. Axis selection and
        # batch clearing are the SAME helpers _run uses, so this
        # declared tiling cannot diverge from the kernel's out_specs
        # (ADVICE round 5, finding 1).
        moved = self._moved_in_tiling()
        name = sort_ops.collective_axis(moved)
        axes = list(sort_ops.batch_axes(moved, name, self.ndim))
        axes.insert(self.axis, name)
        return tiling_mod.Tiling(axes)


def _checked_axis(axis: int, ndim: int) -> int:
    nd = ndim if ndim else 1
    if not -nd <= axis < nd:
        raise ValueError(
            f"sort axis {axis} out of range for ndim {ndim}")
    return axis % nd


def _distributed_sortable(x: Expr, axis: int) -> bool:
    """True when the distributed sample sort beats the traced
    ``jnp.sort``: a multi-device row axis, and (for N-d operands) the
    sort axis actually sharded — an unsharded sort axis sorts locally
    under GSPMD with zero communication, which no collective pipeline
    can beat."""
    from ..array import tiling as tiling_mod
    from ..parallel import mesh as mesh_mod

    p = int(mesh_mod.get_mesh().shape.get(tiling_mod.AXIS_ROW, 1))
    if p <= 1 or x.ndim == 0 or x.size == 0:
        return False
    if x.ndim == 1:
        return True
    return x.out_tiling().axes[axis % x.ndim] is not None


def sort(x, axis: int = -1) -> Expr:
    """Sorted copy along an axis.

    Arrays sharded along the sort axis on a multi-device mesh run the
    distributed sample sort — splitter sampling + all_to_all bucket
    exchange under shard_map (ops/sort.py), the reference's algorithm
    in collective form; any length (ragged tails ride a validity
    channel) and any rank (the kernel vmaps over non-sort axes).
    Everything else is a single traced ``jnp.sort`` over the sharded
    operand (XLA bitonic sort; right when the sort axis is local).
    Masked operands sort valid-first, masked-last (numpy.ma).

    Note: when the sorted length does not divide the mesh the RESULT
    materializes replicated (the DistArray layer's shard grid needs
    even splits) — the sort itself still runs distributed; only the
    final layout is replicated."""
    from ..array.masked import MaskedDistArray, masked_sort

    if isinstance(x, MaskedDistArray):
        return masked_sort(x, axis=axis)
    x = as_expr(x)
    ax = _checked_axis(axis, x.ndim)
    if _distributed_sortable(x, ax):
        return SampleSortExpr(x, axis=ax)
    return map_expr(lambda v: jnp.sort(v, axis=ax), x)


def argsort(x, axis: int = -1) -> Expr:
    """Indices that sort ``x``; arrays sharded along the sort axis run
    the distributed sample argsort (see :func:`sort`). Masked operands
    order valid elements first (numpy.ma semantics)."""
    from ..array.masked import MaskedDistArray, masked_argsort

    if isinstance(x, MaskedDistArray):
        return masked_argsort(x, axis=axis)
    x = as_expr(x)
    ax = _checked_axis(axis, x.ndim)
    if _distributed_sortable(x, ax):
        return SampleSortExpr(x, indices=True, axis=ax)
    return map_expr(lambda v: jnp.argsort(v, axis=ax), x)


def _nan_poison(x: Expr, rdt, axis=None) -> Any:
    """0 when ``x`` is NaN-free, NaN otherwise (per slice of ``axis``
    when given) — added to distributed order statistics so
    median/percentile propagate NaN exactly like the traced jnp
    fallbacks (the sample sort orders NaN to one end, which would
    otherwise silently hide it).

    Derived from NaN-ness alone: counting ``isnan`` per element keeps
    inf inputs and f32 sum overflow (both of which poisoned the old
    ``sum(x) * 0.0`` formulation with spurious NaN) out of the result."""
    if not np.issubdtype(np.dtype(rdt), np.floating) or \
            not np.issubdtype(np.dtype(x.dtype), np.floating):
        return 0.0  # int inputs can't hold NaN: skip the scan entirely
    cnt = sum(map_expr(lambda v: jnp.isnan(v).astype(jnp.float32), x),
              axis=axis)
    return map_expr(
        lambda c: jnp.where(c > 0, jnp.nan, 0.0).astype(rdt), cnt)


def _axis_order_stat_path(x: Expr, axis) -> Any:
    """The normalized axis when an order statistic (median /
    percentile along ``axis``) should ride the distributed sort — the
    operand is sharded along that axis, so the traced fallback would
    all-gather it. None otherwise. 1-D arrays sort on axis 0 for
    ``axis`` in (None, 0, -1); N-d arrays need an integer axis."""
    if x.ndim == 0 or x.size == 0:
        return None
    if x.ndim == 1:
        if axis not in (None, 0, -1):
            return None
        return 0 if _distributed_sortable(x, 0) else None
    if axis is None or not isinstance(axis, (int, np.integer)):
        return None
    ax = _checked_axis(int(axis), x.ndim)
    return ax if _distributed_sortable(x, ax) else None


def _order_stat_interp(x: Expr, ax: int, positions, rdt):
    """Linearly-interpolated order statistics of ``x`` along ``ax``
    at fractional ``positions``, read off ONE distributed sort
    (SampleSortExpr); each result drops ``ax``. The shared kernel of
    median and scalar-q percentile, 1-D and N-d alike. Operands are
    promoted to ``rdt`` BEFORE combining: int middles could overflow."""
    n = x.shape[ax]
    s = SampleSortExpr(x, axis=ax)
    pre = (slice(None),) * ax
    outs = []
    for pos in positions:
        lo = int(np.floor(pos))
        hi = lo + 1 if lo + 1 <= n - 1 else n - 1
        fr = float(pos - lo)
        outs.append((1.0 - fr) * astype(s[pre + (lo,)], rdt)
                    + fr * astype(s[pre + (hi,)], rdt))
    return outs


def median(x, axis=None) -> Expr:
    """Median; arrays sharded along the reduction axis (1-D arrays,
    and any N-d axis) route through the distributed sample sort (two
    order statistics of the sorted result) instead of gathering the
    axis. Matches the traced path's dtype promotion and NaN
    propagation. Masked operands take the median of the UNMASKED
    elements (numpy.ma; fully-masked slices come out NaN)."""
    from ..array.masked import MaskedDistArray, masked_median

    if isinstance(x, MaskedDistArray):
        return masked_median(x, axis=axis)
    x = as_expr(x)
    ax = _axis_order_stat_path(x, axis)
    if ax is not None:
        rdt = jnp.result_type(x.dtype, jnp.float32)
        n = x.shape[ax]
        (out,) = _order_stat_interp(x, ax, [(n - 1) / 2.0], rdt)
        return out + _nan_poison(x, rdt, axis=ax)
    return map_expr(lambda v: jnp.median(v, axis=axis), x)


def percentile(x, q, axis=None) -> Expr:
    """Percentile (linear interpolation), scalar or 1-D vector ``q``;
    the 1-D multi-device case rides the distributed sample sort like
    :func:`median` — ONE sort feeds every quantile (vector ``q``
    gathers the needed order statistics from the sorted result)."""
    x = as_expr(x)
    scalar_q = np.ndim(q) == 0
    qa = np.atleast_1d(np.asarray(q, dtype=np.float64))
    if qa.ndim != 1:
        raise NotImplementedError(
            "spartan_tpu.percentile supports scalar or 1-D q only; "
            f"got q with shape {qa.shape}")
    if qa.size == 0 or np.any(qa < 0.0) or np.any(qa > 100.0) or \
            np.any(np.isnan(qa)):
        raise ValueError(f"percentile q={q} outside [0, 100]")
    ax = _axis_order_stat_path(x, axis)
    if ax is not None and scalar_q:
        rdt = jnp.result_type(x.dtype, jnp.float32)
        n = x.shape[ax]
        (out,) = _order_stat_interp(
            x, ax, [float(qa[0]) / 100.0 * (n - 1)], rdt)
        return out + _nan_poison(x, rdt, axis=ax)
    if ax is not None and x.ndim == 1:
        # vector q: gather every quantile's order statistics from ONE
        # distributed sort
        n = x.shape[0]
        rdt = jnp.result_type(x.dtype, jnp.float32)
        pos = qa / 100.0 * (n - 1)
        lo = np.floor(pos).astype(np.int64)
        # NB: this module shadows builtin min() with the reduce op
        hi = np.minimum(lo + 1, n - 1)
        frac = pos - lo
        s = SampleSortExpr(x)
        w = as_expr(frac.astype(np.float64))
        out = (1.0 - w) * astype(take(s, lo), rdt) \
            + w * astype(take(s, hi), rdt)
        return astype(out, rdt) + _nan_poison(x, rdt)
    # hashable closure capture: the compile cache keys kernels by
    # captured values, and tuples (unlike ndarrays) compare by content
    qq = float(qa[0]) if scalar_q else tuple(qa.tolist())
    return map_expr(
        lambda v: jnp.percentile(v, jnp.asarray(qq), axis=axis), x)


class TopKExpr(Expr):
    """INDICES of the distributed top-k (ops/sort.py
    distributed_topk): per-shard ``lax.top_k`` candidates + one k*p
    all_gather + final top-k — only candidates cross the wire. Values
    are a k-element gather on top (builtins.topk), so one kernel
    serves both outputs."""

    def __init__(self, x: Expr, k: int, largest: bool):
        self.x = x
        self.k = int(k)
        self.largest = bool(largest)
        super().__init__((self.k,), np.dtype(np.int32))

    def children(self):
        return (self.x,)

    def replace_children(self, new_children) -> "TopKExpr":
        return TopKExpr(new_children[0], self.k, self.largest)

    def _lower(self, env) -> Any:
        from ..ops.sort import distributed_topk

        return distributed_topk(self.x.lower(env), self.k,
                                largest=self.largest)[1]

    def _sig(self, ctx):
        return ("topk", self.k, self.largest, ctx.of(self.x))

    def _default_tiling(self):
        from ..array import tiling as tiling_mod

        return tiling_mod.replicated(1)


def topk(x, k: int, largest: bool = True):
    """(values, indices) of the k largest (or smallest) elements of a
    1-D array, values best-first — ``lax.top_k`` at mesh scale. On a
    multi-device mesh with ``k <= ceil(n/p)`` only ``k*p`` candidates
    cross the wire (per-shard top-k + one gather); bigger k rides the
    distributed sample argsort. Values are gathered through the
    indices, so each variant runs ONE distributed kernel. Ties resolve
    to any valid winner set (like ``lax.top_k``)."""
    from ..parallel import mesh as mesh_mod

    x = as_expr(x)
    if x.ndim != 1:
        raise ValueError(f"topk needs a 1-D operand, got {x.shape}")
    k = int(k)
    n = x.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"topk needs 1 <= k <= {n}, got {k}")
    from ..array import tiling as tiling_mod
    p = int(mesh_mod.get_mesh().shape.get(tiling_mod.AXIS_ROW, 1))
    if p > 1 and k > -(-n // p):
        # k exceeds the per-shard candidate budget: distributed
        # argsort, then slice the winning end (best-first)
        si = SampleSortExpr(x, indices=True)
        if largest:
            idx = map_expr(lambda v: v[::-1], si[n - k:])
        else:
            idx = si[:k]
    else:
        idx = TopKExpr(x, k, largest)
    vals = map_expr(lambda v, i: v[i], x, idx)
    return vals, idx


def quantile(x, q, axis=None) -> Expr:
    """``np.quantile``: :func:`percentile` with q in [0, 1]."""
    qa = np.asarray(q, dtype=np.float64)
    if qa.size and (np.any(qa < 0.0) or np.any(qa > 1.0)):
        raise ValueError(f"quantile q={q} outside [0, 1]")
    return percentile(x, qa * 100.0 if np.ndim(q) else float(qa) * 100.0,
                      axis=axis)


def _hist_edges(lo, hi, bins: int):
    """The bin-edge formula BOTH the bucketing kernels and the
    returned-edges exprs evaluate (in f32, on device) — one source, so
    counts can never disagree with the edges the caller receives."""
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    # jnp.linspace pins BOTH endpoints exactly (it concatenates stop),
    # so a value equal to the range max never rounds out of the
    # closed last bin
    return jnp.linspace(lo, hi, bins + 1)


def _hist_expand(lo, hi):
    """np.histogram's degenerate-range rule: all-equal data (or an
    explicit lo == hi range) spans value +/- 0.5."""
    return (jnp.where(hi > lo, lo, lo - 0.5),
            jnp.where(hi > lo, hi, hi + 0.5))


def _hist_guard_range(lo, hi):
    """np.histogram raises on a non-finite autodetected range; the
    detection happens on device, so the check rides the numerics
    sentinel: compiled in (and raised by ``st.audit``) only under
    ``FLAGS.audit_numerics``, free otherwise (ADVICE r5 #2). Module
    level on purpose — a per-call closure cell would break the
    kernels' ``fn_key`` compile-cache stability."""
    from ..obs import numerics as _numerics

    _numerics.guard_finite(
        "histogram.range", jnp.stack([lo, hi]),
        "autodetected range of [%g, %g] is not finite")


def histogram(x, bins: int = 10, range=None):
    """``np.histogram`` with STATIC bin count: (counts, edges).

    Distributed as bucketing (a searchsorted map over the sharded
    operand) + the bincount reduction; ``range`` defaults to the
    operand's (min, max) — computed in the same program when not
    given. With an explicit ``range`` values outside it are dropped
    (np.histogram semantics); a degenerate range or constant data
    expands value +/- 0.5 like numpy. Edges are f32 (no x64 on
    device) and are computed by the same formula the bucketing kernel
    uses, so exact-edge values land where the returned edges say.

    np.histogram parity on non-finite data (ADVICE round 5, finding
    2): with ``range=None`` the (min, max) autodetection runs ON
    DEVICE inside the same traced program — there is no host round
    trip at which a non-finite range could raise eagerly. The
    autodetected range therefore carries a numerics-sentinel
    finiteness guard (``obs/numerics.guard_finite``): evaluating
    through ``st.audit`` raises ``ValueError("autodetected range of
    [nan, nan] is not finite")`` exactly like ``np.histogram``, and
    the audit report names the node that produced the NaN. The guard
    is compiled in only under ``FLAGS.audit_numerics``, so the plain
    dispatch-bound path costs nothing — there, non-finite data still
    yields non-finite edges; pass an explicit finite ``range`` (which
    validates eagerly) for data that may contain non-finite values."""
    from .map2 import map2

    x = as_expr(x)
    bins = int(bins)
    if bins <= 0:
        raise ValueError(f"histogram needs bins >= 1, got {bins}")
    if range is not None:
        lo, hi = float(range[0]), float(range[1])
        if not (np.isfinite(lo) and np.isfinite(hi)) or hi < lo:
            raise ValueError(
                f"histogram range {range}: bounds must be finite "
                f"with max >= min")
        if lo == hi:  # numpy expands the degenerate explicit range
            lo, hi = lo - 0.5, hi + 0.5
    if x.size == 0:
        lo0, hi0 = (lo, hi) if range is not None else (0.0, 1.0)
        return (zeros((bins,), np.int32),
                as_expr(np.linspace(lo0, hi0, bins + 1)
                        .astype(np.float32)))
    if range is not None:
        # lo/hi captured as SCALARS so the kernels' compile-cache keys
        # repeat across calls (an ndarray capture would key by id and
        # recompile every call)
        def bucket(v, lo=lo, hi=hi, bins=bins):
            e = _hist_edges(lo, hi, bins)
            vv = v.astype(e.dtype)
            idx = jnp.searchsorted(e, vv, side="right") - 1
            # np.histogram: the last bin is closed on the right
            idx = jnp.where(vv == e[-1], bins - 1, idx)
            oob = (vv < e[0]) | (vv > e[-1])
            return jnp.where(oob, bins, idx).astype(jnp.int32)

        counts = bincount(map_expr(bucket, x), length=bins)
        edges = map2([as_expr(0.0)],
                     lambda _z, lo=lo, hi=hi, bins=bins:
                     _hist_edges(lo, hi, bins))
        return counts, edges
    # data-dependent range: min/max reductions feed the bucketing map
    # inside one traced program (no host round trip)
    from .reduce import max as _rmax
    from .reduce import min as _rmin

    lo_e, hi_e = _rmin(x), _rmax(x)

    def bucket2(v, lo, hi):
        lo = lo.astype(jnp.float32)
        hi = hi.astype(jnp.float32)
        _hist_guard_range(lo, hi)
        lo, hi = _hist_expand(lo, hi)
        e = _hist_edges(lo, hi, bins)
        idx = jnp.searchsorted(e, v.astype(e.dtype), side="right") - 1
        return jnp.clip(idx, 0, bins - 1).astype(jnp.int32)

    counts = bincount(map_expr(bucket2, x, lo_e, hi_e), length=bins)

    def edges_fn(lo, hi):
        lo = lo.astype(jnp.float32)
        hi = hi.astype(jnp.float32)
        _hist_guard_range(lo, hi)
        lo, hi = _hist_expand(lo, hi)
        return _hist_edges(lo, hi, bins)

    edges = map_expr(edges_fn, lo_e, hi_e)
    return counts, edges


def unique_counts(x, size: int) -> Expr:
    """Counts of each value in [0, size) — static-shape unique()."""
    return bincount(x, length=size)


def unique(x, size: int, fill_value=0.0, return_counts: bool = False):
    """Sorted unique values with STATIC output size (``jnp.unique``'s
    ``size=`` convention: the output is padded with ``fill_value``
    past the distinct count, and distinct values beyond ``size`` are
    dropped — XLA needs static shapes).

    One pipeline serves every mesh size and rank (N-d flattens, like
    np.unique): sort (the distributed sample sort where the operand is
    sharded, a local traced sort otherwise) -> boundary flags (a
    shifted compare GSPMD resolves with a halo exchange) -> prefix
    scan for dense ranks -> scatter into the static output; counts are
    the bincount reduction over ranks, sharing the single sort. NaNs
    compare unequal, so each NaN counts as its own value (the
    sort-based convention)."""
    from .map2 import map2

    x = as_expr(x)
    size = int(size)
    if size <= 0:
        raise ValueError(f"unique needs size >= 1, got {size}")
    if x.ndim != 1:
        x = ravel(x)
    if x.size == 0:
        vals = full((size,), fill_value, x.dtype)
        if not return_counts:
            return vals
        return vals, zeros((size,), np.int32)
    s = sort(x)
    # boundary flags via roll + where, NOT concatenate([ones(1), ...]):
    # the uneven-concat halo pattern mis-partitions under GSPMD on some
    # jax/XLA:CPU versions (every boundary double-counted — same bug
    # family as the linspace lowering note in ndarray.py); roll lowers
    # to a collective-permute that partitions exactly. Slot 0's rolled
    # neighbor is the LAST element, masked off by the where.
    flags = map_expr(
        lambda v: jnp.where(
            jnp.arange(v.shape[0]) == 0, 1,
            (v != jnp.roll(v, 1)).astype(jnp.int32)).astype(jnp.int32), s)
    rank = cumsum(flags) - 1
    vals = map2(
        [s, rank, flags],
        lambda v, r, f, size, fill: jnp.full(
            (size,), fill, v.dtype)
        .at[jnp.where(f == 1, r, size)].set(v, mode="drop"),
        fn_kw={"size": size, "fill": fill_value})
    if not return_counts:
        return vals
    return vals, bincount(rank, length=size)


def linspace(start, stop, num=50, endpoint=True, dtype=np.float32,
             tile_hint=None, tiling=None) -> Expr:
    return CreateExpr((int(num),), dtype, "linspace",
                      (float(start), float(stop), int(num), bool(endpoint)),
                      tiling, tile_hint)


def take(x, indices, axis=None) -> Expr:
    """Gather elements by integer index (NumPy ``take`` semantics).

    Indices enter the DAG as an input (not a closure capture) so the
    structural compile cache keys them by shape/dtype and the gather
    program is reused across different index arrays. Out-of-range
    indices raise up front, numpy-style (the traced gather would
    silently clamp them)."""
    x = as_expr(x)
    idx_np = np.asarray(indices)
    if axis is not None and x.ndim == 0:
        raise ValueError(
            f"take axis {axis} out of range for a 0-d operand")
    bound = x.size if axis is None else \
        x.shape[_checked_axis(int(axis), x.ndim)]
    if idx_np.size and (idx_np.min() < -bound or idx_np.max() >= bound):
        raise IndexError(
            f"take indices out of bounds for axis size {bound}: "
            f"range [{idx_np.min()}, {idx_np.max()}]")
    idx = as_expr(idx_np)
    return map_expr(lambda v, i: jnp.take(v, i, axis=axis), x, idx)


def var(x, axis=None, ddof: int = 0, keepdims: bool = False) -> Expr:
    """Variance: two-pass (mean, then mean of squared deviations), both
    passes fused into one XLA program by the single-jit lowering."""
    x = as_expr(x)
    m = mean(x, axis=axis, keepdims=True)
    d = x - m
    n = x.size if axis is None else _axis_count(x.shape, axis)
    return sum(d * d, axis=axis, keepdims=keepdims) / float(n - ddof)


def std(x, axis=None, ddof: int = 0, keepdims: bool = False) -> Expr:
    return sqrt(var(x, axis=axis, ddof=ddof, keepdims=keepdims))


def ptp(x, axis=None) -> Expr:
    return max(x, axis=axis) - min(x, axis=axis)


def _axis_count(shape, axis) -> int:
    if isinstance(axis, (int, np.integer)):
        axis = (int(axis),)
    n = 1
    for a in axis:
        n *= shape[a % len(shape)]
    return n


def cumsum(x, axis: int = 0) -> Expr:
    return scan(x, axis=axis, op="add")


def cumprod(x, axis: int = 0) -> Expr:
    return scan(x, axis=axis, op="mul")


def einsum(subscripts: str, *operands, precision=None) -> Expr:
    """NumPy-style einsum over lazy operands.

    Two-operand contractions (incl. ellipsis batching) build a planned
    ``ContractExpr`` — the smart-tiling pass searches output grids and
    contraction placements for them exactly as for 2-D GEMMs
    (SURVEY.md §2.3 pass (d)). 3+ operands decompose into a CHAIN of
    planned pairwise contractions along np.einsum_path's greedy order,
    so every intermediate GEMM is planner-visible too. Specs outside
    the family (diagonals, broadcasting ellipses, single-operand
    reductions in the path) stay a single traced ``jnp.einsum``
    sharded by GSPMD from the operands' tilings."""
    from .contract import contract, contract_chain, parse_einsum
    from .map2 import map2

    exprs = [as_expr(o) for o in operands]
    parsed = parse_einsum(subscripts, tuple(e.ndim for e in exprs))
    if parsed is not None:
        per_op, out_labels = parsed
        if len(exprs) == 2:
            e = contract(exprs[0], exprs[1], per_op[0], per_op[1],
                         out_labels, precision=precision)
            if e is not None:
                return e
        elif len(exprs) > 2:
            e = contract_chain(exprs, per_op, out_labels,
                               precision=precision)
            if e is not None:
                return e
    return map2(exprs,
                lambda *xs, subscripts, precision: jnp.einsum(
                    subscripts, *xs, precision=precision),
                fn_kw={"subscripts": subscripts, "precision": precision})


def tensordot(a, b, axes=2) -> Expr:
    """NumPy ``tensordot``: lowered as a planned contraction (free axes
    of ``a``, then of ``b``; contracted pairs share labels), so the
    smart-tiling pass plans it like any GEMM."""
    from .contract import _CANON, contract
    from .map2 import map2

    a, b = as_expr(a), as_expr(b)
    if isinstance(axes, (list, tuple)):
        ax0, ax1 = axes

        def _norm(xs, nd):
            out = []
            for x in np.atleast_1d(xs):
                x = int(x)
                if not -nd <= x < nd:
                    raise ValueError(
                        f"tensordot axis {x} out of range for "
                        f"ndim {nd}")
                out.append(x % nd)
            return tuple(out)

        ax_a = _norm(ax0, a.ndim)
        ax_b = _norm(ax1, b.ndim)
        if len(ax_a) != len(ax_b):
            raise ValueError(
                f"tensordot axes lists differ in length: "
                f"{len(ax_a)} vs {len(ax_b)}")
    else:
        k = int(axes)
        if k > a.ndim or k > b.ndim:
            raise ValueError(
                f"tensordot axes={k} exceeds operand ranks "
                f"{a.ndim} and {b.ndim}")
        ax_a = tuple(range(a.ndim - k, a.ndim))
        ax_b = tuple(range(k))
    la = [_CANON[i] for i in range(a.ndim)]
    lb = [_CANON[a.ndim + i] for i in range(b.ndim)]
    for i, j in zip(ax_a, ax_b):
        lb[j] = la[i]
    out = tuple(la[i] for i in range(a.ndim) if i not in ax_a) + \
        tuple(lb[j] for j in range(b.ndim) if j not in ax_b)
    e = contract(a, b, tuple(la), tuple(lb), out)
    if e is not None:
        return e
    axes_n = (ax_a, ax_b)
    return map2([a, b],
                lambda x, y, axes: jnp.tensordot(x, y, axes=axes),
                fn_kw={"axes": axes_n})


def matmul(a, b, precision=None) -> Expr:
    """``a @ b``: 1-D/2-D operands route through the smart-tiling
    DotExpr; batched (>2-D) operands become a planned batched
    contraction (traced ``jnp.matmul`` only when batch dims need
    broadcasting)."""
    from .contract import _CANON, contract
    from .dot import dot as dot_expr
    from .map2 import map2

    a, b = as_expr(a), as_expr(b)
    if a.ndim <= 2 and b.ndim <= 2:
        return dot_expr(a, b, precision=precision)
    e = None
    if a.ndim >= 2 and b.ndim >= 2:
        nb = _size_max(a.ndim, b.ndim) - 2
        batch = [_CANON[i] for i in range(nb)]
        la = tuple(batch[nb - (a.ndim - 2):]) + (_CANON[nb],
                                                 _CANON[nb + 1])
        lb = tuple(batch[nb - (b.ndim - 2):]) + (_CANON[nb + 1],
                                                 _CANON[nb + 2])
        out = tuple(batch) + (_CANON[nb], _CANON[nb + 2])
        e = contract(a, b, la, lb, out, precision=precision)
    if e is not None:
        return e
    return map2([a, b],
                lambda x, y, precision: jnp.matmul(
                    x, y, precision=precision),
                fn_kw={"precision": precision})


def _size_max(a: int, b: int) -> int:
    return a if a >= b else b


def trace(x, offset: int = 0) -> Expr:
    from .map2 import map2

    return map2([as_expr(x)],
                lambda v, offset: jnp.trace(v, offset=offset),
                fn_kw={"offset": offset})


def inner(a, b) -> Expr:
    """NumPy ``inner``: 1-D operands contract (a dot); otherwise the
    last-axis contraction as a planned ContractExpr."""
    from .contract import _CANON, contract
    from .map2 import map2

    a, b = as_expr(a), as_expr(b)
    if a.ndim == 1 and b.ndim == 1:
        return dot(a, b)
    if a.ndim >= 1 and b.ndim >= 1:
        la = tuple(_CANON[i] for i in range(a.ndim - 1)) + ("z",)
        lb = tuple(_CANON[a.ndim - 1 + i]
                   for i in range(b.ndim - 1)) + ("z",)
        out = la[:-1] + lb[:-1]
        e = contract(a, b, la, lb, out)
        if e is not None:
            return e
    return map2([a, b], lambda x, y: jnp.inner(x, y))


def outer_product(a, b) -> Expr:
    """NumPy ``np.outer``: flattened outer product (distinct from the
    tile-pair ``outer`` primitive in ``expr/outer.py``)."""
    return map_expr(lambda u, v: u.ravel()[:, None] * v.ravel()[None, :],
                    as_expr(a), as_expr(b))


class BlockedScanExpr(Expr):
    """Distributed prefix scan over the sharded leading axis
    (ops/scan.py): local scan, all_gather of per-shard totals,
    exclusive offset combine — ONE shard_map program instead of the
    all-gathered replicated scan GSPMD emits for a traced cumsum on a
    sharded axis (measured minutes vs milliseconds at 4M elements)."""

    def __init__(self, x: Expr, op: str):
        self.x = x
        self.op = op
        super().__init__(x.shape, x.dtype)

    def children(self):
        return (self.x,)

    def replace_children(self, new_children) -> "BlockedScanExpr":
        return BlockedScanExpr(new_children[0], self.op)

    def _lower(self, env) -> Any:
        from ..ops import scan as scan_ops

        return scan_ops.blocked_scan(self.x.lower(env), self.op,
                                     in_axes=self.x.out_tiling().axes)

    def _sig(self, ctx):
        # trailing-axis sharding changes the lowered program
        return ("blocked_scan", self.op, self.x.out_tiling().axes,
                ctx.of(self.x))

    def _default_tiling(self):
        from ..array import tiling as tiling_mod
        from ..ops import scan as scan_ops

        t = scan_ops.scan_axes(self.x.out_tiling().axes, self.ndim)
        return tiling_mod.sanitize(t, self.shape)


def _blocked_scannable(x: Expr, axis: int, op: str) -> bool:
    """Dispatch guard for the distributed blocked scan: leading axis,
    divisible nonempty length, dtype preserved by the cumulative op
    (bool cumsum promotes to int32 — the map path infers that
    correctly), and not a layout where axis 0 is already unsharded
    while another axis carries the sharding (there the local per-shard
    scan is collective-free; resharding to row tiling would regress)."""
    from ..ops import scan as scan_ops
    from ..parallel import mesh as mesh_mod
    from ..array import tiling as tiling_mod

    if x.ndim < 1 or axis not in (0, -x.ndim):
        return False
    p = int(mesh_mod.get_mesh().shape.get(tiling_mod.AXIS_ROW, 1))
    if p <= 1 or x.shape[0] == 0 or x.shape[0] % p != 0:
        return False
    out = jax.eval_shape(lambda v: scan_ops._LOCAL[op](v, axis=0),
                         jax.ShapeDtypeStruct(x.shape, x.dtype))
    if out.dtype != x.dtype:
        return False
    t = x.out_tiling()
    if (x.ndim >= 2 and t.mesh_axis_of(0) is None
            and t.sharded_axes()):
        return False
    return True


def scan(x, axis: int = 0, op: str = "add") -> Expr:
    """Prefix scan along an axis (exercised by SSVD per BASELINE.json:11).

    The leading axis of any-rank arrays on a multi-device mesh (row
    axis dividing the length) runs the distributed blocked scan
    (ops/scan.py), trailing-axis sharding preserved; other axes lower
    to ``jnp.cumsum``-family ops — local per shard when the scan axis
    is unsharded."""
    from ..ops import scan as scan_ops

    x = as_expr(x)
    if op not in scan_ops._LOCAL:
        raise ValueError(f"unknown scan op {op!r}")
    if _blocked_scannable(x, axis, op):
        return BlockedScanExpr(x, op)
    fn = scan_ops._LOCAL[op]
    return map_expr(lambda v: fn(v, axis=axis), x)


import jax  # noqa: E402  (used inside scan closures)
