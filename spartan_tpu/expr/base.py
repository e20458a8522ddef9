"""Lazy expression DAG over DistArrays, evaluated as ONE jitted XLA program.

Parity with the reference's expr layer (SURVEY.md §2.3: ``[U]
spartan/expr/base.py`` — ``Expr`` node with unique id, children,
``evaluate()`` with DAG-level memo cache, ``force``, ``glom``, operator
overloading, ``Val``/``AsArray`` wrappers). The execution model is the
re-design mandated by BASELINE.json:5: instead of shipping per-tile kernels
over RPC, ``force()`` lowers the whole DAG into a single traced function
over the leaf arrays and jit-compiles it with GSPMD out-shardings — the
expr DAG -> jaxpr boundary replaces the expr -> per-tile-kernel boundary
(SURVEY.md §3.2). Compiled executables are cached by DAG structure, so
iterative drivers (k-means, SGD) hit the cache every step.
"""

from __future__ import annotations

import itertools
import os
import threading
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..array import distarray as da
from ..array import tiling as tiling_mod
from ..array.distarray import DistArray
from ..array.tiling import Tiling
from ..obs import ledger as ledger_mod
from ..obs import monitor as monitor_mod
from ..obs import numerics as numerics_mod
from ..obs import profile as profile_mod
from ..obs.explain import build_plan_report, key_hash, scope_digest_table
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redistribute_mod
from .. import persist as persist_mod
from ..resilience import degrade as degrade_mod
from ..resilience import faults as faults_mod
from ..resilience import integrity as integrity_mod
from ..resilience import memory as memory_mod
from ..utils import config as config_mod
from ..utils import profiling as prof
from ..utils.config import FLAGS
from ..utils.log import log_debug

_ids = itertools.count()


def _user_site() -> Optional[Tuple[str, int, str]]:
    """First stack frame outside spartan_tpu — the user line that built
    this expr (the reference's ExprTrace error attribution, SURVEY.md §5).
    """
    import sys

    f = sys._getframe(2)
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.startswith(pkg):
            return (fn, f.f_lineno, f.f_code.co_name)
        f = f.f_back
    return None


class ExprError(RuntimeError):
    """Evaluation error annotated with the user line that built the
    failing expression."""


def fn_key(fn: Any) -> Any:
    """Structural identity for a kernel function: code object + captured
    closure values + defaults. Two closures created by the same def with
    the same captures compare equal, so iterative drivers that rebuild
    their kernels every step (the common pattern) still hit the compile
    cache instead of recompiling per iteration."""
    import functools

    if isinstance(fn, functools.partial):
        return ("partial", fn_key(fn.func), fn.args,
                tuple(sorted(fn.keywords.items())))
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn  # builtins / callables: identity is the best we have
    cells: Tuple = ()
    closure = getattr(fn, "__closure__", None)
    if closure:
        vals = []
        for c in closure:
            try:
                v = c.cell_contents
            except ValueError:
                v = "<empty>"
            try:
                hash(v)
            except TypeError:
                v = id(v)
            vals.append(v)
        cells = tuple(vals)
    return (code, cells, getattr(fn, "__defaults__", None) or ())


class Expr:
    """A node in the lazy DAG. Subclasses define children + lowering."""

    def __init__(self, shape: Tuple[int, ...], dtype: Any):
        self._id = next(_ids)
        self._shape = tuple(int(s) for s in shape)
        self._dtype = np.dtype(dtype)
        self._result: Optional[DistArray] = None
        self._forced_tiling: Optional[Tiling] = None
        self._site = _user_site()

    # -- structure ------------------------------------------------------

    @property
    def shape(self) -> Tuple[int, ...]:
        return self._shape

    @property
    def dtype(self) -> np.dtype:
        return self._dtype

    @property
    def ndim(self) -> int:
        return len(self._shape)

    @property
    def size(self) -> int:
        return int(np.prod(self._shape)) if self._shape else 1

    def children(self) -> Tuple["Expr", ...]:
        raise NotImplementedError

    def replace_children(self, new_children: Tuple["Expr", ...]) -> "Expr":
        """Clone this node over rewritten children (optimizer passes)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support replace_children")

    def _lower(self, env: Dict[int, Any]) -> Any:
        """Emit the traced jnp value for this node (children already in
        env is NOT guaranteed — call self.lower on children)."""
        raise NotImplementedError

    def lower(self, env: Dict[int, Any]) -> Any:
        if self._id not in env:
            try:
                if FLAGS.trace_annotations:
                    # trace-time-only: device profiles (Perfetto /
                    # TensorBoard) attribute XLA ops back to this node;
                    # inside _build_plan's naming session the scope
                    # also carries the node's _sig digest — the join
                    # key st.profile's trace-parse tier matches on
                    with jax.named_scope(profile_mod.scope_name(self)):
                        val = self._lower(env)
                else:
                    val = self._lower(env)
            except Exception as e:
                if self._site and not getattr(e, "_expr_annotated", False):
                    try:
                        e._expr_annotated = True  # annotate innermost only
                        note = (
                            f"while evaluating {type(self).__name__} built "
                            f"at {self._site[0]}:{self._site[1]} "
                            f"(in {self._site[2]})")
                        if hasattr(e, "add_note"):
                            e.add_note(note)
                        else:  # Python < 3.11: emulate PEP 678 notes
                            e.__notes__ = getattr(e, "__notes__", []) + [note]
                    except Exception:
                        pass  # slotted/frozen exceptions: keep the original
                raise
            # numerics sentinel: inside an audited trace (st.audit /
            # FLAGS.audit_numerics) attach a device-side health word +
            # host callback to this node's value; a no-op None check
            # otherwise, and lower() only runs on plan-cache misses
            numerics_mod.probe(self, val)
            if (self._forced_tiling is not None
                    and not profile_mod.shard_local_lowering()):
                # (shard-local lowering — the profiler re-timing one
                # shard's sub-plan per device — must NOT constrain:
                # the value is shard-sized, and resharding it across
                # the mesh is exactly what we're measuring around)
                # smart-tiling chose this node's layout: constrain it
                # so GSPMD materializes the planned resharding points.
                # Through the redistribution seam (parallel/
                # redistribute.constrain): under
                # FLAGS.redistribution_planner, edges where the cost
                # model predicts an explicit collective schedule beats
                # GSPMD's generic lowering are emitted explicitly (the
                # node's natural layout is the source the DP priced
                # this edge from); everything else — planner off, no
                # predicted win, indivisible shapes — stays a plain
                # with_sharding_constraint.
                val = redistribute_mod.constrain(
                    val, self._forced_tiling, mesh_mod.get_mesh(),
                    src=self._default_tiling())
            env[self._id] = val
        return env[self._id]

    def _sig(self, ctx: "_SigCtx") -> Tuple:
        """Structural signature of this node (children via ctx.of)."""
        raise NotImplementedError

    def out_tiling(self) -> Tiling:
        """Sharding of the evaluated result (overridable by the
        auto-tiling pass via ``_forced_tiling``)."""
        if self._forced_tiling is not None:
            return self._forced_tiling
        return self._default_tiling()

    def _default_tiling(self) -> Tiling:
        raise NotImplementedError

    # -- evaluation -----------------------------------------------------

    def evaluate(self, donate: Sequence[Any] = ()) -> DistArray:
        return evaluate(self, donate=donate)

    def evaluate_async(self, donate: Sequence[Any] = (),
                       tenant: Optional[str] = None,
                       deadline_s: Optional[float] = None):
        """Submit this expr to the concurrent serving engine
        (spartan_tpu/serve): returns an ``EvalFuture`` immediately;
        identical-signature requests from concurrent callers coalesce
        into one batched dispatch. See docs/SERVING.md."""
        from ..serve import evaluate_async as _ea

        return _ea(self, donate=donate, tenant=tenant,
                   deadline_s=deadline_s)

    def force(self, donate: Sequence[Any] = ()) -> DistArray:
        return evaluate(self, donate=donate)

    def optimized(self) -> "Expr":
        from .optimize import optimize

        return optimize(self)

    def invalidate(self) -> None:
        """Drop this node's cached result; the next force recomputes from
        lineage (exprs are deterministic — SURVEY.md §5 failure
        recovery: recompute-from-expr-DAG)."""
        self._result = None

    def recompute(self) -> DistArray:
        """Lineage-based recovery: re-evaluate this expr from its
        (deterministic) DAG, ignoring the cached result."""
        self.invalidate()
        return evaluate(self)

    def glom(self) -> np.ndarray:
        return self.evaluate().glom()

    def __array__(self, dtype=None):
        out = self.glom()
        return out.astype(dtype) if dtype is not None else out

    # -- operator overloading (build MapExprs) --------------------------

    def _binop(self, other: Any, name: str, reverse: bool = False) -> "Expr":
        from .map import build_binop

        return build_binop(name, self, other, reverse)

    def __add__(self, o):
        return self._binop(o, "add")

    def __radd__(self, o):
        return self._binop(o, "add", True)

    def __sub__(self, o):
        return self._binop(o, "subtract")

    def __rsub__(self, o):
        return self._binop(o, "subtract", True)

    def __mul__(self, o):
        return self._binop(o, "multiply")

    def __rmul__(self, o):
        return self._binop(o, "multiply", True)

    def __truediv__(self, o):
        return self._binop(o, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, "divide", True)

    def __floordiv__(self, o):
        return self._binop(o, "floor_divide")

    def __rfloordiv__(self, o):
        return self._binop(o, "floor_divide", True)

    def __mod__(self, o):
        return self._binop(o, "mod")

    def __rmod__(self, o):
        return self._binop(o, "mod", True)

    def __pow__(self, o):
        return self._binop(o, "power")

    def __rpow__(self, o):
        return self._binop(o, "power", True)

    def __neg__(self):
        from .map import build_unop

        return build_unop("negative", self)

    def __abs__(self):
        from .map import build_unop

        return build_unop("absolute", self)

    def __eq__(self, o):  # type: ignore[override]
        return self._binop(o, "equal")

    def __ne__(self, o):  # type: ignore[override]
        return self._binop(o, "not_equal")

    def __lt__(self, o):
        return self._binop(o, "less")

    def __le__(self, o):
        return self._binop(o, "less_equal")

    def __gt__(self, o):
        return self._binop(o, "greater")

    def __ge__(self, o):
        return self._binop(o, "greater_equal")

    def __and__(self, o):
        return self._binop(o, "bitwise_and")

    def __or__(self, o):
        return self._binop(o, "bitwise_or")

    def __xor__(self, o):
        return self._binop(o, "bitwise_xor")

    def __invert__(self):
        from .map import build_unop

        # numpy semantics: logical not for bools, bitwise not for ints
        name = "logical_not" if np.dtype(self.dtype) == np.bool_ else "invert"
        return build_unop(name, self)

    def __hash__(self) -> int:  # __eq__ is overloaded; hash by identity
        return id(self)

    def __bool__(self) -> bool:
        # Never truth-test an Expr: __eq__/__lt__/... build lazy
        # element-wise graphs, so `if expr:`, `expr in seq`, and
        # `assert expr == y` would silently build (or worse, force) a
        # graph where the caller expected a Python bool. Raise loudly
        # with both the build site and the remedy.
        here = _user_site()
        built = (f"; the expr was built at {self._site[0]}:"
                 f"{self._site[1]} (in {self._site[2]})"
                 if self._site else "")
        at = (f" at {here[0]}:{here[1]} (in {here[2]})" if here else "")
        raise ExprError(
            f"an Expr has no truth value (truth-tested{at}{built}). "
            "Lazy comparisons build element-wise graphs, so `if "
            "expr:` or `expr in a_list` would silently evaluate or "
            "mis-evaluate. Force explicitly instead: "
            "bool(expr.glom()) for a size-1 result, "
            ".any()/.all() for element-wise tests, or `is` for "
            "object identity.")

    def __getitem__(self, idx) -> "Expr":
        from .slice import make_slice

        return make_slice(self, idx)

    # -- numpy-flavoured conveniences ------------------------------------

    def astype(self, dtype) -> "Expr":
        from .builtins import astype

        return astype(self, dtype)

    def sum(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import sum as _sum

        return _sum(self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import mean

        return mean(self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import max as _max

        return _max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import min as _min

        return _min(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None) -> "Expr":
        from .reduce import argmax

        return argmax(self, axis=axis)

    def argmin(self, axis=None) -> "Expr":
        from .reduce import argmin

        return argmin(self, axis=axis)

    def all(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import all as _all

        return _all(self, axis=axis, keepdims=keepdims)

    def any(self, axis=None, keepdims=False) -> "Expr":
        from .reduce import any as _any

        return _any(self, axis=axis, keepdims=keepdims)

    def dot(self, other) -> "Expr":
        from .dot import dot

        return dot(self, other)

    def __matmul__(self, other) -> "Expr":
        from .dot import dot

        return dot(self, other)

    def transpose(self, *axes) -> "Expr":
        from .reshape import transpose

        return transpose(self, *axes)

    @property
    def T(self) -> "Expr":
        return self.transpose()

    def reshape(self, *shape) -> "Expr":
        from .reshape import reshape

        return reshape(self, *shape)

    def ravel(self) -> "Expr":
        from .reshape import ravel

        return ravel(self)

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(id={self._id}, shape={self._shape}, "
                f"dtype={self._dtype})")


# -- leaf nodes ---------------------------------------------------------

# numpy dtype -> canonical string for structural signatures:
# ``str(dtype)`` re-derives the name each call (~3µs), and leaf
# signing is on the per-request serving hot path
_dtype_strs: Dict[Any, str] = {}


def _dtype_str(dt: Any) -> str:
    s = _dtype_strs.get(dt)
    if s is None:
        s = _dtype_strs[dt] = str(dt)
    return s


class ValExpr(Expr):
    """Leaf wrapping an evaluated DistArray (the reference's ``Val``)."""

    def __init__(self, value: DistArray):
        super().__init__(value.shape, value.dtype)
        self.value = value
        self._result = value

    def invalidate(self) -> None:
        pass  # a Val IS its data; there is no lineage to recompute from

    def children(self) -> Tuple[Expr, ...]:
        return ()

    def replace_children(self, new_children: Tuple[Expr, ...]) -> Expr:
        return self

    def _lower(self, env: Dict[int, Any]) -> Any:
        raise RuntimeError("leaf must be seeded into env before lowering")

    def _sig(self, ctx: "_SigCtx") -> Tuple:
        return ("val", ctx.leaf_pos(self), self._shape,
                _dtype_str(self._dtype), self.value.tiling.axes)

    def _default_tiling(self) -> Tiling:
        return self.value.tiling


class ScalarExpr(Expr):
    """Leaf wrapping a Python scalar, passed as a (weakly-typed) traced
    argument so iterative drivers don't recompile when it changes."""

    def __init__(self, value: Any):
        dtype = np.result_type(type(value))
        super().__init__((), dtype)
        self.pyvalue = value
        self.weak_kind = ("b" if isinstance(value, bool) else
                          "i" if isinstance(value, int) else "f")

    def children(self) -> Tuple[Expr, ...]:
        return ()

    def replace_children(self, new_children: Tuple[Expr, ...]) -> Expr:
        return self

    def _lower(self, env: Dict[int, Any]) -> Any:
        raise RuntimeError("leaf must be seeded into env before lowering")

    def _sig(self, ctx: "_SigCtx") -> Tuple:
        # value intentionally NOT in the signature: same-structure DAGs with
        # different scalar constants share one executable.
        return ("scalar", ctx.leaf_pos(self), self.weak_kind)

    def _default_tiling(self) -> Tiling:
        return tiling_mod.replicated(0)


def as_expr(value: Any) -> Expr:
    """The reference's ``AsArray``: coerce anything to an Expr."""
    if isinstance(value, Expr):
        return value
    if isinstance(value, DistArray):
        return ValExpr(value)
    if isinstance(value, (bool, int, float, np.bool_, np.integer,
                          np.floating)):
        if isinstance(value, (np.bool_, np.integer, np.floating)):
            value = value.item()
        return ScalarExpr(value)
    if isinstance(value, (np.ndarray, list, tuple)):
        return ValExpr(da.from_numpy(np.asarray(value)))
    if isinstance(value, jax.Array):
        return ValExpr(da.from_jax(value))
    if type(value).__name__ == "MaskedDistArray":
        raise TypeError(
            "this operation does not support MaskedDistArray operands "
            "(the mask would be silently dropped). Use the mask-aware "
            "ops — elementwise arithmetic / map_expr, dot, sort, "
            "argsort, median, concatenate, and the masked reductions — "
            "or pass .filled(fill) / .data explicitly.")
    raise TypeError(f"cannot lift {type(value).__name__} into an Expr")


def lazify(value: Any) -> Expr:
    return as_expr(value)


class TupleExpr(Expr):
    """Multiple roots evaluated in ONE jitted program (the reference's
    ``TupleExpr``/``ListExpr`` — SURVEY.md §2.3). ``glom()``/``evaluate()``
    return tuples; elements may have different shapes/dtypes/tilings."""

    def __init__(self, elements: Sequence[Expr]):
        self.elements: Tuple[Expr, ...] = tuple(as_expr(e) for e in elements)
        if not self.elements:
            raise ValueError("TupleExpr needs at least one element")
        super().__init__((), self.elements[0].dtype)

    def children(self) -> Tuple[Expr, ...]:
        return self.elements

    def replace_children(self, new_children: Tuple[Expr, ...]) -> "TupleExpr":
        return TupleExpr(new_children)

    def _lower(self, env: Dict[int, Any]) -> Any:
        return tuple(e.lower(env) for e in self.elements)

    def _sig(self, ctx: "_SigCtx") -> Tuple:
        return ("tuple",) + tuple(ctx.of(e) for e in self.elements)

    def out_tilings(self) -> Tuple[Tiling, ...]:
        return tuple(tiling_mod.sanitize(e.out_tiling(), e.shape)
                     for e in self.elements)

    def _default_tiling(self) -> Tiling:
        return tiling_mod.replicated(0)

    def __len__(self) -> int:
        return len(self.elements)

    def evaluate(self, donate: Sequence[Any] = ()
                 ) -> Tuple[DistArray, ...]:  # type: ignore[override]
        return evaluate(self, donate=donate)

    def force(self, donate: Sequence[Any] = ()
              ) -> Tuple[DistArray, ...]:  # type: ignore[override]
        return evaluate(self, donate=donate)

    def glom(self):  # type: ignore[override]
        """Every root on the host, from one ``fetch``."""
        return da.fetch_to_host(tuple(r.jax_array
                                      for r in evaluate(self)))[0]


def tuple_of(*elements: Any) -> TupleExpr:
    return TupleExpr(elements)


class ListExpr(TupleExpr):
    """List-shaped multi-root evaluation (reference's ``ListExpr``)."""

    def glom(self):  # type: ignore[override]
        return list(TupleExpr.glom(self))


class DictExpr(Expr):
    """Dict of exprs evaluated in ONE jitted program (reference's
    ``DictExpr``); ``glom()``/``evaluate()`` return dicts."""

    def __init__(self, items: Dict[str, Any]):
        self._keys = tuple(sorted(items))
        self._tuple = TupleExpr([items[k] for k in self._keys])
        super().__init__((), self._tuple.elements[0].dtype)

    def children(self) -> Tuple[Expr, ...]:
        return (self._tuple,)

    def replace_children(self, new_children: Tuple[Expr, ...]) -> "DictExpr":
        e = DictExpr.__new__(DictExpr)
        Expr.__init__(e, (), new_children[0].elements[0].dtype)
        e._keys = self._keys
        e._tuple = new_children[0]
        return e

    def _lower(self, env: Dict[int, Any]) -> Any:
        raise RuntimeError("DictExpr is evaluated via its tuple")

    def _sig(self, ctx: "_SigCtx") -> Tuple:
        return ("dict", self._keys, ctx.of(self._tuple))

    def evaluate(self, donate: Sequence[Any] = ()):  # type: ignore[override]
        vals = evaluate(self._tuple, donate=donate)
        return dict(zip(self._keys, vals))

    def force(self, donate: Sequence[Any] = ()):  # type: ignore[override]
        return self.evaluate(donate=donate)

    def glom(self):  # type: ignore[override]
        return dict(zip(self._keys, self._tuple.glom()))

    def __getitem__(self, key: str) -> Expr:  # type: ignore[override]
        return self._tuple.elements[self._keys.index(key)]


def dict_of(**items: Any) -> DictExpr:
    return DictExpr(items)


# -- evaluation machinery ----------------------------------------------


class _SigCtx:
    """Assigns stable positions to leaves and dedups shared subtrees."""

    def __init__(self) -> None:
        self.leaves: List[Expr] = []
        self._leaf_pos: Dict[int, int] = {}
        self._memo: Dict[int, Tuple] = {}
        self._visit: Dict[int, int] = {}

    def leaf_pos(self, leaf: Expr) -> int:
        pos = self._leaf_pos.get(leaf._id)
        if pos is None:
            pos = len(self.leaves)
            self._leaf_pos[leaf._id] = pos
            self.leaves.append(leaf)
        return pos

    def of(self, node: Expr) -> Tuple:
        if node._id in self._memo:
            # shared subtree: refer to it by visit index, not structure,
            # so diamond DAGs don't blow up exponentially
            return ("ref", self._visit[node._id])
        sig = node._sig(self)
        if node._forced_tiling is not None:
            sig = sig + ("forced", node._forced_tiling.axes)
        self._visit[node._id] = len(self._memo)
        self._memo[node._id] = sig
        return sig


class _PlanSigCtx(_SigCtx):
    """Signs the RAW (pre-optimizer) DAG for the plan cache.

    Nodes carrying a cached ``_result`` sign as Val leaves — exactly
    the rewrite ``CollapseCachedPass`` would perform — because the
    optimizer's output is state-dependent: the same structure with a
    different cached-result frontier optimizes to a different plan.
    ``_forced_tiling`` markers stay in the signature via the base
    class. One traversal produces both the plan key and the raw leaf
    list the cached plan's arguments are gathered from."""

    def of(self, node: Expr) -> Tuple:
        if node._id in self._memo:
            return ("ref", self._visit[node._id])
        if (node._result is not None and not isinstance(node, ValExpr)
                and isinstance(node._result, DistArray)):
            # matches ValExpr._sig for the leaf CollapseCachedPass
            # would substitute (no forced marker: the substituted
            # ValExpr never carries one)
            sig = ("val", self.leaf_pos(node), node._shape,
                   _dtype_str(node._dtype), node._result.tiling.axes)
            self._visit[node._id] = len(self._memo)
            self._memo[node._id] = sig
            return sig
        return super().of(node)


class _Plan:
    """Complete steady-state execution recipe for one raw-DAG
    signature: the compile-cache key, the traced callable (donation
    variants re-jit it with ``donate_argnums``), output tilings, and
    ``arg_order`` mapping each executable argument position to the
    position of the raw leaf that feeds it. ``report`` is the
    introspection dict ``st.explain`` reads (obs/explain.py), built
    once on the miss path and shared between the cached plan and its
    first-run identity variant."""

    # __weakref__: the cost ledger (obs/ledger.py) keeps weak plan
    # references so st.ledger(validate=True) can run the memory
    # validation for live plans without pinning evicted ones
    __slots__ = ("key", "traced", "out_tilings", "is_tuple", "arg_order",
                 "report", "governed_rung", "persist_digest",
                 "__weakref__")

    def __init__(self, key: Tuple, traced: Callable,
                 out_tilings: Tuple[Tiling, ...], is_tuple: bool,
                 arg_order: Tuple[int, ...],
                 report: Optional[Dict[str, Any]] = None):
        self.key = key
        self.traced = traced
        self.out_tilings = out_tilings
        self.is_tuple = is_tuple
        self.arg_order = arg_order
        self.report = report
        # set by the memory governor (resilience/memory.py) when this
        # plan's predicted peak exceeded the HBM budget: hits re-route
        # to the named ladder rung instead of dispatching a doomed
        # executable. One attribute read per cache hit when ungoverned.
        self.governed_rung: Optional[str] = None
        # on-disk address in the warm-start store (spartan_tpu/persist)
        # when FLAGS.persist_cache_dir is set and the plan key has a
        # process-stable digest; None otherwise (one attribute read on
        # the first-compile path decides whether to persist)
        self.persist_digest: Optional[str] = None


class _Exec:
    """A jitted executable plus whether its first (trace + XLA
    compile) call already happened — for compile/dispatch phase
    attribution."""

    __slots__ = ("jitted", "warm")

    def __init__(self, jitted: Callable):
        self.jitted = jitted
        self.warm = False


# -- shared evaluation state + locking discipline ------------------------
#
# Everything below is shared by every thread that evaluates (the serve
# engine's workers, st.explain, plain evaluate() callers). The locking
# discipline, also documented in spartan_tpu/serve/__init__.py:
#
#   * ``_cache_lock`` guards BOTH ``_plan_cache`` and ``_compile_cache``
#     (they evict together). It is held only for dict operations — never
#     across an optimize, trace, compile or dispatch — so a slow miss on
#     one thread cannot stall hits on another; the price is that two
#     threads racing the same miss may both build the plan and the
#     loser's work is discarded (``setdefault`` keeps the winner's).
#   * every OTHER module goes through the accessors (``lookup_plan`` /
#     ``store_plan`` / ``cached_executable`` / the clear/size helpers);
#     ``tools/lint_repo.py`` rule 6 forbids touching ``_plan_cache`` /
#     ``_compile_cache`` / ``_cache_lock`` outside this file.
#   * the metrics registry, trace ring, chaos plan and retry budgets
#     take their own locks (obs/metrics.py, obs/trace.py,
#     resilience/faults.py, resilience/engine.py); none of them is ever
#     held while calling into this module, and ``_cache_lock`` is never
#     held while calling out — the lock graph has no cycles.

# define() returns the Flag; the hot lookup reads ._value directly
# (one attribute load) instead of FLAGS.__getattr__'s dict walk
_PLAN_CACHE_MAX_FLAG = FLAGS.define_int(
    "plan_cache_max", 512,
    "Maximum plans retained in the evaluate() plan cache; beyond it "
    "the least-recently-used plan is evicted together with every "
    "compiled variant keyed under it (donation sets, serve batch "
    "sizes). 0 = unbounded (the pre-serving behavior, and the hot "
    "path skips the LRU reordering). Eviction counts land on the "
    "plan_evictions metric.")

_compile_cache: Dict[Tuple, _Exec] = {}
_plan_cache: "OrderedDict[Tuple, _Plan]" = OrderedDict()
_cache_lock = threading.Lock()

# -- executable-launch serialization -------------------------------------
#
# XLA:CPU's intra-process collective rendezvous is NOT safe under
# concurrent launches: two executables running at once interleave
# their all-reduce participants on the same device set and deadlock
# (observed as "waiting for all participants to arrive at rendezvous"
# stalls). Concurrent evaluate() callers and the serve engine's
# workers therefore serialize the LAUNCH (not the planning) on
# backends that need it; TPU launches are queue-serialized per device
# by PJRT already, so "auto" leaves them unguarded.

_DISPATCH_SERIALIZE_FLAG = FLAGS.define_str(
    "dispatch_serialize", "auto",
    "Serialize executable launches across threads: 'auto' (serialize "
    "on the cpu backend, whose collective rendezvous deadlocks under "
    "concurrent launches; leave other backends unserialized), 'on', "
    "or 'off'. Planning, arg gathering and result wrapping always run "
    "concurrently — only the launch is guarded.")

_launch_lock = threading.Lock()
_serialize_auto: Optional[bool] = None


class _NullLaunchGuard:
    __slots__ = ()

    def __enter__(self) -> "_NullLaunchGuard":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


_NULL_GUARD = _NullLaunchGuard()
_NULL_PHASE = _NullLaunchGuard()  # untimed _wrap_result epilogues


def launch_guard():
    """The launch-serialization context for one executable run; shared
    by ``_dispatch`` and the serve coalescer. One flag read (+ a
    cached backend probe under 'auto') on the hot path."""
    global _serialize_auto
    v = _DISPATCH_SERIALIZE_FLAG._value
    if v == "off":
        return _NULL_GUARD
    if v != "on":
        if _serialize_auto is None:
            _serialize_auto = jax.default_backend() == "cpu"
        if not _serialize_auto:
            return _NULL_GUARD
    return _launch_lock


def compile_cache_size() -> int:
    return len(_compile_cache)


def plan_cache_size() -> int:
    return len(_plan_cache)


def clear_compile_cache() -> None:
    # the plan cache holds references into the compile cache (its key
    # and traced closure), so the two clear together
    with _cache_lock:
        _compile_cache.clear()
        _plan_cache.clear()


def clear_plan_cache() -> None:
    with _cache_lock:
        _plan_cache.clear()


def lookup_plan(plan_key: Tuple) -> Optional[_Plan]:
    """Plan-cache read (the ONLY read path — obs/explain and serve/
    go through here, not the dict). A hit refreshes LRU recency when
    the cache is bounded; unbounded (plan_cache_max=0) skips the
    reorder so the legacy hot path is untouched."""
    with _cache_lock:
        plan = _plan_cache.get(plan_key)
        if plan is not None and _PLAN_CACHE_MAX_FLAG._value > 0:
            _plan_cache.move_to_end(plan_key)
        return plan


def store_plan(plan_key: Tuple, plan: _Plan) -> _Plan:
    """Plan-cache insert with LRU eviction (FLAGS.plan_cache_max).

    Eviction is donation-variant-aware: the evicted plan's compile
    signature prefixes every executable compiled FOR it (the donation
    variants ``plan.key + (donate_key,)`` and the serve coalescer's
    batch variants ``plan.key + ('serve', B, mode)``), so those leave
    the compile cache with it — an unbounded per-tenant plan stream
    cannot pin its dead executables' HBM/host memory. First writer
    wins on a race (the existing plan is returned)."""
    evicted = 0
    with _cache_lock:
        cur = _plan_cache.get(plan_key)
        if cur is not None:
            return cur
        _plan_cache[plan_key] = plan
        maxn = _PLAN_CACHE_MAX_FLAG._value
        while maxn and maxn > 0 and len(_plan_cache) > maxn:
            _, old = _plan_cache.popitem(last=False)
            pref, plen = old.key, len(old.key)
            for ck in [k for k in _compile_cache if k[:plen] == pref]:
                del _compile_cache[ck]
            evicted += 1
    if evicted:
        prof.count("plan_evictions", evicted)
    return plan


def cached_executable(key: Tuple, make: Callable[[], Callable]) -> _Exec:
    """Get-or-create a jitted executable in the process compile cache
    under its locking discipline (``make()`` builds the ``jax.jit``
    callable on a miss; built outside the lock, first writer wins).
    The serve coalescer keys its batched variants through here so they
    share eviction, locking and the compiles metric."""
    with _cache_lock:
        ex = _compile_cache.get(key)
    if ex is None:
        mine = _Exec(make())
        with _cache_lock:
            ex = _compile_cache.setdefault(key, mine)
        if ex is mine:
            prof.count("compiles")
            log_debug("compiled executable key=%s", hash(key))
    return ex


# mesh object -> its plan-key component. Sorting the axis dict costs
# ~2.5µs per signature; meshes are few and long-lived, so key them by
# identity (the stored mesh reference keeps the id stable). The key
# LEADS with the mesh epoch and the memo entry records the epoch it
# was built under, so after a rebuild_mesh (elastic recovery) a cached
# identity entry for a dead mesh can never resurrect a stale plan key
# — epoch-N plans miss, and evict_stale_plans() reaps them.
_mesh_keys: Dict[int, Tuple[Any, Tuple, int]] = {}


def _mesh_key(mesh) -> Tuple:
    epoch = mesh_mod._EPOCH
    hit = _mesh_keys.get(id(mesh))
    if hit is not None and hit[0] is mesh and hit[2] == epoch:
        return hit[1]
    key = (epoch,) + tuple(sorted(mesh.shape.items()))
    _mesh_keys[id(mesh)] = (mesh, key, epoch)
    return key


def evict_stale_plans() -> int:
    """Drop every plan (and its compiled variants — donation sets,
    serve batches, the degrade rungs) keyed under a mesh epoch older
    than the current one. Called by elastic recovery after
    ``rebuild_mesh``; reuses the LRU eviction's prefix rule, so the
    dead epoch's executables leave the compile cache with their plans
    and nothing can pin a dead mesh's HBM. Returns plans evicted."""
    epoch = mesh_mod._EPOCH
    evicted = 0
    with _cache_lock:
        for pk in [k for k in _plan_cache
                   if isinstance(k, tuple) and len(k) >= 3
                   and k[2] and k[2][0] != epoch]:
            old = _plan_cache.pop(pk)
            pref, plen = old.key, len(old.key)
            for ck in [k for k in _compile_cache if k[:plen] == pref]:
                del _compile_cache[ck]
            evicted += 1
        # orphan executables (explain pre-plans, uncacheable plans):
        # the compile key's third element is the epoch-led mesh item
        # tuple _build_plan wrote
        for ck in [k for k in _compile_cache
                   if isinstance(k, tuple) and len(k) >= 3
                   and isinstance(k[2], tuple) and k[2]
                   and k[2][0] != epoch]:
            del _compile_cache[ck]
    if evicted:
        prof.count("plan_evictions", evicted)
    # the on-disk half (spartan_tpu/persist): purge persisted entries
    # of dead mesh epochs too — without this a process restart would
    # resurrect plans for a mesh that no longer exists. No-op (one
    # flag read) with the store off; never raises.
    persist_mod.evict_stale()
    # the incremental engine's result cache holds device buffers keyed
    # by plan: entries born under the dead epoch go with their plans
    incremental_mod.evict_stale()
    return evicted


def plan_signature(expr: "Expr", mesh=None) -> Tuple[Tuple, "_PlanSigCtx"]:
    """One raw-DAG traversal -> (plan-cache key, signing context) —
    exactly what ``evaluate()`` computes before its cache probe. The
    serve front end signs requests with this at submit time (caller
    thread) so identical-signature requests can coalesce;
    ``plan.arg_order`` indexes into ``ctx.leaves``."""
    if mesh is None:
        mesh = mesh_mod.get_mesh()
    rctx = _PlanSigCtx()
    raw_sig = rctx.of(expr)
    plan_key = (raw_sig, _opt_flags_key(), _mesh_key(mesh))
    return plan_key, rctx


def _leaf_arg(leaf: Expr) -> Any:
    if isinstance(leaf, ValExpr):
        return leaf.value.jax_array
    if isinstance(leaf, ScalarExpr):
        return leaf.pyvalue
    if isinstance(leaf._result, DistArray):
        return leaf._result.jax_array  # cached node signed as a Val leaf
    raise TypeError(f"unknown leaf {leaf!r}")


def _leaf_array(leaf: Expr) -> Optional[DistArray]:
    """The DistArray behind a leaf (None for scalars)."""
    if isinstance(leaf, ValExpr):
        return leaf.value
    if isinstance(leaf, ScalarExpr):
        return None
    return leaf._result if isinstance(leaf._result, DistArray) else None


def _norm_donate(donate: Sequence[Any]) -> List[DistArray]:
    out: List[DistArray] = []
    for d in donate:
        if isinstance(d, DistArray):
            out.append(d)
        elif isinstance(d, ValExpr):
            out.append(d.value)
        elif isinstance(d, Expr) and isinstance(d._result, DistArray):
            out.append(d._result)
        else:
            raise TypeError(
                f"donate expects DistArrays (or evaluated exprs), got "
                f"{type(d).__name__}")
        arr = out[-1]
        if arr._donate_site is None:
            # record the donating call for use-after-donate provenance
            arr._donate_site = _user_site()
    return out


# (flag mutation count, pass-registry size) -> flags key. Every
# plan_signature/evaluate pays this key; re-deriving it walks the
# FLAGS registry ~10 times (≈20µs — measured 10% of a steady-state
# signature), so it is memoized on config.mutation_count(), which any
# flag write bumps. The thread-local degradation rung stays OUT of the
# memo (appended fresh per call).
_opt_key_memo: Tuple[Tuple, Tuple] = ((), ())
_optimize_mod = None  # lazily-bound .optimize (circular import)


def _opt_flags_key() -> Tuple:
    """Everything the optimizer stack reads that the raw signature
    cannot see: a plan is only reusable under the exact pass
    configuration that produced it."""
    global _opt_key_memo, _optimize_mod
    if _optimize_mod is None:  # bind the module once: the per-call
        import importlib  # `from .optimize import ...` machinery was
        _optimize_mod = importlib.import_module(  # ~3µs on the
            ".optimize", __package__)  # per-request signing path
    _PASSES = _optimize_mod._PASSES

    # late-registered passes (smart tiling self-registers on first
    # optimize) must be in the registry BEFORE the key is read, or the
    # very first plan key in a process can never be hit again
    _optimize_mod._ensure_tiling_pass()
    ver = (config_mod.mutation_count(), len(_PASSES))
    memo_ver, key = _opt_key_memo
    if memo_ver != ver:
        # audit_numerics changes the LOWERED program (health probes
        # are compiled in), so audited and plain plans must never
        # share a key; likewise the OOM degradation rung
        # (resilience/degrade.py) forces different tilings/passes, so
        # degraded and normal plans are keyed apart
        # cost calibration re-weights the tiling DP's terms
        # (obs/ledger profile -> tiling_cost._cal_factors), so a
        # calibrated plan must never alias an uncalibrated one: the
        # active profile's fingerprint is part of the key (set_profile
        # writes the fingerprint FLAG, which bumps mutation_count and
        # invalidates this memo)
        cal = ((FLAGS.cost_calibration_fingerprint or "on")
               if FLAGS.cost_calibration else None)
        # the redistribution planner changes BOTH the DP's edge costs
        # and the emitted lowering (explicit schedules vs GSPMD), so
        # planned and implicit plans must never alias
        # carry sharding (expr/loop FLAGS.shard_loop_carries) changes
        # the loop program's layout constraints: sharded-carry and
        # replicated-carry plans must never alias (the chosen layouts
        # are also in LoopExpr._sig — this is the cheap belt)
        key = (tuple(p.name for p in _PASSES if p.enabled()),
               FLAGS.opt_fold_slices, FLAGS.placement,
               FLAGS.tiling_compute_weight, FLAGS.tiling_flop_weight,
               FLAGS.tiling_operand_move_weight,
               FLAGS.tiling_memory_weight,
               bool(FLAGS.audit_numerics), cal,
               bool(FLAGS.redistribution_planner),
               bool(getattr(FLAGS, "shard_loop_carries", False)))
        _opt_key_memo = (ver, key)
    return key + (getattr(degrade_mod._TLS, "rung", None),)


def _arg_order(raw_leaves: List[Expr],
               opt_leaves: List[Expr]) -> Optional[Tuple[int, ...]]:
    """Map each optimized-DAG leaf back to the raw-DAG leaf feeding it.

    The passes either keep leaf objects intact (fusion re-plumbs, never
    re-creates, Val/Scalar leaves) or substitute ``ValExpr(n._result)``
    for a cached node — which the raw traversal already signed as a
    leaf — so identity on the Expr or on its DistArray recovers the raw
    position. Returns None (plan not cacheable) if a pass ever
    introduces a leaf with no raw counterpart."""
    pos: Dict[int, int] = {}
    for i, leaf in enumerate(raw_leaves):
        pos.setdefault(id(leaf), i)
        arr = _leaf_array(leaf)
        if arr is not None:
            pos.setdefault(id(arr), i)
    order = []
    for leaf in opt_leaves:
        j = pos.get(id(leaf))
        if j is None and isinstance(leaf, ValExpr):
            j = pos.get(id(leaf.value))
        if j is None:
            return None
        order.append(j)
    return tuple(order)


def _gather_args(leaves: List[Expr], order: Tuple[int, ...],
                 donated: List[DistArray]
                 ) -> Tuple[List[Any], List[DistArray], List[int]]:
    """Gather executable arguments for one dispatch: the leaf buffers
    in ``order``, plus the donation bookkeeping — which DistArrays are
    released (``darrs``) and which argument positions may alias into
    the outputs (``dpos``). Shared by ``_dispatch`` and the serve
    coalescer (which gathers per request and never donates)."""
    ordered = [leaves[i] for i in order]
    args = [_leaf_arg(leaf) for leaf in ordered]

    darrs: List[DistArray] = []
    dpos: List[int] = []
    stale: List[DistArray] = []
    epoch = mesh_mod._EPOCH
    seen: Dict[int, int] = {}
    for j, leaf in enumerate(ordered):
        arr = _leaf_array(leaf)
        if arr is None:
            continue
        if arr._epoch != epoch:
            # born on a mesh that a rebuild_mesh has since replaced:
            # its buffers (may) live on dead devices. Raise the clear
            # error BEFORE XLA sees the buffer; collect every stale
            # leaf so one rehome pass heals the whole dispatch.
            if not any(arr is s for s in stale):
                stale.append(arr)
            continue
        if arr._donate_next or any(arr is d for d in donated):
            if id(arr) in seen:
                # the same buffer feeds two argument slots: aliasing
                # it into the output is unsafe, so don't donate
                # either position (the wrapper is still invalidated
                # by _wrap_result)
                k = seen[id(arr)]
                if k in dpos:
                    dpos.remove(k)
                continue
            seen[id(arr)] = j
            dpos.append(j)
            if not any(arr is d for d in darrs):
                darrs.append(arr)
    if stale:
        raise mesh_mod.StaleMeshError(
            f"{len(stale)} input DistArray(s) belong to mesh epoch "
            f"{stale[0]._epoch} but the mesh was rebuilt (current "
            f"epoch {epoch}, e.g. after device loss): their buffers "
            "live on the previous mesh. Re-create them from source, "
            "or — if the data is still fetchable (replicated, or a "
            "simulated loss) — call .rehome() / "
            "resilience.elastic.rehome() to migrate them.",
            arrays=stale)
    return args, darrs, dpos


def _wrap_result(expr: Expr, plan: _Plan, out: Any,
                 darrs: List[DistArray], dpos: List[int], mesh,
                 timed: bool = True) -> Any:
    """Dispatch epilogue: wrap the raw outputs into DistArrays, release
    donated buffers, update the plan report's donation view, seed the
    root's result cache, and re-check numerics watchpoints. Shared by
    ``_dispatch`` and the serve coalescer, which passes ``timed=False``
    and times ONE build phase around the whole batch instead of paying
    a span per coalesced request."""
    ctx = prof.phase("build") if timed else _NULL_PHASE
    with ctx:
        if plan.is_tuple:
            result: Any = tuple(DistArray(o, t, mesh)
                                for o, t in zip(out, plan.out_tilings))
        else:
            result = DistArray(out, plan.out_tilings[0], mesh)
        for arr in darrs:
            arr._release_donated()
        if darrs:
            prof.count("donated_dispatches")
        if plan.report is not None:
            don = plan.report.get("donation")
            if don is not None:
                don["last_donated_args"] = sorted(dpos)
                if darrs:
                    don["donated_dispatches"] = (
                        don.get("donated_dispatches", 0) + 1)
        expr._result = result
        _maybe_record_write(expr, result)
    if numerics_mod._WATCHPOINTS:
        # persistent data-health watchpoints (st.watch): re-check each
        # after every dispatch; the empty-list read above is the whole
        # hot-path cost when none are installed
        numerics_mod.poll_watchpoints()
    return result


def _dispatch(expr: Expr, plan: _Plan, leaves: List[Expr],
              order: Tuple[int, ...], donated: List[DistArray],
              mesh) -> Any:
    """Run a plan: gather leaf args, (lazily) fetch the right donation
    variant of the executable, execute, wrap, invalidate donated
    buffers, seed the root's result cache."""
    with prof.phase("build"):
        args, darrs, dpos = _gather_args(leaves, order, donated)
        donate_key = frozenset(dpos)

    def _make() -> Callable:
        if dpos:
            return jax.jit(plan.traced,
                           donate_argnums=tuple(sorted(dpos)))
        if plan.persist_digest is not None \
                and persist_mod.active() is not None:
            # warm-start store active: build the base variant AOT so
            # the SAME compile is both dispatchable and serializable
            # (persistence never pays a second XLA compile)
            return persist_mod.aot_compile(plan.traced, args)
        return jax.jit(plan.traced)

    ex = cached_executable(plan.key + (donate_key,), _make)

    def run() -> Any:
        with warnings.catch_warnings():
            if dpos:
                # backends without aliasing support (XLA:CPU) warn per
                # dispatch; donation there is bookkeeping-only
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
            with launch_guard():
                return ex.jitted(*args)

    fresh = not ex.warm
    phase_name = "compile" if fresh else "dispatch"
    phase_ctx = prof.phase(phase_name)
    with phase_ctx as dsp:
        # dispatch watchdog (obs/numerics.py): a run that exceeds
        # FLAGS.dispatch_timeout_s dumps the in-flight span tree +
        # plan report + last health word to a crash file; a shared
        # no-op (one flag read) when the timeout is 0
        with numerics_mod.watchdog(phase_name, plan.report):
            # chaos seam (resilience/faults.py): an installed plan may
            # raise a synthetic compile/OOM/transient fault or stall
            # here — BEFORE the executable runs, so donated buffers
            # are never half-consumed by an injected failure. One
            # attribute read when no plan is installed.
            if faults_mod._ACTIVE is not None:
                faults_mod.fire(phase_name)
            out = run()
        if dpos:
            dsp.set(donated=sorted(dpos))
    if faults_mod._ACTIVE is not None:
        # chaos `sdc` seam: a matching token armed a silent corruption
        # at fire() above; apply the seeded bit-flip to the result the
        # device "computed". Nothing raises here — detection is the
        # integrity sentinel's job below (or nobody's, when the check
        # is off: that IS the threat model). One attribute read when
        # no plan is installed.
        out = faults_mod.corrupt_output(out)
    ex.warm = True
    if fresh and not dpos and plan.persist_digest is not None:
        # first compile of a persistable plan: serialize + store it
        # (atomic, lease-arbitrated, no-raise — a failed persist never
        # fails the evaluation that produced the plan)
        persist_mod.maybe_store(plan, ex.jitted, mesh)
    if ledger_mod._LEDGER_FLAG._value and plan.report is not None:
        # cost ledger: the measured wall time of this run, next to the
        # plan's predicted tiling-DP cost (one flag read when off)
        ledger_mod.note_dispatch(plan.report.get("plan_key"),
                                 phase_name, phase_ctx.seconds)
    if profile_mod._SAMPLE_FLAG._value > 0:
        # sampled continuous profiling (obs/profile.py): every Nth
        # warm dispatch of a plan gets a device-time attribution, off
        # the result path — the served result above came from the
        # unmodified executable (bit-equal to unsampled). The legacy
        # FLAGS.profile whole-dispatch capture migrated here: one
        # profiling entry point, one flag read per dispatch when off.
        profile_mod.maybe_sample(expr, plan, phase_name,
                                 phase_ctx.seconds, leaves, dpos, mesh)

    if integrity_mod._CHECK_FLAG._value:
        # SDC sentinel (resilience/integrity.py): every Nth run of a
        # plan gets a per-shard checksum + redundant re-execution on a
        # rotated device assignment. Raises IntegrityError (class
        # 'sdc') on disagreement — the corrupt `out` is never wrapped,
        # cached, or returned. One flag read when off.
        integrity_mod.maybe_check(expr, plan, phase_name, out, args,
                                  dpos, mesh)

    if FLAGS.check_determinism and not dpos:  # a donated arg is gone
        out2 = run()
        pairs = zip(out, out2) if plan.is_tuple else [(out, out2)]
        for o1, o2 in pairs:
            if not bool(jnp.all(o1 == o2)):
                raise AssertionError("nondeterministic evaluation detected")

    return _wrap_result(expr, plan, out, darrs, dpos, mesh)


_write_expr_cls = None  # lazily-bound assign.WriteExpr (import cycle)


def _maybe_record_write(expr: Expr, result: Any) -> None:
    """The assign-expr mutation seam: evaluating ``st.assign(arr, idx,
    v)`` (a WriteExpr over a concrete array) is a functional update of
    that array — stamp the result into the source's Lineage exactly
    like ``DistArray.update()`` does, so the incremental engine sees
    the written region as the only delta."""
    global _write_expr_cls
    if _write_expr_cls is None:
        if type(expr).__name__ != "WriteExpr":
            return
        from .assign import WriteExpr

        _write_expr_cls = WriteExpr
    if not isinstance(expr, _write_expr_cls):
        return
    dst = expr.dst
    if (isinstance(dst, ValExpr) and isinstance(dst.value, DistArray)
            and isinstance(result, DistArray)
            and result.shape == dst.value.shape):
        dst.value._record_mutation(result, expr.region)


_engine_mod = None  # lazily-bound resilience.engine (cold path only)


def _handle_failure(exc: Exception, expr: Expr, plan: "_Plan",
                    leaves: List[Expr], order: Tuple[int, ...],
                    donated: List[DistArray], mesh) -> Any:
    """Route a failed dispatch into the resilience policy engine
    (classify -> retry / degrade / fail-fast). The engine import is
    deferred: failures are the cold path."""
    global _engine_mod
    if _engine_mod is None:
        from ..resilience import engine as _engine

        _engine_mod = _engine
    return _engine_mod.handle_failure(exc, expr, plan, leaves, order,
                                      donated, mesh)


def evaluate(expr: Expr, donate: Sequence[Any] = ()) -> DistArray:
    """Evaluate one root.

    Steady state (plan-cache hit): ONE raw-DAG traversal -> arg gather
    -> dispatch — no optimizer rewrites, no cost model, no re-signing.
    Miss: optimize -> signature -> (cached) jit -> run, then the
    complete plan (leaf order, out tilings, compiled executable) is
    memoized under the raw structural signature so the next
    structurally-identical evaluate skips the planner entirely.

    ``donate``: DistArrays (or their evaluated exprs) whose buffers the
    caller releases to this evaluation. The executable is compiled as a
    ``donate_argnums`` variant so XLA may reuse their HBM for the
    outputs, and the donated DistArrays are invalidated — any later use
    raises instead of reading freed memory. ``DistArray.donate()``
    marks an array for the same treatment without threading an
    argument."""
    if expr._result is not None:
        return expr._result

    prof.count("evaluations")
    mesh = mesh_mod.get_mesh()
    donated = _norm_donate(donate)

    with prof.span("evaluate") as esp:
        if FLAGS.trace:  # skip the label f-strings when not recording
            site = expr._site
            esp.set(root=f"{type(expr).__name__}#{expr._id}",
                    site=(f"{site[0]}:{site[1]}" if site else None))
        rctx: Optional[_PlanSigCtx] = None
        plan_key: Optional[Tuple] = None
        if FLAGS.plan_cache:
            with prof.phase("sign"):
                rctx = _PlanSigCtx()
                raw_sig = rctx.of(expr)
                plan_key = (raw_sig, _opt_flags_key(),
                            _mesh_key(mesh))
            if FLAGS.trace:  # key_hash re-hashes the signature tuple:
                esp.set(plan_key=key_hash(plan_key))  # skip when off
            plan = lookup_plan(plan_key)
            if plan is not None:
                prof.count("plan_hits")
                esp.set(cache="hit")
                if plan.governed_rung is not None:
                    # the memory governor judged this plan over-budget
                    # at build time: re-route to its rung (a rung-keyed
                    # plan-cache hit) instead of dispatching a doomed
                    # executable
                    gov = memory_mod.redirect_governed(
                        expr, plan, donated, mesh)
                    if gov is not memory_mod.NOT_HANDLED:
                        return gov
                if incremental_mod._INC_FLAG._value:
                    # delta-aware path (expr/incremental.py): serve
                    # from the result cache + a dirty sub-plan when
                    # lineage proves most tiles clean; NOT_HANDLED
                    # falls through to the ordinary full dispatch
                    inc = incremental_mod.intercept(
                        expr, plan, rctx.leaves, plan.arg_order,
                        donated, mesh)
                    if inc is not incremental_mod.NOT_HANDLED:
                        expr._result = inc
                        return inc
                try:
                    result = _dispatch(expr, plan, rctx.leaves,
                                       plan.arg_order, donated, mesh)
                except Exception as e:
                    result = _handle_failure(e, expr, plan, rctx.leaves,
                                             plan.arg_order, donated,
                                             mesh)
                if incremental_mod._INC_FLAG._value:
                    incremental_mod.note_result(
                        plan, rctx.leaves, plan.arg_order, result,
                        donated, mesh)
                return result
            prof.count("plan_misses")
            esp.set(cache="miss")

        if FLAGS.verify_evaluate:
            # static sanity on the MISS path only (hits above stay
            # dispatch-bound): well-formedness + donation/tiling lints,
            # raising with user-site provenance before anything compiles
            from ..analysis import check as _check

            with prof.phase("verify"):
                _check(expr, donate=donated)

        plan, dag, leaves = _build_plan(expr, mesh, rctx, plan_key)
        if plan is None:
            # the optimizer collapsed the root onto an already-held
            # result (cached sub-DAG frontier covered everything)
            expr._result = dag._result
            return dag._result

        if FLAGS.verify_evaluate and plan.report is not None:
            # static communication audit of the lowered program
            # (analysis/plan_audit.py), miss path only like the DAG
            # check above: findings (full-operand gathers, replicated
            # intermediates) are logged + counted, never raised. A
            # persist-restored verdict (report["audit"] pre-seeded)
            # makes this a dict read — warm restarts don't re-audit.
            from ..analysis import plan_audit as plan_audit_mod

            with prof.phase("audit_plan"):
                plan_audit_mod.audit_on_miss(plan, mesh)

        if plan.report is not None:
            # predictive memory governor (resilience/memory.py): if the
            # modeled peak exceeds the HBM budget, pick the cheapest
            # sufficient ladder rung NOW — before this plan's first
            # (doomed) compile+dispatch. NOT_HANDLED = within budget,
            # no budget known, or governor off.
            gov = memory_mod.maybe_degrade(expr, plan, plan_key,
                                           donated, mesh)
            if gov is not memory_mod.NOT_HANDLED:
                dag._result = gov
                return gov

        # this first run dispatches through the same path a hit takes,
        # with identity arg order over the OPTIMIZED leaves
        try:
            result = _dispatch(expr, plan, leaves, plan.arg_order,
                               donated, mesh)
        except Exception as e:
            result = _handle_failure(e, expr, plan, leaves,
                                     plan.arg_order, donated, mesh)
        dag._result = result
        if incremental_mod._INC_FLAG._value:
            incremental_mod.note_result(plan, leaves, plan.arg_order,
                                        result, donated, mesh)
        return result


def _build_plan(expr: Expr, mesh, rctx: Optional[_PlanSigCtx],
                plan_key: Optional[Tuple]
                ) -> Tuple[Optional[_Plan], Expr, Optional[List[Expr]]]:
    """The plan-cache MISS pipeline, shared by ``evaluate()`` and
    ``st.explain`` (obs/explain.py): optimize -> sign the optimized DAG
    -> build the traced function + output tilings -> memoize the plan
    (with its introspection report) under the raw signature.

    Returns ``(plan, dag, leaves)`` where ``plan.arg_order`` is the
    identity over the OPTIMIZED leaves (the first dispatch's order);
    ``(None, dag, None)`` when the optimized DAG already carries a
    result and there is nothing to compile."""
    from .optimize import optimize

    # warm-start store consult (spartan_tpu/persist): BEFORE the
    # optimizer runs, probe the on-disk store for this raw signature +
    # environment fingerprint. A hit skips the XLA compile below (the
    # deserialized executable is pre-seeded into the compile cache); a
    # rejected entry (corrupt / stale / foreign / io fault) degrades
    # to this normal recompile with the reason on the plan report.
    # One flag read when the store is off.
    p_entry = p_digest = p_reason = None
    if rctx is not None and plan_key is not None:
        p_entry, p_digest, p_reason = persist_mod.lookup(plan_key, mesh)

    passes_report: List[Dict[str, Any]] = []
    with prof.phase("optimize"):
        dag = optimize(expr, report=passes_report)
    if dag._result is not None:
        return None, dag, None

    degrade_rung = getattr(degrade_mod._TLS, "rung", None)
    if degrade_rung in ("finer_tiling", "fusion_off"):
        # OOM degradation (resilience/degrade.py): override the cost
        # model's choices with the finest divisible shardings — the
        # dag here is a private clone, and the forced markers land in
        # the compile signature below
        degrade_mod.force_finer(dag, mesh)

    with prof.phase("sign"):
        ctx = _SigCtx()
        root_sig = ctx.of(dag)
    leaves = ctx.leaves
    is_tuple = isinstance(dag, TupleExpr)
    if is_tuple:
        out_tilings = dag.out_tilings()
    else:
        out_tilings = (tiling_mod.sanitize(dag.out_tiling(), dag.shape,
                                           mesh),)
    # the audit flag is captured at plan-build time and keyed into the
    # compile signature: an audited trace compiles health probes in,
    # and must never alias a probe-free executable (or vice versa).
    # The degradation rung is keyed the same way: a fusion-off or
    # finer-tiling replan must never alias the normal executable.
    audit = bool(FLAGS.audit_numerics)
    # the mesh component leads with the epoch (elastic recovery): a
    # plan compiled for a dead mesh must never alias a post-rebuild
    # executable of the same structure, and evict_stale_plans reaps
    # old-epoch entries by this element. The redistribution-planner
    # flag is keyed like audit: a planner-on trace emits explicit
    # collective schedules where the planner-off trace emits
    # with_sharding_constraint, for the same structural signature.
    key = (root_sig, tuple(t.axes for t in out_tilings),
           (mesh_mod._EPOCH,) + tuple(sorted(mesh.shape.items())),
           audit, degrade_rung, redistribute_mod.planner_on())

    leaf_ids = tuple(l._id for l in leaves)
    out_shardings = tuple(t.sharding(mesh) for t in out_tilings)

    def traced(*args: Any) -> Any:
        env: Dict[int, Any] = dict(zip(leaf_ids, args))
        # naming session (obs/profile.py): every named_scope emitted
        # under this trace carries the node's _sig digest, so device
        # profiler captures of THIS executable join back to expr nodes
        # (one memoized signing traversal; trace time only; no-op when
        # FLAGS.trace_annotations is off)
        with profile_mod.naming_session():
            if audit:
                # probe session: leaves first (a poisoned input names
                # the LEAF, not its first consumer), then every node as
                # Expr.lower emits it — attach order is topological
                with numerics_mod.probe_session():
                    for leaf, arg in zip(leaves, args):
                        numerics_mod.probe(leaf, arg, kind="leaf")
                    out = dag.lower(env)
            else:
                out = dag.lower(env)
        # a constraint (not jit out_shardings) so GSPMD propagation can
        # negotiate ops like reverse that hard-fail on output overrides.
        # Resolved against the ambient mesh at TRACE time: a retrace
        # under a same-shape substitute assignment (integrity's rotated
        # redundant execution pins one via use_mesh) must bind its
        # constraints to that assignment — XLA rejects programs mixing
        # two device orders. Normal dispatch traces under the build
        # mesh, where this is exactly the prebuilt tuple.
        osh = out_shardings
        amb = mesh_mod.get_mesh()
        if amb is not mesh:
            osh = tuple(t.sharding(amb) for t in out_tilings)
        if is_tuple:
            return tuple(
                jax.lax.with_sharding_constraint(o, s)
                for o, s in zip(out, osh))
        return jax.lax.with_sharding_constraint(out, osh[0])

    identity = tuple(range(len(leaves)))
    raw_order: Optional[Tuple[int, ...]] = None
    if rctx is not None and plan_key is not None:
        raw_order = _arg_order(rctx.leaves, leaves)
    report = build_plan_report(expr, dag, leaves, plan_key,
                               passes_report, out_tilings, raw_order)
    with prof.phase("memory_model"):
        # the predictive memory governor's input: the modeled per-chip
        # peak of THIS plan (resilience/memory.py), on the miss path
        # only — one DAG walk next to an optimizer run + XLA compile
        report["memory"] = memory_mod.estimate_report(dag, out_tilings,
                                                      mesh)
    plan = _Plan(key, traced, out_tilings, is_tuple, identity, report)

    if p_digest is not None or p_reason is not None:
        # persist outcome onto the report (st.explain names disk-hit
        # vs compile; the serve worker stamps it onto flight records)
        rec: Dict[str, Any] = {"source": "compile", "digest": p_digest}
        if p_reason:
            rec["reason"] = p_reason
        if p_entry is not None:
            if p_entry.matches(out_tilings, is_tuple, raw_order,
                               len(raw_order or ())):
                # pre-seed the compile cache with the restored AOT
                # executable under the base (no-donation) variant key:
                # the dispatch below finds it warm — ZERO recompiles.
                # A call-time aval/sharding mismatch inside the guard
                # degrades to a fresh jit of the traced fn just built.
                ex = _Exec(persist_mod.guarded_callable(
                    p_entry, lambda: jax.jit(traced)))
                ex.warm = True
                with _cache_lock:
                    _compile_cache.setdefault(key + (frozenset(),), ex)
                persist_mod.note_hit()
                rec = {"source": "disk", "digest": p_digest}
                if getattr(p_entry, "audit", None) is not None:
                    # the audit verdict persisted next to the
                    # executable: a warm restart under
                    # FLAGS.verify_evaluate reads it instead of
                    # re-lowering + re-compiling for the audit
                    report["audit"] = p_entry.audit
            else:
                persist_mod.reject_entry(p_entry, "meta_mismatch")
                rec["reason"] = "meta_mismatch"
        if raw_order is not None and p_digest is not None:
            plan.persist_digest = p_digest
        report["persist"] = rec
        persist_mod.note_build(rec["source"], p_digest,
                               rec.get("reason"))

    ledger_plan = plan
    if rctx is not None and plan_key is not None:
        if raw_order is not None:
            stored = _Plan(key, traced, out_tilings, is_tuple, raw_order,
                           report)
            # hits dispatch the stored plan: it must carry the same
            # on-disk address so a later recompile re-persists
            stored.persist_digest = plan.persist_digest
            # the winner of a store race is what later lookups (and
            # st.ledger's validation) see — ledger the same object
            ledger_plan = store_plan(plan_key, stored)
        else:
            prof.count("plan_uncacheable")
    # cost ledger (obs/ledger.py): record this plan's predictions
    # (DP cost + per-class components, modeled peak HBM) so measured
    # dispatch times land next to them. Miss-path only.
    ledger_mod.note_plan(ledger_plan)
    # autotune hot-plan templates (obs/monitor.py): under the
    # re-calibration daemon, remember a result-free clone of this
    # miss's raw DAG keyed by its ledger digest so drift-triggered
    # replans run off the hot path. One flag read when the daemon is
    # off — and miss-path only, like the ledger hook above.
    if monitor_mod._AUTOTUNE_FLAG._value:
        monitor_mod.note_plan_built(ledger_plan, expr)
    # the auditor's digest -> node join table, computed LAST: the
    # memory/ledger walks above stamp tiling decisions onto nodes, and
    # the digest must hash the same node state the trace-time naming
    # session will (obs/explain.scope_digest_table)
    report["scope_digests"] = scope_digest_table(dag)
    return plan, dag, leaves


_eval_shape_cache: Dict[Tuple, Any] = {}


def eval_shape_of(fn: Callable, *inputs: Expr, cache_key: Any = None,
                  **kw) -> jax.ShapeDtypeStruct:
    """Exact result shape/dtype via abstract evaluation (no FLOPs).

    With ``cache_key`` (a hashable identity for ``fn``), results are
    memoized on input shapes/dtypes — iterative drivers rebuild
    identical DAG structures every step and abstract evaluation is the
    dominant Python-side cost."""
    key = None
    if cache_key is not None:
        key = (cache_key,
               tuple((i.shape, str(i.dtype),
                      i.weak_kind if isinstance(i, ScalarExpr) else None)
                     for i in inputs))
        hit = _eval_shape_cache.get(key)
        if hit is not None:
            return hit
    specs = []
    for i in inputs:
        if isinstance(i, ScalarExpr):
            specs.append(i.pyvalue)
        else:
            specs.append(jax.ShapeDtypeStruct(i.shape, i.dtype))
    out = jax.eval_shape(fn, *specs, **kw)
    if key is not None and len(_eval_shape_cache) < 4096:
        _eval_shape_cache[key] = out
    return out


# Bottom-bound seam (the persist_mod pattern): the incremental engine
# (expr/incremental.py) needs every Expr type above to exist, and its
# own expr imports are lazy, so binding it here closes the cycle. The
# evaluate() paths read incremental_mod._INC_FLAG._value — one
# attribute-chain read when FLAGS.incremental is off — and
# benchmarks/incremental.py swaps this module binding for its
# null-shim overhead arm (the warm_start.py persist_mod pattern).
from . import incremental as incremental_mod  # noqa: E402
