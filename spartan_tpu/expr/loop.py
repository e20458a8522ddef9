"""On-device iteration: ``loop(n, body_fn, *init)`` -> ``lax.fori_loop``.

The reference's iterative drivers (k-means SURVEY.md §3.4, PageRank,
regression SGD) crossed the driver<->worker boundary every iteration —
eval fan-out plus a glom per step set a hard per-iteration latency floor.
This framework already collapses one iteration into one XLA program; a
``LoopExpr`` collapses the *whole driver loop*: the body DAG is traced
once and iterated by ``lax.fori_loop`` entirely on device, so an N-step
k-means/SGD/PageRank run is ONE dispatch and ONE fetch regardless of N.

The iteration count is a traced scalar (``ScalarExpr``), so changing
``num_iter`` between runs does not recompile.

No reference counterpart exists (this is capability the RPC design could
not express); it is the TPU-native answer to SURVEY.md §3.4's
"per-iteration latency floor" note.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import functools

import jax.numpy as jnp
import numpy as np

from ..array import tiling as tiling_mod
from ..array.tiling import Tiling
from ..obs import numerics as obs_numerics
from ..obs import trace as obs_trace
from ..utils import profiling as prof
from ..utils.config import FLAGS
from .base import Expr, ValExpr, as_expr

FLAGS.define_bool(
    "shard_loop_carries", False,
    "Shard large replicated loop carries across the mesh instead of "
    "keeping one full copy per chip (the cross-replica weight-update "
    "sharding construction): a carry whose init is replicated and at "
    "least shard_carry_min_bytes large is constrained to the default "
    "divisible tiling for the whole loop — inits are re-tiled on "
    "entry, every iteration's outputs keep the sharded layout, and "
    "the final carries come back sharded. Opt-in: reduction orders "
    "inside non-elementwise bodies may change; keyed into BOTH the "
    "plan and compile cache keys so sharded and replicated loop "
    "programs never alias.")
FLAGS.define_int(
    "shard_carry_min_bytes", 1 << 16,
    "Minimum carry size (bytes) for FLAGS.shard_loop_carries to "
    "shard it: tiny carries (scalars, small stats) stay replicated — "
    "resharding them costs more than their residency.")


def _carry_shard_tiling(ini: "Expr", shape: Tuple[int, ...],
                        dtype: Any) -> Optional[Tiling]:
    """The sharded layout a loop carry gets under
    ``FLAGS.shard_loop_carries``, or None to keep the init's own
    tiling: only replicated, large-enough carries with a divisible
    axis are sharded (the default_tiling rule — largest divisible
    axis onto the mesh row axis)."""
    if not FLAGS.shard_loop_carries or not shape:
        return None
    try:
        t0 = ini.out_tiling()
    except Exception:  # noqa: BLE001 - advisory: keep the default
        return None
    if any(a is not None for a in t0.axes):
        return None  # already sharded: the user/DP chose a layout
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if nbytes < FLAGS.shard_carry_min_bytes:
        return None
    t = tiling_mod.default_tiling(shape)
    if all(a is None for a in t.axes):
        return None  # nothing divides: replication is all there is
    return t


class CarryExpr(Expr):
    """Symbolic leaf bound to the loop-carried value inside the body DAG.

    Never evaluated on its own: ``LoopExpr._lower`` seeds its id into the
    body environment with the ``fori_loop`` carry."""

    def __init__(self, shape: Tuple[int, ...], dtype: Any, slot: int,
                 tiling: Tiling, sharded: bool = False):
        super().__init__(shape, dtype)
        self.slot = slot
        self._tiling = tiling
        # True when FLAGS.shard_loop_carries overrode a replicated
        # init: the loop constrains this carry to _tiling on entry
        # and on every iteration's output
        self.sharded = sharded

    def children(self) -> Tuple[Expr, ...]:
        return ()

    def replace_children(self, new_children: Tuple[Expr, ...]) -> "CarryExpr":
        return self

    def _lower(self, env: Dict[int, Any]) -> Any:
        raise RuntimeError(
            "loop carry used outside its loop body (exprs built from a "
            "loop body's carry cannot escape the body function)")

    def _sig(self, ctx) -> Tuple:
        # de Bruijn level relative to the enclosing loop binders (frames
        # pushed by LoopExpr._sig): nested loops with same-shaped carries
        # must NOT collide in the structural compile cache
        frames = getattr(ctx, "_loop_binders", ())
        for level, frame in enumerate(reversed(frames)):
            if self._id in frame:
                return ("carry", level, self.slot, self._shape,
                        str(self._dtype))
        # escaped carry: unique per instance so no cache entry can alias
        # it (lowering raises the escape error regardless)
        return ("carry-escaped", self._id)

    def _default_tiling(self) -> Tiling:
        return self._tiling


class LoopIndexExpr(CarryExpr):
    """Symbolic leaf bound to the fori_loop induction variable."""

    def __init__(self) -> None:
        super().__init__((), np.int32, -1, tiling_mod.replicated(0))


class LoopExpr(Expr):
    """Iterates a body DAG ``n`` times on device. Internal node — always
    consumed through ``LoopItemExpr`` projections (multi-carry loops
    evaluate all carries in one program, like ``TupleExpr``)."""

    def __init__(self, n_expr: Expr, init: Tuple[Expr, ...],
                 carries: Tuple[CarryExpr, ...],
                 body_roots: Tuple[Expr, ...],
                 index_expr: Optional[LoopIndexExpr],
                 health: bool = False, early_exit: bool = False,
                 stall_tol: float = 0.0):
        if len(init) != len(body_roots):
            raise ValueError(
                f"loop body returned {len(body_roots)} values for "
                f"{len(init)} carried inputs")
        for i, (ini, b) in enumerate(zip(init, body_roots)):
            if b.shape != ini.shape:
                raise ValueError(
                    f"loop carry {i} must keep its shape: init "
                    f"{ini.shape}, body returned {b.shape}")
        self.n_expr = n_expr
        self.init = init
        self.carries = carries
        self.body_roots = body_roots
        self.index_expr = index_expr
        self.health = bool(health or early_exit)
        self.early_exit = bool(early_exit)
        self.stall_tol = float(stall_tol)
        super().__init__((), body_roots[0].dtype)

    def children(self) -> Tuple[Expr, ...]:
        return (self.n_expr,) + self.init + self.body_roots

    def replace_children(self, new_children: Tuple[Expr, ...]) -> "LoopExpr":
        k = len(self.init)
        return LoopExpr(new_children[0], tuple(new_children[1:1 + k]),
                        self.carries, tuple(new_children[1 + k:]),
                        self.index_expr, self.health, self.early_exit,
                        self.stall_tol)

    def _carry_norm(self, vals: Tuple[Any, ...]) -> Any:
        # inf-norm, not L2: squaring overflows f32 for |carry| > ~2e19
        # and would flag healthy large-magnitude carries as divergence.
        # XLA's reduce-max does NOT reliably propagate NaN, so NaN is
        # detected explicitly and forced into the result.
        m = jnp.zeros((), jnp.float32)
        nan = jnp.zeros((), jnp.bool_)
        for v in vals:
            vf = jnp.asarray(v, jnp.float32)
            m = jnp.maximum(m, jnp.max(jnp.abs(vf)))
            nan = nan | jnp.isnan(vf).any()
        return jnp.where(nan, jnp.asarray(jnp.nan, jnp.float32), m)

    def _lower(self, env: Dict[int, Any]) -> Any:
        import jax
        from jax import lax

        n = self.n_expr.lower(env)
        # cast inits to the body's (stable) carry dtypes so the fori_loop
        # carry is type-invariant even when init was e.g. a Python int
        inits = tuple(
            jnp.asarray(i.lower(env), b.dtype)
            for i, b in zip(self.init, self.body_roots))
        sharded = any(c.sharded for c in self.carries)
        if sharded:
            # cross-replica carry sharding (FLAGS.shard_loop_carries):
            # re-tile the replicated inits once on entry; the matching
            # constraint on every body output below keeps the carry
            # sharded across iterations, so the loop's resident state
            # is 1/p per chip instead of one full copy per chip
            from ..parallel import redistribute as redist_mod

            inits = tuple(
                redist_mod.constrain(v, ce._tiling) if ce.sharded else v
                for v, ce in zip(inits, self.carries))
        trace_steps = FLAGS.trace_loop_steps
        label = f"loop#{self._id}"

        def body_core(i: Any, carry: Tuple[Any, ...]) -> Tuple[Any, ...]:
            benv = dict(env)
            if self.index_expr is not None:
                benv[self.index_expr._id] = i
            for ce, cv in zip(self.carries, carry):
                benv[ce._id] = cv
            if trace_steps:
                # per-iteration host visibility: a debug callback marks
                # the host clock each step; obs/trace turns consecutive
                # marks into "loop_step" spans with REAL per-step times
                # (the flag is part of _sig, so toggling recompiles)
                jax.debug.callback(
                    functools.partial(obs_trace.record_loop_step,
                                      label), i)
            with obs_trace.named_scope("st_loop_body"):
                out = tuple(b.lower(benv) for b in self.body_roots)
            if sharded:
                from ..parallel import redistribute as redist_mod

                out = tuple(
                    redist_mod.constrain(o, ce._tiling)
                    if ce.sharded else o
                    for o, ce in zip(out, self.carries))
            return out

        def health_of(i: Any, old: Tuple[Any, ...],
                      new: Tuple[Any, ...]) -> Tuple[Any, Any]:
            # carry norm + update norm in f32: ||new|| goes NaN/Inf the
            # iteration the carry diverges; ||new - old|| stalls toward
            # 0 as an iterative driver converges. One callback per step
            # feeds the host series (obs/numerics.record_loop_health).
            norm = self._carry_norm(new)
            un = self._carry_norm(tuple(
                jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)
                for a, b in zip(new, old)))
            jax.debug.callback(
                functools.partial(obs_numerics.record_loop_health,
                                  label), i, norm, un)
            return norm, un

        if not self.early_exit:
            def body(i: Any, carry: Tuple[Any, ...]) -> Tuple[Any, ...]:
                new = body_core(i, carry)
                if self.health:
                    health_of(i, carry, new)
                return new

            return lax.fori_loop(0, n, body, inits)

        # early-exit: a while_loop whose condition reads the PREVIOUS
        # iteration's health — stop when the carry went non-finite
        # (divergence) or, with stall_tol > 0, when the update norm
        # dropped below it (convergence/stall). The health series
        # records every executed step either way.
        f32 = jnp.float32
        stall = self.stall_tol

        def w_cond(state: Tuple[Any, ...]) -> Any:
            i, _carry, norm, un = state
            ok = i < n
            alive = jnp.isfinite(norm) & jnp.isfinite(un)
            if stall > 0:
                alive = alive & (un >= jnp.asarray(stall, f32))
            return ok & ((i == 0) | alive)

        def w_body(state: Tuple[Any, ...]) -> Tuple[Any, ...]:
            i, carry, _norm, _un = state
            new = body_core(i, carry)
            norm, un = health_of(i, carry, new)
            return (i + 1, new, norm, un)

        state0 = (jnp.zeros((), jnp.int32), inits,
                  jnp.asarray(jnp.inf, f32), jnp.asarray(jnp.inf, f32))
        return lax.while_loop(w_cond, w_body, state0)[1]

    def _sig(self, ctx) -> Tuple:
        # the carry-sharding layout is structural: a loop whose carry
        # is constrained to a sharded tiling compiles a different
        # program than the replicated one, so the chosen layouts are
        # part of the signature (None when carry sharding is off) —
        # plan AND compile keys separate automatically
        shard = tuple(c._tiling.axes if c.sharded else None
                      for c in self.carries) \
            if any(c.sharded for c in self.carries) else None
        head = (("loop", bool(FLAGS.trace_loop_steps), self.health,
                 self.early_exit, self.stall_tol, shard,
                 ctx.of(self.n_expr))
                + tuple(ctx.of(i) for i in self.init))
        # bind the carries for the body traversal (see CarryExpr._sig)
        frames = getattr(ctx, "_loop_binders", None)
        if frames is None:
            frames = []
            ctx._loop_binders = frames
        frame = {c._id: c.slot for c in self.carries}
        if self.index_expr is not None:
            frame[self.index_expr._id] = -1
        frames.append(frame)
        try:
            body = tuple(ctx.of(b) for b in self.body_roots)
        finally:
            frames.pop()
        return head + body

    def _default_tiling(self) -> Tiling:
        return tiling_mod.replicated(0)


class LoopItemExpr(Expr):
    """Projection of one carried value out of a ``LoopExpr``. Forcing any
    item of a multi-carry loop evaluates ALL sibling items through one
    ``TupleExpr`` program (one dispatch, one loop execution) and seeds
    every sibling's result cache."""

    def __init__(self, loop: LoopExpr, idx: int):
        self.loop = loop
        self.idx = idx
        b = loop.body_roots[idx]
        super().__init__(b.shape, b.dtype)

    def evaluate(self, donate=()):
        if self._result is not None:
            return self._result
        # loop-carry donation: with donate_init the init buffers feed
        # only this loop and die with it — release them to the dispatch
        donate = tuple(donate) + getattr(self.loop, "_donate_init", ())
        n = self.loop.n_expr
        static_n = getattr(n, "pyvalue", None)
        label = f"loop#{self.loop._id}"
        if FLAGS.trace_loop_steps:
            obs_trace.loop_steps_begin(label)  # anchor step 0's span
        if self.loop.health:
            obs_numerics.loop_health_begin(label)  # fresh series
        if isinstance(static_n, int):  # see loop()'s docstring
            prof.count("loop_iterations", static_n)
        with prof.span("loop", loop=label, n=static_n,
                       carries=len(self.loop.init)):
            siblings = getattr(self.loop, "_items", None)
            # identity check, NOT `in`: Expr.__eq__ builds comparisons
            if (siblings and len(siblings) > 1
                    and any(s is self for s in siblings)):
                from .base import TupleExpr, evaluate as eval_root

                results = eval_root(TupleExpr(siblings), donate=donate)
                for item, res in zip(siblings, results):
                    item._result = res
                return self._result
            from .base import evaluate as eval_root

            return eval_root(self, donate=donate)

    force = evaluate

    def children(self) -> Tuple[Expr, ...]:
        return (self.loop,)

    def replace_children(self, new_children: Tuple[Expr, ...]
                         ) -> "LoopItemExpr":
        return LoopItemExpr(new_children[0], self.idx)

    def _lower(self, env: Dict[int, Any]) -> Any:
        return self.loop.lower(env)[self.idx]

    def _sig(self, ctx) -> Tuple:
        return ("loopitem", self.idx, ctx.of(self.loop))

    def _default_tiling(self) -> Tiling:
        carry = self.loop.carries[self.idx]
        if carry.sharded:
            # the loop constrains this carry's outputs to the sharded
            # layout every iteration — declare it so the plan's out
            # tilings (and anything consuming the result) agree
            return carry._tiling
        return self.loop.body_roots[self.idx].out_tiling()


def loop(n_iters: Any, body_fn: Callable, *init: Any,
         with_index: bool = False, donate_init: bool = False,
         health: bool = False, early_exit: bool = False,
         stall_tol: float = 0.0, checkpoint_every: int = 0,
         checkpoint_path: Optional[str] = None,
         resume: Optional[str] = None):
    """Iterate ``body_fn`` ``n_iters`` times entirely on device.

    ``body_fn`` receives one lazy expr per carried value (prepended with
    the iteration-index expr when ``with_index``) and returns the same
    number of exprs with unchanged shapes. Returns one lazy expr per
    carried value (a single expr for a single carry). Example::

        w = st.loop(100, lambda w: w - 0.1 * grad(x, y, w), w0)

    The whole loop is one XLA program: no per-iteration dispatch, no
    per-iteration fetch (contrast SURVEY.md §3.4's per-iteration
    driver<->worker round trips in the reference).

    ``donate_init``: release the init DistArrays' buffers to the loop
    dispatch (``evaluate(donate=...)`` — the carry re-feed overwrites
    them anyway, so XLA may alias their HBM for the outputs). The
    donated init arrays are invalidated when the loop is forced;
    re-using them afterwards raises.

    ``health``: emit a per-iteration carry-norm / update-norm health
    series through the numerics sentinel (one ``jax.debug.callback``
    per step; read it back via ``st.obs.numerics.loop_health()``) with
    divergence counting in the metrics registry. ``early_exit``
    (implies ``health``) lowers to a ``while_loop`` that stops when
    the carry goes non-finite — a diverged k-means/SGD run ends at the
    iteration it diverged instead of burning the remaining steps —
    or, with ``stall_tol > 0``, when the update norm drops below the
    tolerance (convergence). All three are part of the loop's
    structural signature, so toggling recompiles.

    Each time a result of ``loop`` is forced (``evaluate``/``glom``)
    and its ``n_iters`` is a Python int, ``n_iters`` is added to the
    metrics registry's ``loop_iterations`` counter (``evaluations``
    counts the dispatch); a loop lowered inside a larger expression
    adds nothing. An ``early_exit`` loop adds its ``n_iters`` too: the
    most it may run, not the iterations it ran.

    ``checkpoint_every`` / ``checkpoint_path`` / ``resume``
    (resilience/loop_ckpt.py): split the loop into segments of
    ``checkpoint_every`` iterations, atomically snapshotting the
    carries to ``checkpoint_path`` after each segment and restoring
    the last good snapshot if a segment fails; ``resume=path`` picks
    up a killed run at its last snapshot and reproduces the
    uninterrupted final carry bit-for-bit. Checkpointed loops run
    eagerly (segments must dispatch to snapshot between them) and
    return the final carries as ``Val`` exprs — ``.glom()`` /
    ``.evaluate()`` work unchanged. Composes with ``health`` /
    ``early_exit`` (an early-exited segment ends the loop at that
    snapshot) and with the in-evaluate retry/degradation policy
    engine (docs/RESILIENCE.md).
    """
    if checkpoint_every or resume is not None:
        from ..resilience.loop_ckpt import checkpointed_loop

        return checkpointed_loop(
            n_iters, body_fn, init, with_index=with_index,
            donate_init=donate_init, health=health,
            early_exit=early_exit, stall_tol=stall_tol,
            every=int(checkpoint_every or 0), path=checkpoint_path,
            resume=resume)
    init_exprs = tuple(as_expr(i) for i in init)
    if not init_exprs:
        raise ValueError("loop needs at least one carried value")
    index_expr = LoopIndexExpr() if with_index else None

    def build(carry_specs: Tuple[Tuple[Tuple[int, ...], Any], ...]):
        carries = []
        for slot, ((shape, dtype), ini) in enumerate(
                zip(carry_specs, init_exprs)):
            shard_t = _carry_shard_tiling(ini, shape, dtype)
            carries.append(CarryExpr(
                shape, dtype, slot,
                shard_t if shard_t is not None else ini.out_tiling(),
                sharded=shard_t is not None))
        carries = tuple(carries)
        args = ((index_expr,) if with_index else ()) + carries
        out = body_fn(*args)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return carries, tuple(as_expr(o) for o in out)

    specs = tuple((i.shape, i.dtype) for i in init_exprs)
    carries, body_roots = build(specs)
    out_specs = tuple((b.shape, b.dtype) for b in body_roots)
    if len(out_specs) == len(specs) and out_specs != specs:
        # dtype promotion in the body (e.g. int init, float update):
        # rebuild with the promoted carry dtypes and require a fixpoint
        carries, body_roots = build(out_specs)
        specs2 = tuple((b.shape, b.dtype) for b in body_roots)
        if specs2 != out_specs:
            raise TypeError(
                f"loop body dtypes do not stabilize: {specs} -> "
                f"{out_specs} -> {specs2}")

    le = LoopExpr(as_expr(n_iters), init_exprs, carries, body_roots,
                  index_expr, health=health, early_exit=early_exit,
                  stall_tol=stall_tol)
    items = tuple(LoopItemExpr(le, i) for i in range(len(init_exprs)))
    le._items = items  # sibling set for one-program multi-carry forcing
    if donate_init:
        le._donate_init = tuple(
            i.value for i in init_exprs if isinstance(i, ValExpr))
    return items[0] if len(items) == 1 else items
