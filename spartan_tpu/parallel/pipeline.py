"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh
axis.

The reference has no pipeline parallelism (SURVEY.md §2.6 marks PP
"optional later via shard_map stages + collective_permute"); this module
provides exactly that TPU-native construction. Stages are sharded over a
mesh axis (stage s's parameters live on device s); microbatches enter at
stage 0 and ride the ICI ring via ``ppermute`` one hop per tick, so at
steady state every stage computes concurrently — the classic GPipe
schedule with ``n_micro + n_stages - 1`` ticks.

Everything is a single jitted ``shard_map`` program: the driver-side
loop of the reference's world (ship tile, compute, ship on) collapses
into a ``lax.fori_loop`` of compute + collective_permute.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_mod


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   microbatches: jax.Array,
                   *,
                   mesh=None,
                   axis: str = mesh_mod.AXIS_ROW) -> jax.Array:
    """Run ``n_micro`` microbatches through a pipeline of stages.

    ``stage_fn(params_s, act) -> act`` is one stage's computation; it
    must preserve the activation shape (classic homogeneous-stage
    pipeline). ``stage_params`` is a pytree whose leaves have a leading
    ``n_stages`` axis (sharded over ``axis``); ``microbatches`` is
    ``(n_micro, mb, ...)``. Returns ``(n_micro, mb, ...)`` outputs.

    Grad-friendly: ``jax.grad`` through the returned value
    differentiates the whole pipeline (ppermute is linear).
    ``stage_fn`` is applied to every stage's carry on every tick
    (bubble values included, seeded from the first microbatch), so it
    should be finite on activation-shaped data.
    """
    mesh = mesh or mesh_mod.get_mesh()
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    if n_micro < 1:
        raise ValueError("need at least one microbatch")
    ticks = n_micro + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    from jax import shard_map

    params_spec = jax.tree.map(lambda _: P(axis), stage_params)

    def shard_fn(params, x):
        # params leaves: (1, ...) — this stage's slice; x: full batch
        # (microbatches replicated: cheap relative to weights, and stage
        # 0 needs random access into them)
        params = jax.tree.map(lambda p: p[0], params)
        stage = lax.axis_index(axis)
        # warm-up activations are real data, not zeros: stage_fn is
        # applied to every stage's carry each tick (masking selects the
        # emitted values), and a fn that is non-finite at zeros would
        # otherwise poison grads through 0*NaN cotangents
        act0 = x[0]
        out0 = jnp.zeros_like(x)

        def tick(t, carry):
            act, out = carry
            # stage 0 ingests microbatch t (while available)
            inj = x[jnp.minimum(t, n_micro - 1)]
            act = jnp.where(jnp.logical_and(stage == 0, t < n_micro),
                            inj, act)
            act = stage_fn(params, act)
            # last stage emits the microbatch that entered at t-(S-1)
            m = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, m >= 0)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(emit, act, out[jnp.maximum(m, 0)]),
                jnp.maximum(m, 0), 0)
            act = lax.ppermute(act, axis, fwd)
            return act, out

        _, out = lax.fori_loop(0, ticks, tick, (act0, out0))
        # outputs live on the last stage; share them with everyone
        keep = (stage == n_stages - 1).astype(out.dtype)
        return lax.psum(out * keep, axis)

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(params_spec, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, microbatches)


def pipeline_loss(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
                  stage_params: Any,
                  microbatches: jax.Array,
                  targets: jax.Array,
                  *,
                  mesh=None,
                  axis: str = mesh_mod.AXIS_ROW) -> jax.Array:
    """Mean loss over microbatches run through the pipeline."""
    out = pipeline_apply(stage_fn, stage_params, microbatches,
                         mesh=mesh, axis=axis)
    return jnp.mean(jax.vmap(loss_fn)(out, targets))


def pipeline_grad(stage_fn, loss_fn, stage_params, microbatches, targets,
                  *, mesh=None, axis: str = mesh_mod.AXIS_ROW):
    """(loss, grads) for one pipelined training step — grads have the
    same stage-sharded structure as ``stage_params``."""
    return jax.value_and_grad(
        lambda p: pipeline_loss(stage_fn, loss_fn, p, microbatches,
                                targets, mesh=mesh, axis=axis)
    )(stage_params)
