"""Distributed sample sort (1-D and batched axis sort, any length).

Parity with the reference's sampling-based distributed sort
(``[U] spartan/expr/sort.py``, SURVEY.md §2.3 misc ops). The reference
sampled per-tile splitters, shuffled elements to the worker owning
their splitter range, and locally sorted. TPU-native redesign: the
whole algorithm is ONE traced ``shard_map`` program with static shapes
(XLA-friendly — no data-dependent sizes anywhere):

1. local two-key ``lax.sort`` per shard — ``(is_padding, value)`` so
   ragged tails (``n % p != 0`` pads to the next multiple, a validity
   channel rides the whole pipeline) sort behind every real element;
2. ``s`` evenly-spaced samples from the shard's VALID prefix,
   ``all_gather`` + sort -> ``p - 1`` global splitters;
3. bucket exchange: each shard scatters its sorted elements into a
   fixed ``(p, m)`` send buffer (bucket run *j* goes to row *j*,
   cannot overflow: a shard holds only ``m`` slots) with a parallel
   validity buffer, one ``all_to_all`` for each;
4. local merge: two-key ``lax.sort`` (validity, value) over the
   received ``p * m`` slots — real elements first, in order — giving
   this device the full contents of its splitter range (capacity-safe
   under ANY skew: a bucket can never exceed ``p * m``);
5. rebalance to even row shards: VALID bucket sizes are shared with
   one ``all_gather``; each device cuts the overlap of its bucket's
   global rank range with every output shard's ``[j*m, (j+1)*m)``
   range, exchanges the chunks with a second ``all_to_all``, and
   scatters into its ``m``-element output shard. Globally the valid
   elements occupy ranks ``[0, n)`` so the caller just slices the
   padding back off.

Batched axis sort (:func:`sample_sort_axis`): the same kernel
``jax.vmap``-ed over the unsharded leading axes — an N-d array sharded
ALONG its sort axis sorts without ever gathering that axis (the traced
``jnp.sort`` fallback would all-gather it).

Bandwidth: both exchanges move O(n/p) real payload per device inside
O(n) padded buffers — the static-shape price; prefer this path when p
is moderate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..array import tiling as tiling_mod
from ..parallel import mesh as mesh_mod
from ..parallel import redistribute as redist_mod

_SAMPLES = 64  # per-shard splitter samples (capped at shard size)


def _kernel(xs: jax.Array, axis, p: int, s: int, n: int,
            with_indices: bool = False):
    """One shard's sample sort over its ``m``-slot row of the padded
    array; ``n`` is the true (unpadded) global length, so slots with
    global index >= n form the validity channel. With ``with_indices``
    the element's global source index rides the pipeline as a sort
    payload and the function returns ``(values, indices)`` — the
    distributed argsort (padding sits at the array's end, so a valid
    element's padded index IS its original index).

    Both exchanges move fixed ``(p, m)`` ``all_to_all`` buffers: O(n)
    wire bytes per device for O(n/p) payload. ``lax.ragged_all_to_all``
    would move only the payload, but XLA:TPU lays a 1-D operand out as
    one 128-lane row per element: at 16M elements over four v5e chips
    its two temporaries took 8 GB each and the program did not fit
    (PR 21's compile against a described v5e:2x2)."""
    m = xs.shape[0]
    dt = xs.dtype
    me = jax.lax.axis_index(axis)
    gidx = me.astype(jnp.int32) * m + jnp.arange(m, dtype=jnp.int32)
    inv = (gidx >= n).astype(jnp.int32)  # 1 = padding slot
    if with_indices:
        inv_s, xs_sorted, order = jax.lax.sort(
            (inv, xs, jnp.arange(m, dtype=jnp.int32)), num_keys=2)
        src_idx = me.astype(jnp.int32) * m + order  # global indices
    else:  # plain sort: cheaper than argsort + gather
        inv_s, xs_sorted = jax.lax.sort((inv, xs), num_keys=2)
        src_idx = None
    mv = (m - jnp.sum(inv)).astype(jnp.int32)  # my valid count

    # -- splitters: s evenly-spaced samples over the valid prefix ------
    samp_idx = jnp.clip((jnp.arange(s) * mv) // s, 0, m - 1)
    samples = xs_sorted[samp_idx]
    alls = jnp.sort(jax.lax.all_gather(samples, axis, tiled=True))
    splitters = alls[jnp.arange(1, p) * s]             # (p-1,)

    def exchange(mat):
        return jax.lax.all_to_all(mat, axis, split_axis=0,
                                  concat_axis=0, tiled=True)

    # -- bucket exchange -------------------------------------------------
    # valid elements are the sorted prefix, so per-destination runs are
    # contiguous: counts/starts drive the send buffer
    dst = jnp.searchsorted(splitters, xs_sorted,
                           side="right").astype(jnp.int32)
    dst = jnp.where(inv_s == 1, p, dst)     # padding: routed nowhere
    counts = jnp.bincount(dst, length=p + 1)[:p]
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    pos = jnp.arange(m, dtype=jnp.int32) - starts[
        jnp.minimum(dst, p - 1)]
    ok = (dst < p)
    posc = jnp.where(ok, pos, m)  # padding scatters out of range
    vals = exchange(jnp.zeros((p, m), dt)
                    .at[jnp.minimum(dst, p - 1), posc]
                    .set(xs_sorted, mode="drop")).ravel()
    rvalid = exchange(jnp.zeros((p, m), jnp.int32)
                      .at[jnp.minimum(dst, p - 1), posc]
                      .set(1, mode="drop"))
    valid_key = (1 - rvalid).ravel()
    k = jnp.sum(rvalid)
    ridx = (exchange(jnp.zeros((p, m), jnp.int32)
                     .at[jnp.minimum(dst, p - 1), posc]
                     .set(src_idx, mode="drop")).ravel()
            if with_indices else None)

    # -- local merge: (invalid, value) two-key sort keeps padding last
    # even when the data itself contains +inf; indices ride as payload -
    if with_indices:
        _, bucket, bidx = jax.lax.sort(
            (valid_key, vals, ridx), num_keys=2)
    else:
        _, bucket = jax.lax.sort((valid_key, vals), num_keys=2)
        bidx = None

    # -- rebalance to even output shards --------------------------------
    ks = jax.lax.all_gather(k[None], axis, tiled=True)  # (p,)
    off = (jnp.cumsum(ks) - ks)[me]                    # my global offset
    out_starts = jnp.arange(p, dtype=ks.dtype) * m
    lo = jnp.maximum(off, out_starts)
    hi = jnp.minimum(off + k, out_starts + m)
    cnt = jnp.maximum(hi - lo, 0).astype(jnp.int32)    # (p,) chunk sizes
    st = jnp.clip((lo - out_starts), 0, m).astype(jnp.int32)
    gather_idx = jnp.clip(lo[:, None] - off + jnp.arange(m)[None, :],
                          0, p * m - 1).astype(jnp.int32)
    rchunks = exchange(bucket[gather_idx])             # (p, m)
    rcnt = exchange(cnt)
    rst = exchange(st)
    t = jnp.arange(m, dtype=jnp.int32)[None, :]
    positions = jnp.where(t < rcnt[:, None], rst[:, None] + t, m)
    out_vals = (jnp.zeros((m,), dt)
                .at[positions.ravel()].set(rchunks.ravel(), mode="drop"))
    if not with_indices:
        return out_vals
    richunks = exchange(bidx[gather_idx])
    out_idx = (jnp.zeros((m,), jnp.int32)
               .at[positions.ravel()].set(richunks.ravel(), mode="drop"))
    return out_vals, out_idx


def _padded(x: jax.Array, n: int, p: int):
    """Pad the last axis to the next multiple of ``p`` (slot count per
    shard ``m``); padded VALUES are irrelevant — the validity channel
    governs ordering and output placement."""
    m = -(-n // p)
    n_pad = m * p
    if n_pad != n:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)]
        x = jnp.pad(x, widths)
    return x, m


def _uses(mesh_axis, name) -> bool:
    """Does a Tiling axis entry involve mesh axis ``name``?"""
    if mesh_axis == name:
        return True
    return isinstance(mesh_axis, tuple) and name in mesh_axis


def collective_axis(in_tiling, mesh=None) -> str:
    """The mesh axis the sample sort communicates over: the sort
    (last) axis's existing placement when that is a real (size > 1)
    mesh axis — no reshard — else the mesh row axis.

    Shared by :func:`_run` and ``SampleSortExpr._default_tiling``
    (expr/builtins.py) so the DECLARED output tiling can never diverge
    from the kernel's actual ``out_specs`` (ADVICE round 5, finding 1:
    the declared tiling used to skip the size check and mis-clear
    tuple-sharded batch axes, causing a spurious reshard)."""
    mesh = mesh or mesh_mod.get_mesh()
    name = tiling_mod.AXIS_ROW
    if in_tiling is not None and isinstance(in_tiling.axes[-1], str) \
            and int(mesh.shape.get(in_tiling.axes[-1], 1)) > 1:
        name = in_tiling.axes[-1]
    return name


def batch_axes(in_tiling, name: str, ndim: int):
    """Leading (batch) axis shardings with any use of the collective
    axis ``name`` cleared — tuple-aware via :func:`_uses`, so a batch
    axis sharded on ``('x', 'y')`` clears when ``name`` is either.
    The companion of :func:`collective_axis` (same sharing rationale)."""
    if in_tiling is None:
        return (None,) * (ndim - 1)
    return tuple(None if _uses(a, name) else a
                 for a in in_tiling.axes[:-1])


def _run(x: jax.Array, mesh, with_indices: bool,
         in_tiling=None) -> jax.Array:
    """Shared driver for every sample-sort entry point: pad the last
    axis, pick the collective mesh axis, shard_map the (possibly
    vmapped) kernel, unpad. N-d inputs keep their BATCH-axis shardings
    (minus any use of the collective axis) — a batch-sharded array is
    never replicated to sort it."""
    from jax import shard_map

    mesh = mesh or mesh_mod.get_mesh()
    n = int(x.shape[-1])
    name = collective_axis(in_tiling, mesh)
    p = int(mesh.shape.get(name, 1))
    if p <= 1 or n == 0:
        return (jnp.argsort(x, axis=-1).astype(jnp.int32)
                if with_indices else jnp.sort(x, axis=-1))
    xp, m = _padded(x, n, p)
    batch = batch_axes(in_tiling, name, x.ndim)
    t = tiling_mod.Tiling(batch + (name,))
    xp = redist_mod.constrain(xp, t, mesh)
    s = min(_SAMPLES, m)

    def row_fn(r):
        out = _kernel(r, name, p, s, n, with_indices=with_indices)
        return out[1] if with_indices else out

    def block_fn(v):  # local block: batch axes (locally) whole
        if v.ndim == 1:
            return row_fn(v)
        rows = v.reshape((-1, m))
        return jax.vmap(row_fn)(rows).reshape(v.shape[:-1] + (m,))

    mapped = shard_map(block_fn, mesh=mesh,
                       in_specs=(t.spec(),), out_specs=t.spec())
    out = mapped(xp)
    return out[..., :n] if m * p != n else out


def sample_sort(x: jax.Array, mesh=None) -> jax.Array:
    """Sort a 1-D array of ANY length, sharded over the mesh row axis
    (ragged tails ride the validity channel). Traceable (usable under
    an outer jit)."""
    return _run(x, mesh, with_indices=False)


def sample_argsort(x: jax.Array, mesh=None) -> jax.Array:
    """Indices that sort a 1-D sharded array of any length
    (distributed argsort: global source indices ride the sample-sort
    pipeline as a sort payload)."""
    return _run(x, mesh, with_indices=True)


def sample_sort_axis(x: jax.Array, mesh=None, with_indices: bool =
                     False, in_tiling=None) -> jax.Array:
    """Sort an N-d array along its LAST axis — the 1-D kernel
    ``vmap``-ed over the (locally whole) leading axes, so the sort
    axis is never gathered and batch shardings survive. Callers
    moveaxis before/after for other axes; ``in_tiling`` names the
    operand's current layout so the collective axis follows the sort
    axis's existing placement. Indices are within-row positions
    (``jnp.argsort`` semantics)."""
    return _run(x, mesh, with_indices=with_indices,
                in_tiling=in_tiling)


def _extreme(dtype, lo: bool):
    """The dtype's most extreme value (lo=True: minimum) — the sentinel
    masking padded slots out of a top-k."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return np.bool_(not lo)
    if np.issubdtype(dt, np.floating):
        return dt.type(-np.inf if lo else np.inf)
    info = np.iinfo(dt)
    return dt.type(info.min if lo else info.max)


def distributed_topk(x: jax.Array, k: int, largest: bool = True,
                     mesh=None):
    """(values, indices) of the k largest (or smallest) elements of a
    1-D array, values sorted best-first — the reference-free analogue
    of ``lax.top_k`` at mesh scale. Per shard: a LOCAL ``lax.top_k``
    keeps k candidates; one ``all_gather`` moves the p*k candidates
    (not the array); a final top-k picks the winners, replicated on
    every device. Only k*p values + indices cross the wire. Requires
    ``k <= ceil(n/p)`` (callers route bigger k through the full sort);
    ragged lengths ride the same sentinel masking as the sample sort.
    Smallest-k runs largest-k on the ORDER-FLIPPED key (sentinel
    masked), so int dtypes need no negation."""
    from jax import shard_map

    mesh = mesh or mesh_mod.get_mesh()
    axis = tiling_mod.AXIS_ROW
    p = int(mesh.shape.get(axis, 1))
    n = int(x.shape[0])
    k = int(k)
    if not 1 <= k <= n:
        raise ValueError(f"topk needs 1 <= k <= {n}, got {k}")
    if p <= 1:
        _, idx = jax.lax.top_k(x if largest else _flip_key(x), k)
        return x[idx], idx.astype(jnp.int32)
    xp, m = _padded(x, n, p)
    if k > m:
        raise ValueError(
            f"distributed_topk requires k <= shard size {m}; got {k}")
    row = tiling_mod.row(1)
    xp = redist_mod.constrain(xp, row, mesh)
    sentinel = _extreme(x.dtype, lo=largest)

    def kern(xs):
        me = jax.lax.axis_index(axis)
        gidx = me.astype(jnp.int32) * m + jnp.arange(
            m, dtype=jnp.int32)
        valid = gidx < n
        vv = jnp.where(valid, xs, sentinel)
        # smallest-k = largest-k on the flipped ranking key; the VALUE
        # payload stays untransformed, so ints survive exactly
        key = vv if largest else _flip_key(vv)
        # INVARIANT the sentinel masking depends on: lax.top_k breaks
        # ties toward the LOWER index. Padding slots carry the
        # sentinel extreme; when real data ALSO equals the sentinel
        # (-inf with largest=True, INT_MIN, ...) the padding occupies
        # the global tail [n, n_pad), so in both this local top_k and
        # the post-gather top_k below every tied VALID slot sits at a
        # lower index than every tied padding slot — a padding
        # candidate can never displace a real sentinel-valued element,
        # and every returned index stays < n. (Shard 0 alone holds
        # >= k valid slots since k <= m <= n, so the k winners always
        # exist among valid candidates.) Tested with sentinel-extreme
        # data on a ragged last shard in tests/test_sort.py.
        lk, li = jax.lax.top_k(key, k)
        lv = vv[li]
        gk = jax.lax.all_gather(lk, axis, tiled=True)       # (p*k,)
        gv = jax.lax.all_gather(lv, axis, tiled=True)
        gi = jax.lax.all_gather(gidx[li], axis, tiled=True)
        _, win = jax.lax.top_k(gk, k)
        return gv[win][None], gi[win][None].astype(jnp.int32)

    mapped = shard_map(
        kern, mesh=mesh, in_specs=(row.spec(),),
        out_specs=(tiling_mod.Tiling((axis, None)).spec(),) * 2)
    vals, idx = mapped(xp)
    # every shard computed the same winners: shard 0's row is the answer
    return vals[0], idx[0]


def _flip_key(v: jax.Array) -> jax.Array:
    """An order-reversing, order-preserving-under-top_k transform:
    floats negate; ints flip via bitwise NOT against the unsigned
    midpoint (exact for the whole range, INT_MIN included)."""
    if np.issubdtype(np.dtype(v.dtype), np.floating):
        return -v
    return jnp.invert(v)

