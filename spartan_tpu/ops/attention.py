"""Long-context attention: blockwise, ring (sequence-parallel), Ulysses.

The reference has no attention (SURVEY.md §2.6/§5: its mechanism for "an
axis too big for one node" is tiling + shuffle). This module supplies the
first-class long-context capability the TPU build requires: the sequence
axis is sharded over the mesh and attention runs either

* :func:`blockwise_attention` — single-shard online-softmax over KV
  blocks via ``lax.scan`` (memory-efficient; the substrate),
* :func:`ring_attention` — KV shards rotate around the ring via
  ``ppermute`` while each device accumulates its queries' online softmax
  (communication overlaps compute; seq length scales with mesh size),
* :func:`ulysses_attention` — one ``all_to_all`` swaps the shard from
  the sequence axis to the head axis, local full attention, swap back.

All variants accumulate in f32 and match the dense oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from ..array.tiling import Tiling
from ..parallel import collectives as coll
from ..parallel import mesh as mesh_mod

_NEG_INF = -1e30


def dense_attention(q, k, v, causal: bool = False):
    """Oracle: plain softmax attention. q,k,v: (L, H, D)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool))
        scores = jnp.where(mask[None], scores, _NEG_INF)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w.astype(q.dtype), v)


def _online_block(q, k, v, acc, m, denom, q_off, k_off, causal):
    """One KV block of online softmax. q: (Lq,H,D); k,v: (Lk,H,D);
    acc: (Lq,H,D) f32; m, denom: (H, Lq) f32."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(q.shape[0])
        k_pos = k_off + jnp.arange(k.shape[0])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None], scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    denom = denom * corr + p.sum(axis=-1)
    pv = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v).astype(jnp.float32)
    acc = acc * corr.T[..., None] + pv
    return acc, m_new, denom


def blockwise_attention(q, k, v, block_size: int = 512,
                        causal: bool = False):
    """(L, H, D) attention scanning KV blocks; O(L * block) memory."""
    lq, h, d = q.shape
    lk = k.shape[0]
    bs = min(block_size, lk)
    pad = -lk % bs
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    nb = k.shape[0] // bs
    kb = k.reshape(nb, bs, h, d)
    vb = v.reshape(nb, bs, h, d)

    acc0 = jnp.zeros((lq, h, d), jnp.float32)
    m0 = jnp.full((h, lq), _NEG_INF, jnp.float32)
    den0 = jnp.zeros((h, lq), jnp.float32)

    def body(carry, blk):
        acc, m, den, koff = carry
        kk, vv = blk
        # padding keys sit past lk: causal=False must drop them too
        k_pos = koff + jnp.arange(bs)
        valid = k_pos < lk
        kk = jnp.where(valid[:, None, None], kk, 0.0)
        acc2, m2, den2 = _online_block(
            q, kk, vv, acc, m, den, 0, koff,
            causal) if causal else _masked_block(
                q, kk, vv, acc, m, den, valid)
        return (acc2, m2, den2, koff + bs), None

    (acc, m, den, _), _ = lax.scan(body, (acc0, m0, den0, 0), (kb, vb))
    return (acc / den.T[..., None]).astype(q.dtype)


def _masked_block(q, k, v, acc, m, denom, valid):
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, :], scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    denom = denom * corr + p.sum(axis=-1)
    pv = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v).astype(jnp.float32)
    acc = acc * corr.T[..., None] + pv
    return acc, m_new, denom


def ring_attention(q, k, v, causal: bool = False,
                   mesh_axis: str = mesh_mod.AXIS_ROW):
    """Sequence-parallel attention: (L, H, D) arrays sharded on L over
    ``mesh_axis``; KV shards rotate around the ring (ppermute) while each
    device accumulates its local queries' online softmax."""
    mesh = mesh_mod.get_mesh()
    n = mesh.shape[mesh_axis]
    l = q.shape[0]
    if l % max(n, 1):
        raise ValueError(f"sequence length {l} must divide over "
                         f"{n} devices")
    seq_t = Tiling((mesh_axis, None, None))
    spec = seq_t.spec()
    shard_l = l // n

    def kernel(ql, kl, vl):
        my = lax.axis_index(mesh_axis)
        q_off = my * shard_l
        # pcast-to-varying: these carries become device-varying once
        # the ring runs, so the initial values must be marked varying
        # too (pvary was deprecated in favor of pcast)
        acc = lax.pcast(jnp.zeros(ql.shape, jnp.float32), (mesh_axis,),
                        to="varying")
        m = lax.pcast(jnp.full((ql.shape[1], ql.shape[0]), _NEG_INF,
                               jnp.float32), (mesh_axis,), to="varying")
        den = lax.pcast(jnp.zeros((ql.shape[1], ql.shape[0]), jnp.float32),
                        (mesh_axis,), to="varying")

        def body(s, carry):
            acc, m, den, kk, vv = carry
            # block s came from device (my - s) mod n
            src = (my - s) % n
            k_off = src * shard_l
            acc, m, den = _online_block(ql, kk, vv, acc, m, den,
                                        q_off, k_off, causal)
            kk = coll.ring_permute(kk, mesh_axis, 1)
            vv = coll.ring_permute(vv, mesh_axis, 1)
            return (acc, m, den, kk, vv)

        acc, m, den, _, _ = lax.fori_loop(
            0, n, body, (acc, m, den, kl, vl))
        return (acc / den.T[..., None]).astype(ql.dtype)

    q = jax.device_put(q, seq_t.sharding(mesh))
    k = jax.device_put(k, seq_t.sharding(mesh))
    v = jax.device_put(v, seq_t.sharding(mesh))
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return jax.jit(fn)(q, k, v)


def ulysses_attention(q, k, v, causal: bool = False,
                      mesh_axis: str = mesh_mod.AXIS_ROW):
    """SP via axis swap: inputs seq-sharded (L, H, D); one all_to_all
    re-shards to head-sharded, full-sequence attention runs locally per
    head group, and the inverse all_to_all restores seq sharding."""
    mesh = mesh_mod.get_mesh()
    n = mesh.shape[mesh_axis]
    if q.shape[1] % max(n, 1):
        raise ValueError(f"head count {q.shape[1]} must divide over "
                         f"{n} devices")
    seq_t = Tiling((mesh_axis, None, None))
    spec = seq_t.spec()

    def kernel(ql, kl, vl):
        # (L/n, H, D) -> (L, H/n, D)
        qh = coll.all_to_all(ql, mesh_axis, split_axis=1, concat_axis=0)
        kh = coll.all_to_all(kl, mesh_axis, split_axis=1, concat_axis=0)
        vh = coll.all_to_all(vl, mesh_axis, split_axis=1, concat_axis=0)
        out = dense_attention(qh, kh, vh, causal)
        return coll.all_to_all(out, mesh_axis, split_axis=0, concat_axis=1)

    q = jax.device_put(q, seq_t.sharding(mesh))
    k = jax.device_put(k, seq_t.sharding(mesh))
    v = jax.device_put(v, seq_t.sharding(mesh))
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return jax.jit(fn)(q, k, v)
