"""Long-context attention + collectives tests: ring and Ulysses vs the
dense oracle on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spartan_tpu.ops.attention import (blockwise_attention, dense_attention,
                                       ring_attention, ulysses_attention)
from spartan_tpu.parallel import collectives as coll
from spartan_tpu.parallel import mesh as mesh_mod
from spartan_tpu.array.tiling import Tiling


def _qkv(l=64, h=8, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(l, h, d).astype(np.float32) * 0.3
                 for _ in range(3))


def test_blockwise_matches_dense(mesh1d):
    q, k, v = _qkv()
    dense = np.asarray(jax.jit(dense_attention)(q, k, v))
    block = np.asarray(jax.jit(
        lambda a, b, c: blockwise_attention(a, b, c, block_size=16))(
            q, k, v))
    np.testing.assert_allclose(block, dense, rtol=2e-4, atol=2e-5)


def test_blockwise_causal_and_uneven(mesh1d):
    q, k, v = _qkv(l=60)
    dense = np.asarray(jax.jit(
        lambda a, b, c: dense_attention(a, b, c, causal=True))(q, k, v))
    block = np.asarray(jax.jit(
        lambda a, b, c: blockwise_attention(a, b, c, block_size=16,
                                            causal=True))(q, k, v))
    np.testing.assert_allclose(block, dense, rtol=2e-4, atol=2e-5)


def test_ring_attention(mesh1d):
    q, k, v = _qkv(l=64, seed=1)
    dense = np.asarray(jax.jit(dense_attention)(q, k, v))
    ring = np.asarray(ring_attention(q, k, v))
    np.testing.assert_allclose(ring, dense, rtol=2e-4, atol=2e-5)


def test_ring_attention_causal(mesh1d):
    q, k, v = _qkv(l=64, seed=2)
    dense = np.asarray(jax.jit(
        lambda a, b, c: dense_attention(a, b, c, causal=True))(q, k, v))
    ring = np.asarray(ring_attention(q, k, v, causal=True))
    np.testing.assert_allclose(ring, dense, rtol=2e-4, atol=2e-5)


def test_ring_rejects_indivisible(mesh1d):
    q, k, v = _qkv(l=60)
    with pytest.raises(ValueError):
        ring_attention(q, k, v)


def test_ulysses_attention(mesh1d):
    q, k, v = _qkv(l=64, h=8, seed=3)
    dense = np.asarray(jax.jit(dense_attention)(q, k, v))
    out = np.asarray(ulysses_attention(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-4, atol=2e-5)


def test_ulysses_causal(mesh1d):
    q, k, v = _qkv(l=64, h=8, seed=4)
    dense = np.asarray(jax.jit(
        lambda a, b, c: dense_attention(a, b, c, causal=True))(q, k, v))
    out = np.asarray(ulysses_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out, dense, rtol=2e-4, atol=2e-5)


def test_collectives_in_shard_map(mesh1d):
    from jax import shard_map

    mesh = mesh_mod.get_mesh()
    x = np.arange(8, dtype=np.float32)
    t = Tiling(("x",))

    def kern(v):
        total = coll.all_reduce(v, "x")
        gathered = coll.all_gather(v, "x")
        rotated = coll.ring_permute(v, "x", 1)
        return total + gathered.sum() + rotated

    xs = jax.device_put(x, t.sharding(mesh))
    out = jax.jit(shard_map(kern, mesh=mesh, in_specs=(t.spec(),),
                            out_specs=t.spec()))(xs)
    expect = x.sum() + x.sum() + np.roll(x, 1)
    np.testing.assert_allclose(np.asarray(out), expect)


def test_ulysses_swap_roundtrip(mesh1d):
    x = np.random.RandomState(5).rand(64, 8, 4).astype(np.float32)
    swapped = coll.ulysses_swap(jnp.asarray(x), seq_axis=0, head_axis=1)
    np.testing.assert_allclose(np.asarray(swapped), x, rtol=1e-6)
    # head-sharded now
    assert swapped.sharding.spec[1] == "x" or swapped.sharding.spec == (
        None, "x", None)


def test_reshard(mesh1d):
    x = np.random.RandomState(6).rand(8, 8).astype(np.float32)
    arr = coll.reshard(jnp.asarray(x), Tiling(("x", None)))
    arr2 = coll.reshard(arr, Tiling((None, None)))
    np.testing.assert_array_equal(np.asarray(arr2), x)
