"""Pallas kernel layer: tiling->grid derivation property tests over
the tiling vocabulary, the k-means kernel's selection fallback reasons,
and its CPU interpret-mode parity. docs/KERNELS.md documents the
contracts asserted here."""

import numpy as np
import pytest

from spartan_tpu.array import tiling
from spartan_tpu.kernels import registry as kreg
from spartan_tpu.parallel import mesh as mesh_mod

jax = mesh_mod.jax
jnp = jax.numpy


VOCAB = [tiling.replicated, tiling.row, tiling.col, tiling.block,
         tiling.row_t, tiling.col_t, tiling.block_t, tiling.flat_row]


@pytest.fixture()
def pallas(monkeypatch):
    """Kernel selection as on the chip; the kernels run in interpret
    mode here."""
    monkeypatch.setattr(kreg, "mode", lambda: "pallas")


# -- tiling -> grid derivation ---------------------------------------


def test_derive_property_over_vocabulary(mesh2d):
    """Every divisible Tiling over the vocabulary produces a grid
    whose blocks cover the shard exactly: no empty trailing block, no
    row covered twice, padding bounded by one quantum."""
    mesh = mesh_mod.get_mesh()
    shapes = [(8,), (1000,), (4096,), (64, 256), (40, 16), (12, 24),
              (128, 128), (16, 8, 4)]
    checked = 0
    for shape in shapes:
        for tf in VOCAB:
            t = tf(len(shape))
            tiles = t.tiles_per_dim(mesh)
            divisible = all(d % n == 0 for d, n in zip(shape, tiles)
                            if n > 1)
            for dt in (np.float32, np.int32):
                sched, why = kreg.derive(shape, t, dt, mesh)
                if not divisible:
                    assert sched is None
                    assert "divide" in why
                    continue
                checked += 1
                shard = tuple(d // n for d, n in zip(shape, tiles))
                rows = (-(-shard[0] // kreg.LANE) if len(shard) == 1
                        else shard[0])
                grid = sched.grid[0]
                brows = sched.block[0]
                # blocks cover the shard rows exactly: the last block
                # is non-empty and no block is wholly padding
                assert grid * brows >= rows
                assert (grid - 1) * brows < rows
                assert sched.padded[0] == grid * brows
                # quantization: sublane rows, lane-multiple last dim
                assert brows % kreg.sublane(dt) == 0
                assert sched.block[-1] % kreg.LANE == 0
                assert sched.block[-1] >= (kreg.LANE if sched.lifted
                                           else shard[-1])
                # padding never exceeds one block of rows + one lane
                # tile of columns — nothing for a kernel to re-count
                assert sched.padded[0] - rows < brows
                assert sched.block[-1] - (kreg.LANE if sched.lifted
                                          else shard[-1]) < kreg.LANE
    assert checked > 20  # the vocabulary actually got exercised


def test_derive_indivisible_falls_back_with_reason(mesh1d):
    mesh = mesh_mod.get_mesh()
    sched, why = kreg.derive((10,), tiling.row(1), np.float32, mesh)
    assert sched is None and "divide" in why


@pytest.mark.parametrize("n,d,k,dtype,why", [
    (8 * 1024, 128, 8, np.float16, "dtype"),
    (8 * 1024, 100, 8, np.float32, "multiple of 128"),
    (8 * 1024, 128, 200, np.float32, "> 128"),
    (1025 * 8 + 1, 128, 8, np.float32, "divisible"),
    (8 * 1000, 128, 8, np.float32, "point block"),
], ids=["dtype", "d", "k", "shards", "block"])
def test_select_gating_and_fallback_reasons(mesh1d, pallas, n, d, k,
                                            dtype, why):
    """Each constraint of the k-means kernel falls back to the XLA
    path with its reason recorded."""
    sel = kreg.select("kmeans", (n, d), dtype, tiling.row(2), k=k,
                      block=1024)
    assert sel.backend == "gspmd" and why in sel.reason, sel.reason


def test_kmeans_sharded_kernel_parity(mesh1d, pallas):
    from spartan_tpu.kernels import kmeans as kk

    n, d, k = 8 * 1024, 128, 8
    assert kk.supports(n, d, k)
    rng = np.random.RandomState(8)
    pts = rng.rand(n, d).astype(np.float32)
    cen = pts[:k].copy()
    sums, cnt = kk.assign_accumulate(jnp.asarray(pts),
                                     jnp.asarray(cen), k)
    d2 = ((pts ** 2).sum(1)[:, None] - 2 * pts @ cen.T
          + (cen ** 2).sum(1)[None, :])
    a = d2.argmin(1)
    es = np.zeros((k, d), np.float32)
    np.add.at(es, a, pts)
    np.testing.assert_allclose(np.asarray(sums), es, rtol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(a, minlength=k))
    # per-shard validity masking (driver padding)
    nv = n - 700
    s2, c2 = kk.assign_accumulate(jnp.asarray(pts), jnp.asarray(cen),
                                  k, valid_rows=nv)
    es2 = np.zeros((k, d), np.float32)
    np.add.at(es2, a[:nv], pts[:nv])
    np.testing.assert_allclose(np.asarray(s2), es2, rtol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(c2), np.bincount(a[:nv], minlength=k))


def test_kmeans_supports_respects_policy(mesh1d, monkeypatch):
    """Pallas on TPU only; the constraints are the parametrised cases
    of test_select_gating_and_fallback_reasons."""
    from spartan_tpu.kernels import kmeans as kk

    assert not kk.supports(8 * 1024, 128, 8)  # CPU: the XLA path
    monkeypatch.setattr(kreg, "mode", lambda: "pallas")
    assert kk.supports(8 * 1024, 128, 8)      # multi-shard, parity
