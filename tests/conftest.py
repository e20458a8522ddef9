"""Test harness: the full mesh/collective path on 8 virtual CPU devices.

The reference's test pattern was master + 3-4 workers as local subprocesses
on localhost ZeroMQ (SURVEY.md §4); the TPU analogue is CPU JAX with
``--xla_force_host_platform_device_count=8`` so every 'distributed' test
runs multi-device on one machine.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU, chip or not
# Optimizer-pass invariant checking is ON by default under pytest
# (analysis/passes.py): every pass in every test run is bracketed by
# the shape/dtype/leaf/well-formedness checker. Export =0 to disable.
os.environ.setdefault("SPARTAN_VERIFY_PASSES", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The XLA:CPU async dispatch thread intermittently deadlocks (futex
# wait at init/exit/mid-run) when 8 virtual devices share ONE physical
# core — observed freezing whole suite runs at random points. Tests
# are correctness checks, not throughput: synchronous dispatch costs a
# little latency and removes the lottery.
jax.config.update("jax_cpu_enable_async_dispatch", False)
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_persist_cache(request, tmp_path_factory):
    """Warm-start store isolation: FLAGS.persist_cache_dir (and the
    process-level store singleton behind it) must never leak state
    between tests — a shared directory would let one test's persisted
    executables satisfy another test's cache misses. If the flag is
    set (an env override, or a prior test's leftovers), rebind it to a
    fresh per-test tmpdir; always drop the store singleton + digest
    memo afterwards. Tests that point the flag at their own tmp_path
    are unaffected (their explicit set wins inside the test body)."""
    from spartan_tpu import persist
    from spartan_tpu.utils.config import FLAGS

    prev = FLAGS.persist_cache_dir
    if prev:
        FLAGS.persist_cache_dir = str(
            tmp_path_factory.mktemp("persist_cache"))
        persist.reset()
    yield
    if FLAGS.persist_cache_dir != prev:
        FLAGS.persist_cache_dir = prev
    persist.reset()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh2d():
    """4x2 (x, y) mesh over the 8 virtual devices, installed as ambient."""
    from spartan_tpu.parallel import mesh as mesh_mod

    m = mesh_mod.build_mesh(jax.devices(), shape=(4, 2))
    with mesh_mod.use_mesh(m):
        yield m


@pytest.fixture()
def mesh1d():
    """8x1 mesh — pure row tiling."""
    from spartan_tpu.parallel import mesh as mesh_mod

    m = mesh_mod.build_mesh(jax.devices(), shape=(8, 1))
    with mesh_mod.use_mesh(m):
        yield m
