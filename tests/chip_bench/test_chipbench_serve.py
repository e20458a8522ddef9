"""The serving cell ``kmeans_1m.assign_serve`` driven end to end on the
CPU at a tiny size: a sound run is correct and coalesces; the control
and each fault planted where the answer is produced come out not
correct. The limit is the cell's own (configs/kmeans_1m.json)."""

import os
import sys

import jax
import numpy as np
import pytest

HARNESS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import harness  # noqa: E402

from spartan_tpu.serve import future as future_mod  # noqa: E402

SEED = 2 ** 32 + 777
WORKLOAD = "kmeans_1m.assign_serve"


@pytest.fixture(autouse=True)
def _program_state(monkeypatch, tmp_path):
    """A run initializes the program as the benchmark does: keep what it
    sets (the persistent compile cache, span recording) out of the other
    tests this worker runs."""
    from spartan_tpu.utils.config import FLAGS

    # set: st.initialize() then leaves JAX's compilation cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = FLAGS.trace, FLAGS.trace_ring
    yield
    FLAGS.trace, FLAGS.trace_ring = saved


def run(control: bool = False, trace: bool = False) -> dict:
    cell = harness.load_cell(harness.load_bench(), WORKLOAD)
    cell.config.update(k=8)
    cell.traffic.update(clients=4, rows=64, pool_batches=8,
                        warm_batches=[1, 2, 4])
    return harness.run_cell(cell, SEED, 0.5, trace, jax.devices(),
                            control=control)


def test_sound_run_is_correct():
    line = run()
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"query_p95_ms", "queries_per_s",
                                    "setup_s"}
    assert line["attempted"] > 4 and line["failed"] == 0
    assert line["diagnostics"]["compiles_in_window"] == 0


def test_control_is_not_correct():
    assert not run(control=True)["correct"]


def _altered(glom):
    def fetch(self, timeout=None):
        ids = np.array(glom(self, timeout))
        if ids.ndim == 1:  # a query's answer, not the warm-up's sum
            ids[0] = (ids[0] + 1) % 8
        return ids
    return fetch


def _half_rows(glom):
    def fetch(self, timeout=None):
        ids = np.array(glom(self, timeout))
        if ids.ndim == 1:
            half = len(ids) // 2
            ids[half:] = ids[:half]
        return ids
    return fetch


@pytest.mark.parametrize("fault", [_altered, _half_rows])
def test_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(future_mod.EvalFuture, "glom",
                        fault(future_mod.EvalFuture.glom))
    assert not run()["correct"]
