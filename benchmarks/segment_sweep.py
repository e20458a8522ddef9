"""Where ``ops.segment.segment_sum`` stops summing by one-hot product.

Times, for float32 values over ``n`` rows, XLA's scatter
(``jax.ops.segment_sum``) against the one-hot contraction
(``ops.segment._onehot_sum``) at each segment count ``k`` and width
``d``, and reports the largest ``k`` of the sweep at which the one-hot
form wins at every width: the value of ``_ONEHOT_MAX_SEGMENTS``. Each
time is the wall time of ``--reps`` calls dispatched back to back and
blocked on once, over the call count, after a compiled warm-up; ids are
uniform over the ``k`` segments, values normal, both made on the device.

On one chip, from the repository root:
  python benchmarks/segment_sweep.py --out chiprun_out/segment_sweep.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from spartan_tpu.ops import segment  # noqa: E402


def _time_ms(fn, vals, ids, reps: int) -> float:
    jax.block_until_ready(fn(vals, ids))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(vals, ids)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def sweep(n: int, widths, counts, reps: int) -> dict:
    rows = []
    for d in widths:
        shape = (n, d) if d > 1 else (n,)
        vals = jax.random.normal(jax.random.PRNGKey(d), shape, jnp.float32)
        for k in counts:
            ids = jax.random.randint(jax.random.PRNGKey(k), (n,), 0, k)
            forms = {
                "scatter": jax.jit(lambda v, i, k=k: jax.ops.segment_sum(
                    v, i, num_segments=k)),
                "onehot": jax.jit(lambda v, i, k=k: segment._onehot_sum(
                    v, i, k)),
            }
            row = {"d": d, "k": k}
            for name, fn in forms.items():
                row[name + "_ms"] = _time_ms(fn, vals, ids, reps)
            print(json.dumps(row), flush=True)
            rows.append(row)
    wins = [k for k in counts
            if all(r["onehot_ms"] < r["scatter_ms"]
                   for r in rows if r["k"] == k)]
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "n": n, "reps": reps, "rows": rows,
            "bound": max(wins) if wins else 0}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--widths", default="1,128")
    ap.add_argument("--counts", default="16,64,256,1024,4096")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    rep = sweep(a.n, [int(x) for x in a.widths.split(",")],
                [int(x) for x in a.counts.split(",")], a.reps)
    print(json.dumps({"bound": rep["bound"],
                      "device_kind": rep["device_kind"]}))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rep, f, indent=1)


if __name__ == "__main__":
    main()
