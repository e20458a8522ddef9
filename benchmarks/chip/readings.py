"""Readings for the limits of a cell's comparison: the program on many
seeds and the control (the plain reference at the next precision down,
in the program's place) on a few, all in one process so set-up and
compilation are paid once. Not run by the benchmark.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 3

Prints one JSON line per run: which side, the seed, ``correct`` and
every number compared beside its limit.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]


def _seeds(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy each traced run's capture here")
    args = ap.parse_args(argv)

    # a cache the machine brings carries compiled code across calls
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        harness.configure_env()
    import jax

    cell = harness.load_cell(harness.load_bench(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("readings: no TPU", file=sys.stderr)
        return 2
    runs = ([("program", s) for s in args.seeds]
            + [("control", s) for s in args.control_seeds])
    for side, seed in runs:
        t0 = time.perf_counter()
        line = harness.run_cell(cell, seed, args.seconds, bool(args.trace),
                                devs, control=side == "control",
                                keep_trace=args.keep_trace)
        print(json.dumps({"side": side, "seed": seed,
                          "wall_s": time.perf_counter() - t0, **line}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
