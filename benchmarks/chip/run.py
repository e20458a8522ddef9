"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

There is no CPU path: without as many TPU chips as the cell asks for it
exits non-zero and prints no result.
"""

import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

if __name__ == "__main__":
    import harness

    harness.configure_env()
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
